"""Median time a request waited in the service's queue (submit to dequeue,
``RequestTiming.queue_ms``) over the window's answered requests."""
import numpy as np


def read(ctx):
    q = [r.answer.timing.queue_ms for r in ctx.window.records
         if r.error is None and getattr(r.answer, "timing", None) is not None]
    return float(np.median(q)) if q else None
