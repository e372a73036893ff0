"""Median latency of all requests of the window, each from its due time to
its answer; a failed request counts as the window's length."""
import numpy as np


def read(ctx):
    lat = ctx.window.latencies_s()
    return float(np.percentile(lat, 50)) * 1e3 if lat.size else None
