"""Host milliseconds per answered request that JAX spent tracing programs
to jaxprs and lowering them to MLIR inside the window: the program's
``jit.trace`` and ``jit.lower`` spans (``repro.obs``, recorded from JAX's
own compile-stage events) that start in the window, as the union of their
intervals on each thread (a jit traced inside another's trace counts
once), summed over the threads and divided by the requests answered. The
backend compile that follows is ``compile_ms.serve``. A program without
these spans gives nothing."""
from collections import defaultdict

import numpy as np

from bench import trace

NAMES = ("jit.trace", "jit.lower")


def read(ctx):
    if ctx.spans is None or ctx.completed == 0:
        return None
    t0, t1 = ctx.window.t0, ctx.window.t1
    per_thread = defaultdict(list)
    for s in ctx.spans:
        if s.name in NAMES and t0 <= s.t0 <= t1:
            per_thread[s.thread_id].append((s.t0, s.t1))
    if not per_thread:
        return None
    total = sum(trace.measure(trace.union(np.asarray(iv, dtype=np.float64)))
                for iv in per_thread.values())
    return total / ctx.completed * 1e3
