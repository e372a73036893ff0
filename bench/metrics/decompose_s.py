"""Seconds per decomposition: the window's wall time, up to the end of the
last call, over the decompositions completed in it."""


def read(ctx):
    if ctx.completed == 0:
        return None
    return (ctx.window.t1 - ctx.window.t0) / ctx.completed
