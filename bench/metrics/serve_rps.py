"""Requests answered per second: those completed over the time from the
window's start to the last answer."""


def read(ctx):
    if ctx.completed == 0:
        return None
    return ctx.completed / (ctx.window.t1 - ctx.window.t0)
