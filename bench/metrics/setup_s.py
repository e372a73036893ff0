"""Set-up seconds: process start to the window's start (inputs from the
seed, schedules, compile or compile-cache load, warm-up)."""


def read(ctx):
    return ctx.setup_s
