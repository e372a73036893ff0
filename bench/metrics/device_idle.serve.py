"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (1 - union of busy intervals / window)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share()
