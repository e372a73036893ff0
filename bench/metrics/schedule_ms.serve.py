"""Host milliseconds per answered request spent building and uploading the
per-tensor schedules (the program's ``engine.schedule.build`` and
``engine.schedule.upload`` spans, outermost only, in the window)."""

NAMES = ("engine.schedule.build", "engine.schedule.upload")


def read(ctx):
    if ctx.spans is None or ctx.completed == 0:
        return None
    t0, t1 = ctx.window.t0, ctx.window.t1
    ours = {s.span_id: s for s in ctx.spans if s.name in NAMES}
    total = sum(s.t1 - s.t0 for s in ours.values()
                if s.parent_id not in ours and t0 <= s.t0 <= t1)
    return total / ctx.completed * 1e3
