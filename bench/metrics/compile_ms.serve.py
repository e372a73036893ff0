"""Host milliseconds per answered request that the backend spent compiling
inside the window: JAX's own ``backend_compile_duration`` events, which the
harness records with the time each ended, summed over the window and
divided by the requests answered. The persistent compile cache is off in the
window, so every program the service meets there for the first time in this
process is compiled there, as it is for data a deployment has never seen.
Tracing and lowering to MLIR are not in it."""

EVENT = "/jax/core/compile/backend_compile_duration"


def read(ctx):
    if ctx.completed == 0:
        return None
    t0, t1 = ctx.window.t0, ctx.window.t1
    s = sum(d for t, name, d in ctx.compiles if name == EVENT and t0 <= t <= t1)
    return s / ctx.completed * 1e3
