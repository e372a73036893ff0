"""Device milliseconds per decomposition in which an all-reduce ran and no
other operation on that chip ran, averaged over the cell's chips: the
exposed time of the sharded program's psum of each mode's ``Y_(n)``.

An all-reduce is selected by its opcode in the operation's HLO text:
``all-reduce(``, or the ``all-reduce-start(`` / ``all-reduce-done(`` pair
of an asynchronous one. On a v5e 2x2 host the sharded nell-2 program runs
three synchronous ``all-reduce(`` operations a sweep, one per mode, named
``psum.18``, ``psum.19`` and ``psum.20`` after the JAX primitive, and no
asynchronous pair; nothing else runs beside them, so all of their time is
exposed. Reads nothing without a trace, or where no all-reduce ran (a
one-chip cell)."""
import re

OPCODE = re.compile(r"(?<![-\w.%])all-reduce(?:-start|-done)?\(")


def matches(hlo: str) -> bool:
    return bool(OPCODE.search(hlo.split(" = ", 1)[-1]))


def read(ctx):
    if ctx.trace is None or ctx.completed == 0 or ctx.trace.op_seconds(matches) == 0:
        return None
    s = sum(ctx.trace.exposed_seconds(matches)) / ctx.trace.n_devices
    return s / ctx.completed * 1e3
