"""Share of the roofline reached by the Kronecker-accumulation kernels:
the least time the chip could take for the accumulation's work, over the
kernels' measured time (``kron_ms.decompose``).

The work is the algorithm's, from the shape, the ranks and the nonzeros,
not from any kernel's implementation, so a later kernel that does the same
work reads against the same count. For mode n, with ``K = prod_{t != n}
R_t``, every nonzero needs its Kronecker row of the other modes' factor rows,
one scale by its value and one add into ``Y_(n)``:

* operations: ``nnz * (b + 2K)``, where ``b`` counts the multiplies that
  build the row (``K`` for three modes);
* bytes, the least any implementation must move: each nonzero's indices and
  value once (``4 * (N + 1)``), each other factor matrix read once, and
  ``Y_(n)`` written once, all at 4 bytes.

Summed over the modes and the ``n_iter`` sweeps of one decomposition, at
the chip's peaks (``bench/peaks.json``). At nell-2's shape and ranks the
work is about 48 operations per byte against the v5e's 240, so the memory
bound applies.
"""
from pathlib import Path

import numpy as np


def mode_work(shape, ranks, nnz: int, mode: int):
    """``(operations, bytes)`` of one mode's Kronecker accumulation."""
    others = [r for t, r in enumerate(ranks) if t != mode]
    k = int(np.prod(others))
    build, acc = 0, others[0]
    for r in others[1:]:
        acc *= r
        build += acc
    ops = nnz * (build + 2 * k)
    nbytes = 4 * (nnz * (len(shape) + 1)
                  + sum(shape[t] * ranks[t] for t in range(len(shape)) if t != mode)
                  + shape[mode] * k)
    return ops, nbytes


def least_seconds(shape, ranks, nnz: int, n_iter: int, peaks: dict) -> float:
    ops = nbytes = 0
    for mode in range(len(shape)):
        o, b = mode_work(shape, ranks, nnz, mode)
        ops += o
        nbytes += b
    return n_iter * max(ops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def read(ctx):
    from bench.harness import reader

    kron_ms = reader("kron_ms.decompose", Path(__file__).resolve().parents[2]).read(ctx)
    if not kron_ms:
        return None
    c = ctx.config
    t = least_seconds(c["shape"], c["ranks"], int(c["nnz"]), int(c["n_iter"]), ctx.peaks)
    return 100.0 * t / (kron_ms / 1e3)
