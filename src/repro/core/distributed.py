"""Pod-scale distributed sparse HOOI (shard_map data-parallel form).

The paper's PCIe CPU<->FPGA offload becomes a data-parallel collective
dataflow on the TPU mesh:

  * nonzeros (COO rows) are sharded across the data-parallel axes
    (``("pod", "data")`` on the production mesh) — each device owns a slice
    of the nonzeros, padded with explicit zeros for even sharding;
  * factor matrices are replicated (they are small: I_n x R_n);
  * each device runs the Kron-accumulation over its local nonzeros to get a
    *partial* Y_(n); a single ``psum`` over the nnz axes completes the sum
    (the scatter-add is linear in the nonzeros, so partial sums commute);
  * the QRP factor update runs replicated on every device (deterministic:
    identical inputs -> identical U_n everywhere, no broadcast needed).

The per-sweep communication is N psums of I_n x prod(R_t) f32 — independent
of nnz, which is exactly why the scheme scales to thousands of nodes: compute
scales with nnz/devices while collective bytes stay constant
(:func:`psum_bytes_per_sweep` is that invariant as a number, reported per
call as ``TuckerResult.collective_bytes_per_sweep``).

The execution path lives in the plan/execute pipeline: a
:class:`~repro.tucker.spec.TuckerSpec` with ``shard=ShardSpec(...)`` compiles
the whole multi-sweep loop as ONE shard_map-wrapped scan program
(``core.hooi.build_sharded_program``). This module keeps the collective byte
count and the nonzero sharding helper.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import numpy as np

from repro.core.coo import SparseCOO
from repro.sparse.layout import build_shard_schedule


def psum_bytes_per_sweep(
    shape: Sequence[int], ranks: Sequence[int], dtype=np.float32
) -> int:
    """Collective payload of one ALS sweep: N psums, one per mode, each of
    the full partial unfolding Y_(n) — I_n x prod_{t != n} R_t elements at
    the program's working precision (f32, or f64 under the x64 flag). The
    quantity is independent of nnz (the scaling invariant of the scheme)."""
    shape, ranks = tuple(shape), tuple(ranks)
    itemsize = int(np.dtype(dtype).itemsize)
    total = 0
    for mode, dim in enumerate(shape):
        k = int(np.prod([r for t, r in enumerate(ranks) if t != mode]))
        total += dim * k * itemsize
    return total


def shard_nonzeros(
    coo: SparseCOO, mesh: jax.sharding.Mesh, nnz_axes: Tuple[str, ...]
) -> SparseCOO:
    """Pad nnz to a multiple of the nnz-axis size and device_put the COO
    arrays sharded on their leading (nnz) dimension.

    Validates that every ``nnz_axes`` name is a mesh axis up front (a missing
    name used to surface as an opaque ``KeyError`` deep in ``device_put``).
    The padding math and the one-time ``device_put`` live in
    :func:`repro.sparse.layout.build_shard_schedule`, shared with the
    plan/execute pipeline's :class:`~repro.sparse.layout.ShardSchedule`.
    """
    sched = build_shard_schedule(coo, mesh, tuple(nnz_axes))
    return SparseCOO(sched.indices, sched.values, coo.shape)
