"""Jit'd public wrappers over the Pallas kernels.

``interpret`` defaults to True exactly when the backend is not a TPU: off the
chip the kernels validate in interpret mode, and on a TPU the same call sites
compile to Mosaic.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stages
from repro.core.coo import SparseCOO
from repro.kernels import kron_kernel, ttm_kernel
from repro.kernels.kron_kernel import ScatterPlan, build_scatter_plan


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def ttm(y: jax.Array, u: jax.Array, *, bl: Optional[int] = None, bk: Optional[int] = None,
        interpret: Optional[bool] = None, precision: str = "fp32") -> jax.Array:
    """Paper TTM module: G = Y @ U^T (Eq. 12) via the Pallas kernel."""
    kw = {}
    if bl is not None:
        kw["bl"] = bl
    if bk is not None:
        kw["bk"] = bk
    return ttm_kernel.ttm_pallas(
        y, u, interpret=default_interpret() if interpret is None else interpret,
        precision=precision, **kw
    )


def kron_contrib(a: jax.Array, b: jax.Array, v: jax.Array, *,
                 bn: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 precision: str = "fp32") -> jax.Array:
    """Paper Kronecker module (Alg. 4) over a batch of nonzeros."""
    kw = {} if bn is None else {"bn": bn}
    return kron_kernel.kron_contrib_pallas(
        a, b, v, interpret=default_interpret() if interpret is None else interpret,
        precision=precision, **kw
    )


def sparse_ttm_chain_kernel(
    coo: SparseCOO,
    factors: Sequence[jax.Array],
    skip_mode: int,
    plan: Optional[ScatterPlan] = None,
    *,
    interpret: Optional[bool] = None,
    fused: bool = True,
) -> jax.Array:
    """Full Alg. 2 line 5 on the kernel path.

    3-way tensors (the paper's case) run the fused kron-contrib→one-hot-
    scatter pipeline in a single kernel; higher orders fall back to chained
    ``kron_contrib`` calls followed by the standalone scatter kernel.

    The ``plan`` — a ``ScatterPlan`` or a ``sparse.layout.SortedCOO`` (the
    engine's richer schedule, same fields) — plays the role of the paper's
    FPGA dataflow schedule; build it once per (tensor, mode) and reuse
    across sweeps. ``hooi_sparse(..., engine="pallas")`` does exactly that
    via ``core.engine.SweepEngine``.
    """
    interp = default_interpret() if interpret is None else interpret
    if coo.nnz and plan is None:
        plan = build_scatter_plan(
            np.asarray(coo.indices[:, skip_mode]), coo.shape[skip_mode]
        )
    # one implementation: the schedule fields index identically whether they
    # are host numpy (a ScatterPlan / SortedCOO) or device arrays.
    return sparse_ttm_chain_device(
        coo.indices, coo.values, factors, skip_mode, plan,
        shape=tuple(coo.shape), interpret=interp, fused=fused,
    )


def _gathered_block_rows(indices, values, factors, skip_mode, sched, n):
    """Gather the non-mode factor rows in the schedule's block order (padding
    slots gather row 0 with value 0). Shared by the unfolding chain and the
    fused core update, with identical operands on purpose: when both run in
    one program (the megakernel re-streams the same nonzeros the mode-(N-1)
    unfolding just consumed), XLA CSEs the gathers instead of re-reading."""
    with jax.named_scope(stages.ORDER_GATHER):
        idx = indices[sched.order]
        vals = values[sched.order] * sched.valid
    modes = [t for t in range(n - 1, -1, -1) if t != skip_mode]
    with jax.named_scope(stages.ROW_GATHER):
        rows = [factors[t][idx[:, t]] for t in modes]
        if len(rows) == 1:  # order-2 tensor: the "Kron row" is a single factor row
            rows.append(jnp.ones((rows[0].shape[0], 1), dtype=rows[0].dtype))
    return rows, vals


def sparse_ttm_chain_device(
    indices: jax.Array,
    values: jax.Array,
    factors: Sequence[jax.Array],
    skip_mode: int,
    sched,
    *,
    shape: Sequence[int],
    interpret: bool,
    fused: bool = True,
    precision: str = "fp32",
) -> jax.Array:
    """Trace-safe twin of :func:`sparse_ttm_chain_kernel` for the compiled
    scan-over-sweeps pipeline: the schedule (``sched``, a
    ``sparse.layout.DeviceSchedule``) is already device-resident, ``shape`` /
    ``interpret`` are static, and no numpy or host sync happens — safe to
    call under ``jit`` / ``lax.scan`` / ``lax.cond``.
    """
    n = len(shape)
    n_rows = int(shape[skip_mode])
    if indices.shape[0] == 0:
        from repro.core.kron import zero_unfolding

        return zero_unfolding(tuple(shape), factors, skip_mode)
    rows, vals = _gathered_block_rows(indices, values, factors, skip_mode, sched, n)
    with jax.named_scope(stages.KRON):
        if len(rows) == 2 and fused:
            return kron_kernel.fused_kron_scatter_pallas(
                rows[0], rows[1], vals, sched, n_rows, interpret=interpret,
                precision=precision,
            )
        contrib = kron_contrib(
            rows[0], rows[1], vals, interpret=interpret, precision=precision
        )
        for extra in rows[2:]:
            contrib = kron_contrib(contrib, extra, jnp.ones_like(vals), interpret=interpret)
        return kron_kernel.scatter_rows_pallas(contrib, sched, n_rows, interpret=interpret)


def sparse_ttm_core_device(
    indices: jax.Array,
    values: jax.Array,
    factors: Sequence[jax.Array],
    skip_mode: int,
    sched,
    *,
    shape: Sequence[int],
    interpret: bool,
    precision: str = "fp32",
) -> jax.Array:
    """Fused core update (Eq. 12): G_(N) = U_N^T Y_(N) WITHOUT materializing
    Y_(N) — the megakernel re-streams the nonzeros through the Kron→scatter
    pipeline into VMEM scratch and contracts each finished row block against
    the (just updated) factor in the same grid step. The gathers match the
    mode-``skip_mode`` unfolding's exactly, so inside one compiled sweep XLA
    dedups them; the (I_n x K) unfolding itself never crosses HBM a second
    time. Returns (R_N, prod_{t != skip} R_t) f32.

    Orders > 3 fall back to the split path (chained Kron + blocked TTM): the
    megakernel streams exactly two operand blocks, the paper's case.
    """
    n = len(shape)
    n_rows = int(shape[skip_mode])
    u = factors[skip_mode]
    if indices.shape[0] == 0:
        from repro.core.kron import zero_unfolding

        y0 = zero_unfolding(tuple(shape), factors, skip_mode)
        return jnp.zeros((u.shape[1], y0.shape[1]), dtype=jnp.float32)
    rows, vals = _gathered_block_rows(indices, values, factors, skip_mode, sched, n)
    if len(rows) == 2:
        # the megakernel's Kron accumulation and its TTM are one kernel,
        # counted with the Kron kernels
        with jax.named_scope(stages.KRON):
            return kron_kernel.fused_kron_scatter_ttm_pallas(
                rows[0], rows[1], vals, u, sched, n_rows, interpret=interpret,
                precision=precision,
            )
    y = sparse_ttm_chain_device(
        indices, values, factors, skip_mode, sched,
        shape=shape, interpret=interpret, precision=precision,
    )
    with jax.named_scope(stages.CORE):
        return ttm(y.T, u.T, interpret=interpret, precision=precision).T


def flash_attention(q, k, v, *, causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Blockwise (FlashAttention-style) causal GQA attention kernel."""
    from repro.kernels import flash_attention as fa

    return fa.flash_attention_pallas(
        q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=default_interpret() if interpret is None else interpret,
    )


def ssd_chunk(x, a_cumsum, b_mat, c_mat, *, interpret: Optional[bool] = None):
    """Mamba-2 SSD within-chunk kernel (diag block + outgoing chunk state)."""
    from repro.kernels import ssd_scan

    return ssd_scan.ssd_chunk_pallas(
        x, a_cumsum, b_mat, c_mat,
        interpret=default_interpret() if interpret is None else interpret,
    )
