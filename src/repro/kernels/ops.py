"""Jit'd public wrappers over the Pallas kernels.

``interpret`` defaults to True exactly when the backend is not a TPU: off the
chip the kernels validate in interpret mode, and on a TPU the same call sites
compile to Mosaic.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

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
    precision: str = "fp32",
) -> jax.Array:
    """Full Alg. 2 line 5 on the kernel path.

    3-way tensors (the paper's case) run the fused kron-contrib→one-hot-
    scatter pipeline in a single kernel; higher orders fall back to chained
    ``kron_contrib`` calls followed by the standalone scatter kernel.

    The ``plan`` — a ``ScatterPlan`` or a ``sparse.layout.SortedCOO`` (the
    engine's richer schedule, same fields) — plays the role of the paper's
    FPGA dataflow schedule; build it once per (tensor, mode) and reuse
    across sweeps, as ``core.engine.SweepEngine`` does for a plan with
    ``engine="pallas"``.
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
        shape=tuple(coo.shape), interpret=interp, fused=fused, precision=precision,
    )


class SortedNonzeros(NamedTuple):
    """One mode's nonzeros in its schedule's block order: what the Kron
    kernels stream, minus the factor rows. ``coords[i]`` is the coordinate
    of non-mode ``_row_modes(n, skip_mode)[i]`` of each slot; padding slots
    read coordinate 0 with value 0. Every field is 1-D, ``(P,)``: a
    ``(P, N)`` index array would be tiled to a padded lane width on a TPU."""

    values: jax.Array  # (P,) values[order] * valid
    coords: Tuple[jax.Array, ...]  # (P,) int32 each


def _row_modes(n: int, skip_mode: int) -> list:
    """The non-mode modes in the order the Kron kernels take their rows."""
    return [t for t in range(n - 1, -1, -1) if t != skip_mode]


def order_gather(indices, values, sched, skip_mode) -> SortedNonzeros:
    """Permute the nonzeros into the mode's schedule order (``sched`` a
    ``ScatterPlan``, ``SortedCOO`` or ``DeviceSchedule``). It reads only the
    tensor and the schedule, never the factors, so a compiled pipeline runs
    it once per call before its sweeps; the per-call drivers run it at each
    call. An empty tensor has no order and needs no schedule."""
    modes = _row_modes(indices.shape[1], skip_mode)
    if indices.shape[0] == 0:
        return SortedNonzeros(values, tuple(indices[:, t] for t in modes))
    with jax.named_scope(stages.ORDER_GATHER):
        # the (P, N) rows are gathered once, then cut into 1-D columns, so
        # the padded temporary dies here
        idx = indices[sched.order]
        return SortedNonzeros(values[sched.order] * sched.valid,
                              tuple(idx[:, t] for t in modes))


def _gathered_block_rows(nz: SortedNonzeros, factors, skip_mode):
    """Gather each slot's non-mode factor rows from the pre-sorted
    coordinates (padding slots read row 0). The unfolding chain and the
    fused core update of one mode both read the same ``nz``, which the
    compiled pipeline orders once per call; only these row gathers, which
    read the factors of the moment, run in every sweep."""
    with jax.named_scope(stages.ROW_GATHER):
        rows = [factors[t][c] for t, c in zip(_row_modes(len(factors), skip_mode), nz.coords)]
        if len(rows) == 1:  # order-2 tensor: the "Kron row" is a single factor row
            rows.append(jnp.ones((rows[0].shape[0], 1), dtype=rows[0].dtype))
    return rows


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
    """Trace-safe twin of :func:`sparse_ttm_chain_kernel`: the schedule
    (``sched``, a ``sparse.layout.DeviceSchedule``) is already
    device-resident, ``shape`` / ``interpret`` are static, and no numpy or
    host sync happens. Orders the nonzeros at this call
    (:func:`order_gather`), then runs :func:`sorted_ttm_chain`.
    """
    return sorted_ttm_chain(
        order_gather(indices, values, sched, skip_mode), factors, skip_mode, sched,
        shape=shape, interpret=interpret, fused=fused, precision=precision,
    )


def sorted_ttm_chain(
    nz: SortedNonzeros,
    factors: Sequence[jax.Array],
    skip_mode: int,
    sched,
    *,
    shape: Sequence[int],
    interpret: bool,
    fused: bool = True,
    precision: str = "fp32",
) -> jax.Array:
    """Y_(n) from nonzeros already in the mode's schedule order — what the
    compiled scan-over-sweeps pipeline calls every sweep on operands it
    ordered once. Safe under ``jit`` / ``lax.scan`` / ``lax.cond``."""
    n_rows = int(shape[skip_mode])
    if nz.values.shape[0] == 0:
        from repro.core.kron import zero_unfolding

        return zero_unfolding(tuple(shape), factors, skip_mode)
    rows = _gathered_block_rows(nz, factors, skip_mode)
    vals = nz.values
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
    """The fused core update at one call: orders the nonzeros
    (:func:`order_gather`), then runs :func:`sorted_ttm_core`."""
    return sorted_ttm_core(
        order_gather(indices, values, sched, skip_mode), factors, skip_mode, sched,
        shape=shape, interpret=interpret, precision=precision,
    )


def sorted_ttm_core(
    nz: SortedNonzeros,
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
    the (just updated) factor in the same grid step. ``nz`` is the
    mode-``skip_mode`` unfolding's own sorted operands, so the compiled
    pipeline orders them once for both; the (I_n x K) unfolding itself never
    crosses HBM a second time. Returns (R_N, prod_{t != skip} R_t) f32.

    Orders > 3 fall back to the split path (chained Kron + blocked TTM): the
    megakernel streams exactly two operand blocks, the paper's case.
    """
    n_rows = int(shape[skip_mode])
    u = factors[skip_mode]
    if nz.values.shape[0] == 0:
        from repro.core.kron import zero_unfolding

        y0 = zero_unfolding(tuple(shape), factors, skip_mode)
        return jnp.zeros((u.shape[1], y0.shape[1]), dtype=jnp.float32)
    rows = _gathered_block_rows(nz, factors, skip_mode)
    if len(rows) == 2:
        # the megakernel's Kron accumulation and its TTM are one kernel,
        # counted with the Kron kernels
        with jax.named_scope(stages.KRON):
            return kron_kernel.fused_kron_scatter_ttm_pallas(
                rows[0], rows[1], nz.values, u, sched, n_rows, interpret=interpret,
                precision=precision,
            )
    y = sorted_ttm_chain(
        nz, factors, skip_mode, sched,
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
