"""Pallas TPU kernels for the paper's Kronecker-product module (Alg. 4,
Section III-C) and its scatter-accumulation into Y_(n) (Eq. 13).

The FPGA design streams nonzeros through a pipelined outer-product unit
(multipliers only) and accumulates rows of Y_(n) in BRAM. A TPU has no
efficient random scatter, so the module is *re-associated* into two
TPU-native kernels:

1. ``kron_contrib`` — Alg. 4 itself, vectorized over a block of nonzeros:
   contrib[t, :] = v[t] * (a[t, :] (x) b[t, :]).  Pure VPU work (outer
   product per nonzero), pipelined over nnz blocks — the direct analogue of
   the paper's pipeline-outer/unroll-inner HLS loops.

2. ``scatter_rows`` — the BRAM row-accumulator becomes a *one-hot matmul*:
   nonzeros are pre-sorted/grouped by output row-block (host-side plan, the
   moral equivalent of the paper's (j,k)-sharing reuse), and each nnz block
   does  Y_blk += onehot(rel_row)^T @ contrib  on the MXU. Consecutive
   same-target blocks keep Y_blk resident in VMEM (Pallas revisiting rule),
   exactly like the paper keeps a row batch in BRAM across accumulations.
   Scalar prefetch (PrefetchScalarGridSpec) supplies the data-dependent
   block->row-block map to the BlockSpec index_map.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BN = 128  # nonzeros per block
DEFAULT_BI = 128  # output rows per block

# mixed-precision axis shared by every kernel in this module: "fp32" keeps
# the legacy all-f32 pipeline; "bf16_fp32acc" loads/multiplies the gathered
# factor rows in bfloat16 while every accumulator (the one-hot matmul, the
# resident Y block, the core contraction) stays f32 — the MXU's native mode.
PRECISIONS = ("fp32", "bf16_fp32acc")


def _cast_operands(precision: str, *arrays):
    """Apply the kernel-input side of the precision axis (bf16 loads)."""
    if precision not in PRECISIONS:
        raise ValueError(
            f"precision must be one of {PRECISIONS}, got {precision!r}"
        )
    if precision == "bf16_fp32acc":
        return tuple(a.astype(jnp.bfloat16) for a in arrays)
    return arrays


# ---------------------------------------------------------------------------
# Kernel 1: Kronecker rows (Alg. 4), blocked over nonzeros.
# ---------------------------------------------------------------------------


def _kron_rows(a, b):
    """Outer product per nonzero, (BN, Ra) x (BN, Rb) -> (BN, Ra*Rb) f32; Rb
    varies fastest (paper Alg. 4 line 4: c[R3*i + j] = a[i] * b[j]). The
    operand tiles widen to f32 before the 3-D broadcast: Mosaic has no
    bf16 (BN, R) -> (BN, R, 1) shape cast, and a product of two bf16 values
    is exact in f32, so bf16 loads from HBM are kept at no cost in accuracy."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    bn, ra = a.shape
    return (a[:, :, None] * b[:, None, :]).reshape(bn, ra * b.shape[1])


def _kron_kernel(a_ref, b_ref, v_ref, o_ref):
    kron = _kron_rows(a_ref[...], b_ref[...])
    o_ref[...] = (kron * v_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "interpret", "precision"))
def kron_contrib_pallas(
    a: jax.Array,
    b: jax.Array,
    v: jax.Array,
    *,
    bn: int = DEFAULT_BN,
    interpret: bool,
    precision: str = "fp32",
) -> jax.Array:
    """contrib[t] = v[t] * (a[t] (x) b[t]) for a block-padded batch.

    Args:
      a: (nnz, Ra) gathered rows U_j(i_j, :).
      b: (nnz, Rb) gathered rows U_k(i_k, :).
      v: (nnz,) nonzero values.
      precision: "fp32" or "bf16_fp32acc" (bf16 outer products, f32 scale).
    Returns:
      (nnz, Ra*Rb) f32 contributions.
    """
    nnz, ra = a.shape
    rb = b.shape[1]
    bn_ = min(bn, max(8, nnz))
    pad = (-nnz) % bn_
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
        v = jnp.pad(v, ((0, pad),))
    a, b = _cast_operands(precision, a, b)
    nnzp = a.shape[0]
    out = pl.pallas_call(
        _kron_kernel,
        grid=(nnzp // bn_,),
        in_specs=[
            pl.BlockSpec((bn_, ra), lambda i: (i, 0)),
            pl.BlockSpec((bn_, rb), lambda i: (i, 0)),
            pl.BlockSpec((bn_, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn_, ra * rb), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nnzp, ra * rb), jnp.float32),
        interpret=interpret,
    )(a, b, v[:, None].astype(jnp.float32))
    return out[:nnz]


# ---------------------------------------------------------------------------
# Kernel 2: row scatter-accumulation as a one-hot MXU matmul.
# ---------------------------------------------------------------------------


class ScatterPlan(NamedTuple):
    """Host-side grouping of nonzeros by output row-block (static metadata).

    Built once per (tensor, mode) — the analogue of the paper's observation
    that nonzeros sharing indices can share work. ``order`` permutes the
    nonzeros so each BN-block targets exactly one BI-row-block and blocks
    with the same target are consecutive.
    """

    order: np.ndarray  # (nnz_padded,) gather order into original nonzeros
    valid: np.ndarray  # (nnz_padded,) 1.0 for real nonzeros, 0.0 for padding
    rel_row: np.ndarray  # (nnz_padded,) row index within the target block
    blkmap: np.ndarray  # (nblocks,) target row-block per nnz block
    first: np.ndarray  # (nblocks,) 1 if first block of its target
    last: np.ndarray  # (nblocks,) 1 if last block of its target
    n_row_blocks: int
    bn: int
    bi: int
    # precomputed keep-mask over output rows (None = all row blocks visited);
    # cached here so the scatter wrappers do no host work per call.
    row_mask: Optional[np.ndarray] = None


def build_scatter_plan(
    rows: np.ndarray, n_rows: int, bn: int = DEFAULT_BN, bi: int = DEFAULT_BI
) -> ScatterPlan:
    """Thin wrapper over the shared grouping in ``sparse.layout`` (one
    implementation of the pad/group/order construction for both plan types)."""
    from repro.sparse.layout import build_schedule, visited_row_mask

    order, valid, rel, blkmap, first, last, n_row_blocks, _ = build_schedule(
        rows, n_rows, bn, bi
    )
    return ScatterPlan(
        order=order,
        valid=valid,
        rel_row=rel,
        blkmap=blkmap,
        first=first,
        last=last,
        n_row_blocks=n_row_blocks,
        bn=bn,
        bi=bi,
        row_mask=visited_row_mask(blkmap, n_row_blocks, bi, n_rows),
    )


def _scatter_kernel(blkmap_ref, first_ref, rel_ref, contrib_ref, o_ref):
    b = pl.program_id(0)

    @pl.when(first_ref[b] == 1)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    rel = rel_ref[...]  # (BN, 1) int32
    bi = o_ref.shape[0]
    onehot = (rel == jax.lax.broadcasted_iota(jnp.int32, (rel.shape[0], bi), 1)).astype(
        jnp.float32
    )  # (BN, BI)
    # MXU: (BI, BN) @ (BN, K)
    o_ref[...] += jnp.dot(onehot.T, contrib_ref[...], preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_rows", "bn", "bi", "interpret"))
def _scatter_call(blkmap, first, rel, contrib, *, n_rows, bn, bi, interpret):
    nblocks = blkmap.shape[0]
    n_row_blocks = -(-n_rows // bi)
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nblocks,),
            in_specs=[
                pl.BlockSpec((bn, 1), lambda b, m, f: (b, 0)),
                pl.BlockSpec((bn, contrib.shape[1]), lambda b, m, f: (b, 0)),
            ],
            out_specs=pl.BlockSpec((bi, contrib.shape[1]), lambda b, m, f: (m[b], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_row_blocks * bi, contrib.shape[1]), jnp.float32),
        interpret=interpret,
    )(blkmap, first, rel[:, None], contrib)
    return out[:n_rows]


def scatter_rows_pallas(
    contrib: jax.Array,
    plan: ScatterPlan,
    n_rows: int,
    *,
    interpret: bool,
) -> jax.Array:
    """Y_(n) accumulation: sum contrib rows into their target rows.

    ``contrib`` must already be permuted by ``plan.order`` with padding rows
    zeroed (ops.py does this). Row blocks whose groups are empty are zero.
    """
    out = _scatter_call(
        jnp.asarray(plan.blkmap),
        jnp.asarray(plan.first),
        jnp.asarray(plan.rel_row),
        contrib,
        n_rows=n_rows,
        bn=plan.bn,
        bi=plan.bi,
        interpret=interpret,
    )
    return _mask_unvisited(out, plan, n_rows)


def _mask_unvisited(out: jax.Array, plan, n_rows: int) -> jax.Array:
    """Row blocks with zero nonzeros are never visited by the grid -> their
    rows may be uninitialized in interpret mode; mask them explicitly. The
    mask is precomputed at plan-build time (``plan.row_mask``; ``None`` means
    every row block is visited), so this is trace-safe — device-resident
    plans (``sparse.layout.DeviceSchedule``) flow through jit/scan with no
    host work per call."""
    mask = plan.row_mask
    if mask is None:
        return out
    return jnp.where(jnp.asarray(mask)[:, None], out, 0.0)


# ---------------------------------------------------------------------------
# Fused kernel: Kron rows + one-hot scatter in a single pipeline step.
# ---------------------------------------------------------------------------


def _expansions(ra: int, rb: int):
    """The 0/1 matrices that spread factor rows over Kron columns:
    ``E_a[i, Rb*i + j] = E_b[j, Rb*i + j] = 1``, Rb fastest (paper Alg. 4
    line 4: c[R3*i + j] = a[i] * b[j])."""
    col = np.arange(ra * rb)
    e_a = col // rb == np.arange(ra)[:, None]
    e_b = col % rb == np.arange(rb)[:, None]
    return jnp.asarray(e_a, jnp.float32), jnp.asarray(e_b, jnp.float32)


def _bf16_parts(x):
    """``x`` as f32 terms that sum to it exactly and each hold a bf16 value,
    so that one bf16 MXU pass reads each term whole: ``x`` itself when it
    was loaded as bf16, else three. Each term keeps the top 8 significant
    bits of what is left (the low 16 bits masked off), and 8 + 8 + 8 bits
    cover an f32 significand."""
    if x.dtype == jnp.bfloat16:
        return (x.astype(jnp.float32),)
    parts = []
    for _ in range(2):
        bits = jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536)
        top = jax.lax.bitcast_convert_type(bits, jnp.float32)
        parts.append(top)
        x = x - top
    return (*parts, x)


def _expand(x, e):
    """``x @ e`` exactly for a 0/1 matrix ``e``: one pass at the MXU's
    default (single bf16) precision per term of :func:`_bf16_parts`, each
    product a copy of one term, summed in f32."""
    out = None
    for part in _bf16_parts(x):
        y = jnp.dot(part, e, precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)
        out = y if out is None else out + y
    return out


def _kron_block(a, b, e_a, e_b):
    """Lane-dense Kron rows, (BN, Ra) x (BN, Rb) -> (BN, Ra*Rb) f32: both
    factor rows are spread over the Kron columns on the MXU and multiplied
    once on the VPU, so ``kron[t, Rb*i + j]`` is ``a[t, i] * b[t, j]`` rounded
    once to f32, as the outer product would give it."""
    return _expand(a, e_a) * _expand(b, e_b)


def _scatter_block(rel, v, kron, bi):
    """The block's Y rows: ``onehot @ kron`` with the one-hot built in
    (BI, BN) orientation from lane-dense (1, BN) rows, ``onehot[r, t] =
    v[t] if rel[t] == r`` (a sublane iota against ``rel`` broadcast over
    sublanes), so the MXU contraction needs no transpose. Padding slots carry
    ``v = 0``. Both operands are f32, so the contraction runs at the default
    matmul precision in force (``highest`` where fp32 is asked for)."""
    hit = rel == jax.lax.broadcasted_iota(jnp.int32, (bi, rel.shape[1]), 0)
    onehot = jnp.where(hit, v, 0.0)
    return jnp.dot(onehot, kron, preferred_element_type=jnp.float32)


def _fused_kernel(
    blkmap_ref, first_ref, a_ref, b_ref, v_ref, rel_ref, ea_ref, eb_ref, o_ref
):
    """One nnz block: build the Kron rows and immediately accumulate them
    into the resident Y row block (MXU one-hot matmul) — the contrib matrix
    never round-trips through HBM. This is the closest TPU analogue of the
    paper's fully pipelined FPGA dataflow, where multiplier outputs feed the
    BRAM accumulator directly. Every tile is lane-dense: the values and row
    offsets arrive as (1, BN) rows and the Kron rows as (BN, Ra*Rb)."""
    blk = pl.program_id(0)

    @pl.when(first_ref[blk] == 1)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    kron = _kron_block(a_ref[...], b_ref[...], ea_ref[...], eb_ref[...])
    o_ref[...] += _scatter_block(rel_ref[...], v_ref[...], kron, o_ref.shape[0])


def _stream_specs(bn, ra, rb):
    """BlockSpecs of the operands every fused kernel streams: the two factor
    row blocks, the values and row offsets as (1, BN) rows of their
    (nblocks, 1, BN) views, and the two constant expansions (fetched once)."""
    return [
        pl.BlockSpec((bn, ra), lambda i, *_: (i, 0)),
        pl.BlockSpec((bn, rb), lambda i, *_: (i, 0)),
        pl.BlockSpec((None, 1, bn), lambda i, *_: (i, 0, 0)),
        pl.BlockSpec((None, 1, bn), lambda i, *_: (i, 0, 0)),
        pl.BlockSpec((ra, ra * rb), lambda i, *_: (0, 0)),
        pl.BlockSpec((rb, ra * rb), lambda i, *_: (0, 0)),
    ]


def _stream_operands(a, b, v, rel, bn, precision):
    """The operands :func:`_stream_specs` describes. ``P = nblocks * BN`` by
    the schedule's construction, so the (nblocks, 1, BN) views are reshapes."""
    a, b = _cast_operands(precision, a, b)
    rows = (-1, 1, bn)
    return (a, b, v.astype(jnp.float32).reshape(rows), rel.reshape(rows),
            *_expansions(a.shape[1], b.shape[1]))


@functools.partial(
    jax.jit, static_argnames=("n_rows", "bn", "bi", "interpret", "precision")
)
def _fused_call(
    blkmap, first, a, b, v, rel, *, n_rows, bn, bi, interpret, precision="fp32"
):
    nblocks = blkmap.shape[0]
    n_row_blocks = -(-n_rows // bi)
    ra, rb = a.shape[1], b.shape[1]
    out = pl.pallas_call(
        _fused_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nblocks,),
            in_specs=_stream_specs(bn, ra, rb),
            out_specs=pl.BlockSpec((bi, ra * rb), lambda blk, m, f: (m[blk], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_row_blocks * bi, ra * rb), jnp.float32),
        interpret=interpret,
    )(blkmap, first, *_stream_operands(a, b, v, rel, bn, precision))
    return out[:n_rows]


def fused_kron_scatter_pallas(
    a: jax.Array,
    b: jax.Array,
    v: jax.Array,
    plan,
    n_rows: int,
    *,
    interpret: bool,
    precision: str = "fp32",
) -> jax.Array:
    """Y_(n)[i_n] += v * (a (x) b), fused: Alg. 4 + Eq. 13 in one kernel.

    ``a``, ``b``, ``v`` must already be permuted into the plan's block order
    (``plan.order``) with padding values zeroed (``plan.valid``); ``plan`` is
    a ``ScatterPlan`` or ``sparse.layout.SortedCOO`` (same schedule fields).
    """
    out = _fused_call(
        jnp.asarray(plan.blkmap),
        jnp.asarray(plan.first),
        a,
        b,
        v,
        jnp.asarray(plan.rel_row),
        n_rows=n_rows,
        bn=plan.bn,
        bi=plan.bi,
        interpret=interpret,
        precision=precision,
    )
    return _mask_unvisited(out, plan, n_rows)


# ---------------------------------------------------------------------------
# Megakernel: Kron rows + one-hot scatter + core TTM in one pipeline step.
# ---------------------------------------------------------------------------


def _mega_kernel(
    blkmap_ref, first_ref, last_ref, a_ref, b_ref, v_ref, rel_ref, ea_ref,
    eb_ref, u_ref, g_ref, y_ref,
):
    """One nnz block of the fused core update G_(N) = U_N^T Y_(N) (Eq. 12):
    rebuild the target Y row block in VMEM scratch from the streamed nonzeros
    (the fused kernel's lane-dense Kron rows and one-hot scatter — Y never
    touches HBM in this pass), then, at each row-block group's LAST nnz block,
    contract the finished block into the grid-resident (R, K) core
    accumulator. The output block's index map is constant, so ``g_ref`` stays
    in VMEM for the whole grid (Pallas revisiting rule) — the closest TPU
    analogue of the paper's FPGA keeping both the BRAM row batch and the TTM
    accumulator on chip."""
    blk = pl.program_id(0)

    @pl.when(blk == 0)
    def _init_core():
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when(first_ref[blk] == 1)
    def _init_rows():
        y_ref[...] = jnp.zeros_like(y_ref)

    kron = _kron_block(a_ref[...], b_ref[...], ea_ref[...], eb_ref[...])
    y_ref[...] += _scatter_block(rel_ref[...], v_ref[...], kron, y_ref.shape[0])

    @pl.when(last_ref[blk] == 1)
    def _contract():
        # (Rp, BI) @ (BI, K): the finished row block feeds the MXU directly
        # from VMEM. f32 accumulation regardless of the load precision.
        u = u_ref[...].astype(jnp.float32)
        g_ref[...] += jnp.dot(u.T, y_ref[...], preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("n_rows", "bn", "bi", "interpret", "precision")
)
def _mega_call(
    blkmap, first, last, a, b, v, rel, u, *, n_rows, bn, bi, interpret,
    precision="fp32",
):
    nblocks = blkmap.shape[0]
    n_row_blocks = -(-n_rows // bi)
    ra, rb = a.shape[1], b.shape[1]
    k = ra * rb
    r = u.shape[1]
    rp = -(-r // 8) * 8  # sublane-aligned core rows
    # pad U to the grid's padded row extent so block (bi, rp) slices line up
    # with the scratch Y blocks; padding rows/cols contract to exact zeros.
    up = jnp.pad(
        u.astype(jnp.float32),
        ((0, n_row_blocks * bi - u.shape[0]), (0, rp - r)),
    )
    (up,) = _cast_operands(precision, up)
    out = pl.pallas_call(
        _mega_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nblocks,),
            in_specs=[
                *_stream_specs(bn, ra, rb),
                pl.BlockSpec((bi, rp), lambda blk, m, f, e: (m[blk], 0)),
            ],
            out_specs=pl.BlockSpec((rp, k), lambda blk, m, f, e: (0, 0)),
            scratch_shapes=[pltpu.VMEM((bi, k), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rp, k), jnp.float32),
        interpret=interpret,
    )(blkmap, first, last, *_stream_operands(a, b, v, rel, bn, precision), up)
    return out[:r]


def fused_kron_scatter_ttm_pallas(
    a: jax.Array,
    b: jax.Array,
    v: jax.Array,
    u: jax.Array,
    plan,
    n_rows: int,
    *,
    interpret: bool,
    precision: str = "fp32",
) -> jax.Array:
    """G = U^T Y where Y[i_n] += v * (a (x) b) — Alg. 4 + Eq. 13 + Eq. 12
    in ONE kernel, with Y living only in VMEM scratch.

    ``a``, ``b``, ``v`` follow the same contract as
    :func:`fused_kron_scatter_pallas` (permuted by ``plan.order``, padding
    zeroed); ``u`` is the (n_rows, R) factor of the skipped mode. ``plan``
    must carry the ``last`` block flags (any schedule built by
    ``sparse.layout.build_schedule``). Row blocks with no nonzeros contribute
    exact zeros (their U rows never meet a resident Y block), so no
    row-masking is needed on the (R, K) output.
    """
    last = getattr(plan, "last", None)
    if last is None:
        raise ValueError(
            "fused core update needs a schedule with 'last' block flags — "
            "rebuild the plan with the current sparse.layout.build_schedule"
        )
    return _mega_call(
        jnp.asarray(plan.blkmap),
        jnp.asarray(plan.first),
        jnp.asarray(last),
        a,
        b,
        v,
        jnp.asarray(plan.rel_row),
        u,
        n_rows=n_rows,
        bn=plan.bn,
        bi=plan.bi,
        interpret=interpret,
        precision=precision,
    )
