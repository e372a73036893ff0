"""Pallas TPU kernel for the paper's TTM module (Alg. 3, Section III-B).

The paper computes ``G = Y x_N U_N^T`` on the unfolded operands
(Eq. 12: ``G_(N) = U_N^T Y_(N)``, i.e. ``G = Y @ U^T`` with
``Y: (R1R2, I3)``, ``U: (R3, I3)``) in row *batches* of b=32 with an
on-chip ``tmp`` accumulator and cyclic BRAM partitioning.

TPU adaptation (hardware re-think, not a port):
  * the FPGA row-batch b=32 with unrolled MACs   -> MXU tile: the row batch
    becomes a (BL x BK) VMEM block feeding 128x128 systolic matmuls;
  * cyclic partitioning by 8/16 for port parallelism -> BlockSpec tiling
    (multiples of (8,128)) so HBM->VMEM DMAs are contiguous and the MXU
    contraction dim is lane-aligned;
  * the PE's register 'tmp' accumulator (Fig. 4)  -> f32 VMEM scratch
    accumulator, zeroed at k==0 and flushed at the last k block.

Grid: (rows/BL, I3/BK); the contraction dim I3 is the innermost grid axis so
the output block stays resident in VMEM across all its partial products.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BL = 256  # rows of Y per block (paper's b=32, scaled to MXU tiles)
DEFAULT_BK = 512  # contraction (I3) block


def _ttm_kernel(y_ref, u_ref, o_ref, acc_ref):
    """One (BL, R3) output block: acc += Y_blk (BL,BK) @ U_blk (R3,BK)^T."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        y_ref[...], u_ref[...].T, preferred_element_type=jnp.float32
    )

    @pl.when(k == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("bl", "bk", "interpret", "precision"))
def ttm_pallas(
    y: jax.Array,
    u: jax.Array,
    *,
    interpret: bool,
    bl: int = DEFAULT_BL,
    bk: int = DEFAULT_BK,
    precision: str = "fp32",
) -> jax.Array:
    """``G = Y @ U^T`` — the paper's TTM (Eq. 12) as a tiled Pallas kernel.

    Args:
      y: (L, I3) unfolded dense tensor (L = prod of the other ranks).
      u: (R3, I3) factor (transposed application, Eq. 11).
      bl, bk: VMEM block shape knobs (rows / contraction).
      interpret: run the kernel body in interpret mode (off-TPU only; the
        callers pass ``kernels.ops.default_interpret()``).
      precision: "fp32", or "bf16_fp32acc" for bf16 operand loads/multiplies
        with the f32 VMEM scratch accumulator (the MXU's native mixed mode).

    VMEM budget per step: bl*bk (Y) + R3p*bk (U) + bl*R3p (acc+out), f32
    -> with defaults and R3<=512: 256*512*4 + 512*512*4 + 2*256*512*4
       = 2.6 MiB, comfortably inside ~16 MiB v5e VMEM.
    """
    l, i3 = y.shape
    r3, i3u = u.shape
    assert i3 == i3u, (y.shape, u.shape)
    bl_ = min(bl, max(8, l))
    # clamp the contraction block to I3 rounded up to a lane multiple — a
    # small-I3 call (e.g. the HOOI core update on a rank-4 sweep) would
    # otherwise zero-pad the contraction 25x past the data.
    bk_ = min(bk, max(128, -(-i3 // 128) * 128))
    # pad everything to tile multiples (MXU-aligned lanes).
    yp = _pad_to(_pad_to(y, 0, bl_), 1, bk_)
    up = _pad_to(_pad_to(u, 0, 8), 1, bk_)
    from repro.kernels.kron_kernel import _cast_operands

    yp, up = _cast_operands(precision, yp, up)
    lp, i3p = yp.shape
    r3p = up.shape[0]
    grid = (lp // bl_, i3p // bk_)
    out = pl.pallas_call(
        _ttm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bl_, bk_), lambda i, k: (i, k)),
            pl.BlockSpec((r3p, bk_), lambda i, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((bl_, r3p), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((lp, r3p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bl_, r3p), jnp.float32)],
        interpret=interpret,
    )(yp, up)
    return out[:l, :r3]
