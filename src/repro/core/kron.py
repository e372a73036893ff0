"""Kronecker-product accumulation — the paper's module 2 (Section III-C).

Alg. 2 line 5 / Eq. (13): for every nonzero x at coordinate (i_1..i_N),

    Y_(n)(i_n, :) += x * [ kron_{t != n} U_t(i_t, :) ]

evaluated only over nonzeros. This file is the mathematical / XLA layer; the
TPU Pallas kernel (one-hot-matmul re-association of the FPGA scatter chain)
lives in ``repro.kernels.kron_kernel``.

Column ordering. We take the Kronecker product over the non-mode factors in
*descending* mode order, so that the first non-mode dimension varies fastest.
This matches the paper's Eq. (2) (Kolda column ordering) and therefore matches
:func:`repro.core.coo.unfold_dense` exactly — the sparse accumulation and the
dense TTM-chain oracle produce bitwise-comparable unfoldings.

Paper-faithful reuse trick (Section III-C): "a Kronecker product can be
re-used for all non-zero elements that share the same indices (j,k)". We
expose this as a host-side precomputation (:func:`precompute_kron_reuse`)
that deduplicates non-mode index tuples; the jitted path then gathers each
unique Kronecker row once.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stages
from repro.core.coo import SparseCOO
from repro.sparse.layout import KronReusePlan, build_kron_reuse


def kron_rows(rows: Sequence[jax.Array]) -> jax.Array:
    """Row-wise Kronecker product of a list of ``(nnz, R_t)`` matrices.

    Returns ``(nnz, prod_t R_t)`` where, per paper Alg. 4, entry
    ``c[R_b*i + j] = a[i] * b[j]`` for each consecutive pair — i.e. the
    *last* operand varies fastest.
    """
    out = rows[0]
    for r in rows[1:]:
        nnz = out.shape[0]
        out = (out[:, :, None] * r[:, None, :]).reshape(nnz, -1)
    return out


def gathered_factor_rows(
    coo: SparseCOO, factors: Sequence[jax.Array], skip_mode: int
) -> List[jax.Array]:
    """Gather ``U_t(i_t, :)`` for every nonzero, for all modes t != skip_mode,
    in *descending* mode order (Kolda column ordering — see module docstring).
    """
    rows = []
    for t in range(coo.ndim - 1, -1, -1):
        if t == skip_mode:
            continue
        rows.append(factors[t][coo.indices[:, t]])
    return rows


def zero_unfolding(
    shape: Sequence[int], factors: Sequence[jax.Array], skip_mode: int
) -> jax.Array:
    """The Y_(n) of a tensor with no nonzeros: exactly zero, f32. Single
    definition of the empty-tensor contract shared by every chain variant."""
    k_cols = int(np.prod([f.shape[1] for t, f in enumerate(factors) if t != skip_mode]))
    return jnp.zeros((shape[skip_mode], k_cols), dtype=jnp.float32)


def sparse_ttm_chain(
    coo: SparseCOO,
    factors: Sequence[jax.Array],
    skip_mode: int,
    precision: str = "fp32",
) -> jax.Array:
    """Sparse power-iteration TTM chain (Alg. 2 lines 4-5).

    Computes the mode-``skip_mode`` unfolding of
    ``X x_1 U_1^T ... x_{n-1} U_{n-1}^T x_{n+1} U_{n+1}^T ... x_N U_N^T``
    touching only the nonzeros of ``X``.

    Args:
      coo: sparse tensor, indices (nnz, N), values (nnz,).
      factors: list of N factor matrices, U_t of shape (I_t, R_t). The entry
        at ``skip_mode`` is ignored.
      skip_mode: the mode n that is *not* contracted.
      precision: "fp32" (legacy, full working precision) or "bf16_fp32acc":
        the gathered factor rows and their Kronecker products run in
        bfloat16, the value scale and the scatter-add accumulate in f32 —
        the XLA-engine mirror of the kernels' mixed mode.

    Returns:
      Y_(n) of shape (I_n, prod_{t != n} R_t), f32.
    """
    if coo.indices.shape[0] == 0:
        return zero_unfolding(coo.shape, factors, skip_mode)
    with jax.named_scope(stages.ROW_GATHER):
        rows = gathered_factor_rows(coo, factors, skip_mode)
    with jax.named_scope(stages.KRON):
        if precision == "bf16_fp32acc":
            rows = [r.astype(jnp.bfloat16) for r in rows]
            k = kron_rows(rows)  # (nnz, K) bf16 multiplies
            dt = jnp.promote_types(coo.values.dtype, jnp.float32)
        else:
            k = kron_rows(rows)  # (nnz, K)
            dt = jnp.promote_types(
                jnp.promote_types(coo.values.dtype, k.dtype), jnp.float32
            )
        contrib = k.astype(dt) * coo.values.astype(dt)[:, None]
        i_n = coo.indices[:, skip_mode]
        out = jnp.zeros((coo.shape[skip_mode], k.shape[1]), dtype=dt)
        return out.at[i_n].add(contrib)


def precompute_kron_reuse(coo: SparseCOO, skip_mode: int) -> KronReusePlan:
    """Deduplicate the (N-1)-tuples of non-mode indices so each distinct
    Kronecker row is computed once (Section III-C). Alias of
    :func:`repro.sparse.layout.build_kron_reuse` (kept for API stability)."""
    return build_kron_reuse(coo, skip_mode)


def _reuse_chain(
    indices: jax.Array,
    values: jax.Array,
    factors: Sequence[jax.Array],
    skip_mode: int,
    unique_indices,
    inverse,
    modes: Sequence[int],
    shape: Sequence[int],
) -> jax.Array:
    """Shared body of the Kron-reuse chain: compute each unique Kronecker row
    once, gather per-nonzero, scatter-add into Y_(n). The dedup arrays index
    identically whether host numpy (KronReusePlan) or device-resident
    (DeviceSchedule) — the single implementation behind both entry points."""
    if indices.shape[0] == 0:
        return zero_unfolding(tuple(shape), factors, skip_mode)
    with jax.named_scope(stages.ROW_GATHER):
        rows = [factors[t][unique_indices[:, c]] for c, t in enumerate(modes)]
    with jax.named_scope(stages.KRON):
        k_unique = kron_rows(rows)  # (n_unique, K)
        k = k_unique[inverse]  # (nnz, K)
        dt = jnp.promote_types(jnp.promote_types(values.dtype, k.dtype), jnp.float32)
        contrib = k.astype(dt) * values.astype(dt)[:, None]
        i_n = indices[:, skip_mode]
        out = jnp.zeros((shape[skip_mode], k.shape[1]), dtype=dt)
        return out.at[i_n].add(contrib)


def sparse_ttm_chain_reuse(
    coo: SparseCOO,
    factors: Sequence[jax.Array],
    skip_mode: int,
    plan: KronReusePlan,
) -> jax.Array:
    """As :func:`sparse_ttm_chain` but computing each unique Kronecker row
    once and gathering per-nonzero (paper's reuse optimization). Exact same
    result; fewer multiplies when nonzeros share non-mode index tuples.
    """
    return _reuse_chain(
        coo.indices, coo.values, factors, skip_mode,
        jnp.asarray(plan.unique_indices), jnp.asarray(plan.inverse),
        plan.modes, coo.shape,
    )


def sparse_ttm_chain_reuse_device(
    indices: jax.Array,
    values: jax.Array,
    factors: Sequence[jax.Array],
    skip_mode: int,
    sched,
    *,
    shape: Sequence[int],
) -> jax.Array:
    """As :func:`sparse_ttm_chain_reuse` but with the dedup plan already
    device-resident (``sched.kron_unique`` / ``sched.kron_inverse`` on a
    ``sparse.layout.DeviceSchedule``): no host constants enter the trace, so
    the compiled scan-over-sweeps pipeline can call it every sweep without
    re-uploading the plan."""
    return _reuse_chain(
        indices, values, factors, skip_mode,
        sched.kron_unique, sched.kron_inverse, sched.kron_modes, shape,
    )


def kron_flops(coo: SparseCOO, ranks: Sequence[int], skip_mode: int) -> int:
    """Analytic multiply count of the sparse chain for the roofline harness:
    nnz * (kron build + scale) — matches the paper's O(nnz * prod R) claim.
    """
    ks = [r for t, r in enumerate(ranks) if t != skip_mode]
    k_total = int(np.prod(ks))
    # building the kron row costs sum of partial products; scaling costs K.
    build = 0
    acc = ks[0]
    for r in ks[1:]:
        acc *= r
        build += acc
    return coo.nnz * (build + 2 * k_total)
