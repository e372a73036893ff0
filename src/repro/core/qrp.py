"""QR decomposition with column pivoting — the paper's module 3 (Sec. III-D).

The paper replaces the SVD in HOOI's factor update with Householder QRP
(2mn^2 - 2n^3/3 flops vs 2mn^2 + 11n^3) and runs it on the CPU because the
per-step column-norm comparison is sequential. Two implementations here:

1. :func:`qrp_householder` — the paper-faithful sequential Householder loop
   (Eqs. 14-18), jittable via ``lax.fori_loop``. Only ``R`` reflections are
   performed (we need just the leading R columns of Q), so the sequential
   chain has length R, not m.

2. :func:`qrp_gram` — the beyond-paper TPU adaptation: pivoted Cholesky on
   the Gram matrix ``A^T A``. In exact arithmetic pivoted Cholesky of the
   Gram matrix selects the *same pivot sequence* as column-pivoted QR on A,
   and ``Q = A[:, piv] @ inv(L^T)``. The O(m)-long sequential dependency of
   Householder QRP collapses to one MXU matmul (A^T A) plus an R-step loop
   over a K x K matrix (K = prod R << m) — the paper's "keep the sequential
   part off the parallel engine" insight, re-targeted at TPU.

Both return U with orthonormal columns spanning the R most "weighted"
columns of A — exactly what HOOI consumes.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import stages

_EPS = 1e-12


def _householder_vector(a: jax.Array) -> jax.Array:
    """v for H = I - 2 v v^T / (v^T v) zeroing a below its first entry
    (Eq. 17-18), guarded against the zero column."""
    norm_a = jnp.linalg.norm(a)
    sign = jnp.where(a[0] >= 0, 1.0, -1.0).astype(a.dtype)
    v = a.at[0].add(sign * norm_a)
    vnorm = jnp.linalg.norm(v)
    safe = vnorm > _EPS
    e1 = jnp.zeros_like(a).at[0].set(1.0)
    v = jnp.where(safe, v / jnp.where(safe, vnorm, 1.0), e1)
    return v


def qrp_householder(a: jax.Array, r: int) -> Tuple[jax.Array, jax.Array]:
    """Column-pivoted Householder QR, truncated to ``r`` reflections.

    Args:
      a: (m, n) matrix (the unfolding Y_(n); m = I_n, n = prod_{t!=n} R_t).
      r: number of orthonormal columns wanted (the Tucker rank R_n).

    Returns:
      (q, piv): q (m, r) with orthonormal columns; piv (r,) the pivot
      column indices in selection order (|r_11| >= |r_22| >= ... by
      construction, Eq. 15).
    """
    m, n = a.shape
    r = min(r, m, n)
    dt = jnp.promote_types(a.dtype, jnp.float32)
    a = a.astype(dt)

    def step(j, carry):
        a_work, vs, piv, used, col_ids = carry
        # column norms of the trailing (rows >= j) block; paper: re-compare
        # norms every iteration and pick the heaviest remaining column.
        row_mask = (jnp.arange(m) >= j)[:, None]
        norms = jnp.sum(jnp.square(a_work * row_mask), axis=0)
        norms = jnp.where(used, -jnp.inf, norms)
        p = jnp.argmax(norms)
        # record the ORIGINAL column id (columns get physically swapped).
        piv = piv.at[j].set(col_ids[p])
        used = used.at[p].set(True)
        # swap columns j <-> p via a gather permutation.
        cols = jnp.arange(n)
        jj = jnp.asarray(j)
        perm = jnp.where(cols == jj, p, jnp.where(cols == p, jj, cols))
        a_work = a_work[:, perm]
        used = used[perm]
        col_ids = col_ids[perm]
        # Householder on rows >= j of column j.
        col = a_work[:, j]
        col = jnp.where(jnp.arange(m) >= j, col, 0.0)
        # shift so the "first" entry of the active subvector sits at row j:
        # build v in full-length coordinates with v[:j] = 0.
        norm_c = jnp.linalg.norm(col)
        cj = col[j]
        sign = jnp.where(cj >= 0, 1.0, -1.0)
        v = col.at[j].add(sign * norm_c)
        vnorm = jnp.linalg.norm(v)
        safe = vnorm > _EPS
        ej = jnp.zeros((m,), dtype=dt).at[j].set(1.0)
        v = jnp.where(safe, v / jnp.where(safe, vnorm, 1.0), ej)
        # reflect the whole working matrix: A <- A - 2 v (v^T A)
        a_work = a_work - 2.0 * jnp.outer(v, v @ a_work)
        vs = vs.at[:, j].set(v)
        return a_work, vs, piv, used, col_ids

    vs0 = jnp.zeros((m, r), dtype=dt)
    piv0 = jnp.zeros((r,), dtype=jnp.int32)
    used0 = jnp.zeros((n,), dtype=bool)
    ids0 = jnp.arange(n, dtype=jnp.int32)
    _, vs, piv, _, _ = jax.lax.fori_loop(0, r, step, (a, vs0, piv0, used0, ids0))

    # Q[:, :r] = H_1 ... H_r I[:, :r]  (apply reflections in reverse).
    q0 = jnp.eye(m, r, dtype=dt)

    def apply(jrev, q):
        j = r - 1 - jrev
        v = vs[:, j]
        return q - 2.0 * jnp.outer(v, v @ q)

    q = jax.lax.fori_loop(0, r, apply, q0)
    return q, piv


def pivoted_cholesky(g: jax.Array, r: int) -> Tuple[jax.Array, jax.Array]:
    """Rank-r pivoted Cholesky of an SPSD matrix ``g`` (K x K).

    Returns (l, piv) with l (K, r) lower-trapezoidal in *pivoted* row order
    such that g[piv][:, piv] ~= (l l^T)[piv-order...]. We keep l in original
    row indexing: g ~= l @ l.T after r steps on the selected pivots.
    """
    k = g.shape[0]
    r = min(r, k)
    dt = jnp.promote_types(g.dtype, jnp.float32)
    l = jnp.zeros((k, r), dtype=dt)
    d = jnp.diag(g).astype(dt)  # remaining diagonal
    piv0 = jnp.zeros((r,), dtype=jnp.int32)
    g = g.astype(dt)

    def step(j, carry):
        l, d, piv = carry
        p = jnp.argmax(d)
        piv = piv.at[j].set(p)
        dp = jnp.maximum(d[p], 0.0)
        root = jnp.sqrt(dp + _EPS)
        # new column: (g[:, p] - l @ l[p, :]^T) / root
        col = g[:, p] - l @ l[p, :]
        col = col / root
        # zero out entries for already-eliminated pivots happens naturally as
        # their remaining diagonal is ~0; we just clamp d.
        l = l.at[:, j].set(col)
        d = jnp.maximum(d - jnp.square(col), 0.0)
        d = d.at[p].set(-jnp.inf)  # never re-pick
        return l, d, piv

    l, _, piv = jax.lax.fori_loop(0, r, step, (l, d, piv0))
    return l, piv


def qrp_gram(a: jax.Array, r: int) -> Tuple[jax.Array, jax.Array]:
    """Beyond-paper QRP: Gram matrix + pivoted Cholesky + triangular solve.

    Same pivot sequence as :func:`qrp_householder` in exact arithmetic; the
    long sequential loop shrinks from O(m) work per step on the accelerator
    to an R-step loop over the K x K Gram matrix. The heavy ops (A^T A and
    A_S @ inv(L_S^T)) are MXU matmuls.
    """
    m, n = a.shape
    r = min(r, m, n)
    a32 = a.astype(jnp.promote_types(a.dtype, jnp.float32))
    g = a32.T @ a32  # (K, K) — one matmul on the MXU
    l, piv = pivoted_cholesky(g, r)
    # L restricted to pivot rows is lower-triangular (r x r).
    l_s = l[piv, :]  # (r, r) lower triangular in pivot order
    a_s = a32[:, piv]  # (m, r) selected columns
    # Q = A_S @ inv(L_S^T): triangular solve on the right.
    q = jax.lax.linalg.triangular_solve(
        l_s, a_s, left_side=False, lower=False, transpose_a=True
    )
    # Numerical safety: one Gram-Schmidt pass via QR (small r) to clean up
    # conditioning lost in the normal equations. Cheap: (m, r) thin QR.
    q, _ = jnp.linalg.qr(q)
    return q, piv


def qrp(a: jax.Array, r: int, method: str = "householder") -> jax.Array:
    """Factor update U_n <- QRP(Y_(n), R_n) (Alg. 2 line 7)."""
    if method == "householder":
        q, _ = qrp_householder(a, r)
    elif method == "gram":
        q, _ = qrp_gram(a, r)
    else:
        raise ValueError(f"unknown QRP method: {method}")
    return q


def svd_factor(a: jax.Array, r: int) -> jax.Array:
    """The baseline the paper replaces: R leading left singular vectors."""
    u, _, _ = jnp.linalg.svd(
        a.astype(jnp.promote_types(a.dtype, jnp.float32)), full_matrices=False
    )
    return u[:, :r]


def factor_update(y_n: jax.Array, r: int, method: str) -> jax.Array:
    """HOOI factor update U_n <- orth(Y_(n), R_n) — Alg. 1 line 5 ('svd') or
    Alg. 2 line 7 ('householder' / 'gram'). Every method is pure ``lax``
    (``fori_loop`` chains, no data-dependent Python), which is what lets the
    whole-sweep pipeline in ``core.hooi`` run N of these inside one compiled
    ``lax.scan`` over sweeps."""
    with jax.named_scope(stages.QRP):
        if method == "svd":
            return svd_factor(y_n, r)
        return qrp(y_n, r, method=method)


def qrp_flops(m: int, n: int) -> int:
    """Paper's QRP flop model: 2mn^2 - 2n^3/3."""
    return int(2 * m * n * n - 2 * n**3 // 3)


def svd_flops(m: int, n: int) -> int:
    """Paper's SVD flop model: 2mn^2 + 11n^3."""
    return int(2 * m * n * n + 11 * n**3)
