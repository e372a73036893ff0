"""The plain reference: sparse Tucker HOOI with column-pivoted QR factor
updates, written from the algorithm and independent of the program.

Alg. 2 of the paper: for every sweep and every mode n, accumulate the
unfolding ``Y_(n) = X x_{t != n} U_t^T`` over the nonzeros, then set ``U_n``
to the first ``R_n`` columns of Q of the column-pivoted QR of ``Y_(n)``; the
core is ``U_N^T Y_(N)`` folded. The columns of an unfolding are the
Kronecker product of the other modes' factor rows in descending mode order,
the first remaining mode fastest.

The accumulation runs on the device in float32 (elementwise products and a
scatter-add, no matrix unit), over fixed-size blocks of nonzeros so that it
compiles once per block shape and fits beside whatever else the device
holds. The pivoted QR (modified Gram-Schmidt with column pivoting, which
picks the same columns as Householder QRP in exact arithmetic) and the core
run on the host in float64.
"""
from __future__ import annotations

from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("mode",), donate_argnames=("y",))
def _accumulate(y, idx, val, factors, *, mode):
    n = idx.shape[1]
    rows = [factors[t][idx[:, t]] for t in range(n - 1, -1, -1) if t != mode]
    k = rows[0]
    for r in rows[1:]:
        k = (k[:, :, None] * r[:, None, :]).reshape(k.shape[0], -1)
    return y.at[idx[:, mode]].add(k * val[:, None])


class Tensor:
    """A sparse tensor held on one device in blocks of ``block`` nonzeros
    (the last block padded with zero values)."""

    def __init__(self, indices: np.ndarray, values: np.ndarray,
                 shape: Sequence[int], block: int, device=None) -> None:
        self.shape = tuple(int(s) for s in shape)
        self.nnz = int(indices.shape[0])
        self.xnorm2 = float(np.sum(np.square(values, dtype=np.float64)))
        self.device = device if device is not None else jax.devices()[0]
        nb = -(-self.nnz // block)
        pad = nb * block - self.nnz
        idx = np.concatenate([indices, np.zeros((pad, len(self.shape)), np.int32)])
        val = np.concatenate([values, np.zeros(pad, np.float32)])
        self.blocks = [
            (jax.device_put(idx[b * block:(b + 1) * block], self.device),
             jax.device_put(val[b * block:(b + 1) * block], self.device))
            for b in range(nb)
        ]

    def unfold(self, factors: Sequence[np.ndarray], mode: int) -> np.ndarray:
        """``Y_(mode)`` as a float64 host array, ``(I_mode, prod R_t)``."""
        k = int(np.prod([f.shape[1] for t, f in enumerate(factors) if t != mode]))
        fs = tuple(jax.device_put(np.asarray(f, np.float32), self.device)
                   for f in factors)
        y = jax.device_put(np.zeros((self.shape[mode], k), np.float32), self.device)
        for idx, val in self.blocks:
            y = _accumulate(y, idx, val, fs, mode=mode)
        return np.asarray(y, dtype=np.float64)


def qrp(a: np.ndarray, r: int) -> np.ndarray:
    """First ``r`` columns of Q of the column-pivoted QR of ``a``: at each
    step the column of largest residual norm is taken (the lowest index on a
    tie) and projected out of all the others."""
    a = np.array(a, dtype=np.float64)
    m, n = a.shape
    r = min(r, m, n)
    q = np.zeros((m, r))
    used = np.zeros(n, dtype=bool)
    for j in range(r):
        norms = np.einsum("ij,ij->j", a, a)
        norms[used] = -np.inf
        p = int(np.argmax(norms))
        used[p] = True
        nrm = np.sqrt(norms[p])
        v = a[:, p] / nrm if nrm > 1e-300 else np.eye(m)[:, j]
        q[:, j] = v
        a -= np.outer(v, v @ a)
    return q


def fold_last(g: np.ndarray, ranks: Sequence[int]) -> np.ndarray:
    """Fold ``G_(N)`` (R_N x prod R_t, the first mode fastest) into the core
    tensor (R_1, ..., R_N)."""
    return g.reshape(tuple(ranks)[::-1]).T


def init_factors(shape: Sequence[int], ranks: Sequence[int],
                 key) -> List[np.ndarray]:
    """Random orthonormal start: standard normals from ``key`` (one split per
    mode), orthonormalised."""
    keys = jax.random.split(key, len(shape))
    return [np.linalg.qr(np.asarray(jax.random.normal(k, (i, r), jnp.float32),
                                    np.float64))[0]
            for k, i, r in zip(keys, shape, ranks)]


def hooi(x: Tensor, ranks: Sequence[int], n_iter: int,
         key) -> Tuple[np.ndarray, List[np.ndarray], np.ndarray]:
    """``(core, factors, fit_history)`` of ``n_iter`` HOOI sweeps; the fit
    history is the relative error ``sqrt(||X||^2 - ||G||^2) / ||X||`` after
    each sweep."""
    n = len(x.shape)
    factors = init_factors(x.shape, ranks, key)
    hist = []
    for _ in range(n_iter):
        for mode in range(n):
            y = x.unfold(factors, mode)
            factors[mode] = qrp(y, ranks[mode])
        g = factors[n - 1].T @ y
        hist.append(np.sqrt(max(x.xnorm2 - float(np.sum(g * g)), 0.0) / x.xnorm2))
    return fold_last(g, [f.shape[1] for f in factors]), factors, np.asarray(hist)


def core_given(x: Tensor, factors: Sequence[np.ndarray]) -> np.ndarray:
    """The core ``X x_1 U_1^T ... x_N U_N^T`` for the given factors."""
    n = len(x.shape)
    y = x.unfold(factors, n - 1)
    g = np.asarray(factors[n - 1], np.float64).T @ y
    return fold_last(g, [f.shape[1] for f in factors])
