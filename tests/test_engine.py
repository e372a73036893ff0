"""Differential harness for the sweep engines (core.engine).

Contract: every available engine must produce, for every mode, an unfolding
Y_(n) within tolerance of the dense ``ttm_chain`` oracle — across tensor
orders, dtypes, ranks, and pathological sparsity patterns — and every engine
must drive a sparse HOOI fit (``tucker.decompose``) to the same result. Any new engine (or any change to
the Pallas kernels / layouts) has to pass this file before it can ship.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import tucker
from repro.core import engine as E
from repro.core.coo import SparseCOO, unfold_dense
from repro.core.ttm import ttm_chain, ttm_unfolded
from repro.sparse.generators import low_rank_sparse_tensor, random_sparse_tensor
from repro.sparse.layout import build_mode_layout, layout_padding_fraction

ENGINES = E.available_engines()
RNG = np.random.default_rng(0)


def _factors(shape, ranks, dtype=jnp.float32):
    return [
        jnp.asarray(RNG.standard_normal((s, r)).astype(np.float32), dtype=dtype)
        for s, r in zip(shape, ranks)
    ]


def _oracle_unfolding(coo: SparseCOO, factors, mode: int) -> np.ndarray:
    """Dense ground truth: unfold(X x_{t!=n} U_t^T, n) via the TTM chain."""
    dense = coo.to_dense().astype(jnp.float32)
    f32 = [f.astype(jnp.float32) for f in factors]
    return np.asarray(unfold_dense(ttm_chain(dense, f32, skip=mode, transpose=True), mode))


def _assert_all_engines_match(coo, ranks, tol=2e-5, dtype=jnp.float32):
    factors = _factors(coo.shape, ranks, dtype)
    for mode in range(coo.ndim):
        want = _oracle_unfolding(coo, factors, mode)
        scale = np.abs(want).max() + 1e-9
        for name in ENGINES:
            got = np.asarray(E.make_engine(name).mode_unfolding(coo, factors, mode))
            assert got.shape == want.shape, (name, mode, got.shape, want.shape)
            err = np.abs(got - want).max() / scale
            assert err < tol, f"engine={name} mode={mode} relerr={err:.2e}"


# ---------------------------------------------------------------------------
# Engine vs dense oracle: modes x ranks x orders.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "shape,ranks,density",
    [
        ((40, 30, 20), (6, 5, 4), 0.02),  # paper's 3-way case
        ((25, 25, 25), (4, 4, 4), 0.05),  # cubic, equal ranks
        ((12, 10, 8, 6), (3, 3, 2, 2), 0.01),  # order-4 falls back to chained kron
        ((30, 20), (4, 3), 0.05),  # order-2 degenerate kron
    ],
)
def test_engines_match_oracle(shape, ranks, density):
    coo = random_sparse_tensor(shape, density, seed=hash(shape) % 2**31)
    _assert_all_engines_match(coo, ranks)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 4e-2)])
def test_engines_match_oracle_dtypes(dtype, tol):
    coo = random_sparse_tensor((30, 24, 18), 0.03, seed=7)
    _assert_all_engines_match(coo, (5, 4, 3), tol=tol, dtype=dtype)


# ---------------------------------------------------------------------------
# Pathological sparsity patterns.
# ---------------------------------------------------------------------------


def test_engines_empty_tensor():
    coo = SparseCOO.from_parts(
        np.zeros((0, 3), np.int32), np.zeros((0,), np.float32), (10, 8, 6)
    )
    _assert_all_engines_match(coo, (3, 3, 2))


def test_engines_duplicate_coordinates():
    # COO semantics: duplicates accumulate (to_dense uses scatter-add).
    idx = np.array([[1, 2, 3], [1, 2, 3], [0, 0, 0], [9, 7, 5]], np.int32)
    vals = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    _assert_all_engines_match(SparseCOO.from_parts(idx, vals, (10, 8, 6)), (3, 3, 2))


def test_engines_explicit_padding_rows():
    # zero-valued entries at coordinate 0 (the pad_to convention) contribute 0.
    idx = np.array([[5, 1, 2], [0, 0, 0], [0, 0, 0], [2, 3, 4]], np.int32)
    vals = np.array([1.0, 0.0, 0.0, 2.0], np.float32)
    _assert_all_engines_match(SparseCOO.from_parts(idx, vals, (10, 8, 6)), (3, 3, 2))


def test_engines_single_dense_slice():
    # all nonzeros in one mode-0 slice of a large mode: most row blocks empty.
    idx = np.array([[4, 1, 2], [4, 3, 1], [4, 0, 0]], np.int32)
    vals = np.array([1.0, 2.0, 3.0], np.float32)
    _assert_all_engines_match(SparseCOO.from_parts(idx, vals, (300, 8, 6)), (4, 3, 2))


def test_engines_nnz_not_block_multiple():
    # 130 nonzeros with bn=128 default: second block is mostly padding.
    coo = random_sparse_tensor((50, 40, 30), 130 / (50 * 40 * 30), seed=11)
    _assert_all_engines_match(coo, (5, 4, 3))


# ---------------------------------------------------------------------------
# sparse HOOI fit parity across engines (acceptance criterion: >= 3 tensors).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "tensor_id",
    ["random-3way", "lowrank-3way", "random-4way"],
)
def test_hooi_sparse_engine_fit_parity(tensor_id):
    if tensor_id == "random-3way":
        coo = random_sparse_tensor((30, 30, 30), 0.02, seed=1)
        ranks = (4, 4, 4)
    elif tensor_id == "lowrank-3way":
        coo, _ = low_rank_sparse_tensor((24, 20, 16), (3, 2, 2), 0.15, seed=2)
        ranks = (3, 2, 2)
    else:
        coo = random_sparse_tensor((14, 12, 10, 8), 0.01, seed=3)
        ranks = (3, 3, 2, 2)
    ref = tucker.decompose(coo, ranks, n_iter=3, method="gram", engine="xla")
    for name in ENGINES:
        res = tucker.decompose(coo, ranks, n_iter=3, method="gram", engine=name)
        assert res.engine == name
        assert abs(float(res.rel_error) - float(ref.rel_error)) < 1e-4, name
        np.testing.assert_allclose(
            np.asarray(res.core), np.asarray(ref.core), rtol=1e-3, atol=1e-3
        )


def test_hooi_sparse_engine_auto_resolves():
    coo = random_sparse_tensor((15, 12, 10), 0.05, seed=5)
    res = tucker.decompose(coo, (3, 3, 2), n_iter=1, method="gram", engine="auto")
    want = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert res.engine == want


def test_unknown_engine_raises():
    with pytest.raises(ValueError):
        E.resolve_engine("fpga")


# ---------------------------------------------------------------------------
# Engine internals: layout cache, core TTM dispatch, layout invariants.
# ---------------------------------------------------------------------------


def test_engine_layout_cache_reused():
    coo = random_sparse_tensor((20, 16, 12), 0.05, seed=8)
    eng = E.make_engine("pallas")
    fs = _factors(coo.shape, (3, 3, 2))
    eng.mode_unfolding(coo, fs, 0)
    first = eng.layouts[0]
    eng.mode_unfolding(coo, fs, 0)
    assert eng.layouts[0] is first  # schedule built once, reused across sweeps


def test_engine_rebinds_on_new_tensor():
    """One engine fed different tensors must rebuild its schedules, not
    silently replay the first tensor's nonzero order against the second."""
    eng = E.make_engine("pallas")
    coo_a = random_sparse_tensor((20, 16, 12), 0.05, seed=21)
    coo_b = random_sparse_tensor((22, 18, 14), 0.04, seed=22)
    for coo in (coo_a, coo_b, coo_a):
        fs = _factors(coo.shape, (3, 3, 2))
        want = _oracle_unfolding(coo, fs, 0)
        got = np.asarray(eng.mode_unfolding(coo, fs, 0))
        scale = np.abs(want).max() + 1e-9
        assert np.abs(got - want).max() / scale < 2e-5


def test_sparse_chain_kernel_empty_tensor():
    """The public kernel wrapper (not just the engine) must survive nnz==0."""
    from repro.kernels import ops

    coo = SparseCOO.from_parts(
        np.zeros((0, 3), np.int32), np.zeros((0,), np.float32), (10, 8, 6)
    )
    fs = _factors(coo.shape, (3, 3, 2))
    got = np.asarray(ops.sparse_ttm_chain_kernel(coo, fs, 0))
    assert got.shape == (10, 6) and not got.any()


@pytest.mark.parametrize("mode", [0, 1])
def test_sparse_chain_kernel_order2(mode):
    """ops.sparse_ttm_chain_kernel on a matrix (order-2 COO): degenerate
    single-factor 'Kron row' must work, matching the dense oracle."""
    from repro.kernels import ops

    coo = random_sparse_tensor((30, 20), 0.05, seed=23)
    fs = _factors(coo.shape, (4, 3))
    want = _oracle_unfolding(coo, fs, mode)
    got = np.asarray(ops.sparse_ttm_chain_kernel(coo, fs, mode))
    scale = np.abs(want).max() + 1e-9
    assert np.abs(got - want).max() / scale < 2e-5


def test_core_ttm_engine_dispatch():
    y = jnp.asarray(RNG.standard_normal((64, 48)).astype(np.float32))
    u = jnp.asarray(RNG.standard_normal((8, 48)).astype(np.float32))
    want = np.asarray(ttm_unfolded(y, u))
    for name in ENGINES:
        got = np.asarray(ttm_unfolded(y, u, engine=name))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_mode_layout_invariants(mode):
    coo = random_sparse_tensor((37, 29, 23), 0.03, seed=13)
    layout = build_mode_layout(coo, mode, bn=32, bi=16)
    rows = np.asarray(coo.indices)[:, mode]
    # every real nonzero streamed exactly once
    real = layout.order[layout.valid > 0]
    assert sorted(real.tolist()) == list(range(coo.nnz))
    # each nnz block targets exactly the row block the plan says
    n_blocks = layout.blkmap.shape[0]
    for b in range(n_blocks):
        sl = slice(b * layout.bn, (b + 1) * layout.bn)
        v = layout.valid[sl] > 0
        if v.any():
            tgt = rows[layout.order[sl][v]] // layout.bi
            assert (tgt == layout.blkmap[b]).all()
            assert (rows[layout.order[sl][v]] % layout.bi == layout.rel_row[sl][v]).all()
    # first flags: exactly one per distinct target row block
    assert layout.first.sum() == len(set(layout.blkmap.tolist()))
    # segments partition the sorted nonzeros by row coordinate
    assert layout.segments[0] == 0 and layout.segments[-1] == coo.nnz
    for i in range(coo.shape[mode]):
        lo, hi = layout.row_segment(i)
        assert hi - lo == int((rows == i).sum())
    assert 0.0 <= layout_padding_fraction(layout) < 1.0
