"""Property tests for the sparse invariants (hypothesis; optional dev dep).

The layout transforms (``sort_by_mode``, ``pad_to``, ``build_mode_layout``)
and the linearized unfolding index must all be *value-preserving*: whatever
permutation/padding the schedule applies, ``to_dense()`` — and therefore
every contraction — is unchanged. And the sparse TTM chain must equal the
dense ``ttm_chain`` oracle on arbitrary COO tensors, duplicates included.
"""
import numpy as np
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st

from repro.core.coo import SparseCOO, unfold_dense
from repro.core.kron import sparse_ttm_chain
from repro.core.ttm import ttm_chain
from repro.sparse.layout import build_mode_layout

SETTINGS = settings(max_examples=25, deadline=None)


@st.composite
def coo_tensors(draw, max_ndim=3, max_side=6, max_nnz=20):
    ndim = draw(st.integers(2, max_ndim))
    shape = tuple(draw(st.integers(1, max_side)) for _ in range(ndim))
    nnz = draw(st.integers(0, max_nnz))
    idx = np.array(
        [[draw(st.integers(0, s - 1)) for s in shape] for _ in range(nnz)],
        dtype=np.int32,
    ).reshape(nnz, ndim)
    vals = np.array(
        [draw(st.floats(-4, 4, allow_nan=False, width=32)) for _ in range(nnz)],
        dtype=np.float32,
    )
    return SparseCOO.from_parts(idx, vals, shape)


@SETTINGS
@given(coo=coo_tensors(), data=st.data())
def test_sort_by_mode_preserves_dense(coo, data):
    mode = data.draw(st.integers(0, coo.ndim - 1))
    want = np.asarray(coo.to_dense())
    got = np.asarray(coo.sort_by_mode(mode).to_dense())
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@SETTINGS
@given(coo=coo_tensors(), extra=st.integers(0, 17))
def test_pad_to_preserves_dense(coo, extra):
    want = np.asarray(coo.to_dense())
    got = np.asarray(coo.pad_to(coo.nnz + extra).to_dense())
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@SETTINGS
@given(coo=coo_tensors(), data=st.data())
def test_linearized_index_matches_unfolding(coo, data):
    """Scattering values at (i_mode, linearized col) rebuilds unfold(dense)."""
    mode = data.draw(st.integers(0, coo.ndim - 1))
    col = coo.linearized_index(mode)
    rest = int(np.prod([s for t, s in enumerate(coo.shape) if t != mode]))
    mat = np.zeros((coo.shape[mode], rest), dtype=np.float32)
    np.add.at(mat, (np.asarray(coo.indices)[:, mode], col), np.asarray(coo.values))
    want = np.asarray(unfold_dense(coo.to_dense(), mode))
    np.testing.assert_allclose(mat, want, rtol=1e-6, atol=1e-6)


@SETTINGS
@given(coo=coo_tensors(), data=st.data(), bn=st.sampled_from([4, 8, 32]),
       bi=st.sampled_from([4, 16]))
def test_mode_layout_streams_each_nonzero_once(coo, data, bn, bi):
    """The engine schedule is a permutation + padding: replaying it through a
    plain scatter reproduces to_dense()'s mode unfolding of the values."""
    mode = data.draw(st.integers(0, coo.ndim - 1))
    layout = build_mode_layout(coo, mode, bn=bn, bi=bi)
    real = layout.order[layout.valid > 0]
    assert sorted(real.tolist()) == list(range(coo.nnz))
    # replay: padded slots carry valid=0 so they add nothing
    rows_global = layout.blkmap.repeat(bn) * bi + layout.rel_row
    vals_src = np.asarray(coo.values)
    vals = (
        vals_src[layout.order] if coo.nnz else np.zeros(layout.order.shape, np.float32)
    ) * layout.valid
    acc = np.zeros((layout.n_row_blocks * bi,), dtype=np.float32)
    np.add.at(acc, rows_global, vals)
    want = np.zeros_like(acc)
    np.add.at(want, np.asarray(coo.indices)[:, mode], np.asarray(coo.values))
    np.testing.assert_allclose(acc, want, rtol=1e-6, atol=1e-6)


@SETTINGS
@given(coo=coo_tensors(), data=st.data(), seed=st.integers(0, 2**31 - 1))
def test_sparse_ttm_chain_matches_dense_oracle(coo, data, seed):
    mode = data.draw(st.integers(0, coo.ndim - 1))
    rng = np.random.default_rng(seed)
    ranks = [min(3, s) for s in coo.shape]
    factors = [
        jnp.asarray(rng.standard_normal((s, r)).astype(np.float32))
        for s, r in zip(coo.shape, ranks)
    ]
    got = np.asarray(sparse_ttm_chain(coo, factors, mode))
    want = np.asarray(
        unfold_dense(ttm_chain(coo.to_dense(), factors, skip=mode, transpose=True), mode)
    )
    scale = np.abs(want).max() + 1e-6
    np.testing.assert_allclose(got / scale, want / scale, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Shard padding (the sharded pipeline's even-split layer).
# ---------------------------------------------------------------------------

from repro.sparse.layout import shard_pad_nnz  # noqa: E402


@SETTINGS
@given(nnz=st.integers(0, 10_000), n_shards=st.integers(1, 64))
def test_shard_pad_nnz_is_minimal_multiple(nnz, n_shards):
    """The padded nnz is the MINIMAL multiple of the shard count that holds
    every nonzero (and is never zero: each shard owns at least one slot)."""
    p = shard_pad_nnz(nnz, n_shards)
    assert p % n_shards == 0 and p >= nnz and p >= n_shards
    # minimality: one shard-width less would drop nonzeros (or hit zero)
    assert p - n_shards < max(nnz, 1)
    # idempotent: padding an already-even count is the identity
    assert shard_pad_nnz(p, n_shards) == p


@SETTINGS
@given(coo=coo_tensors(), data=st.data(), n_shards=st.integers(1, 8),
       seed=st.integers(0, 2**31 - 1))
def test_shard_padding_preserves_unfolding_product(coo, data, n_shards, seed):
    """Explicit-zero padding to the shard multiple never changes any mode-n
    unfolding product: the padded tensor's sparse TTM chain equals the
    unpadded one's, for every mode."""
    mode = data.draw(st.integers(0, coo.ndim - 1))
    rng = np.random.default_rng(seed)
    factors = [
        jnp.asarray(rng.standard_normal((s, min(2, s))).astype(np.float32))
        for s in coo.shape
    ]
    padded = coo.pad_to(shard_pad_nnz(coo.nnz, n_shards))
    assert padded.nnz % n_shards == 0
    got = np.asarray(sparse_ttm_chain(padded, factors, mode))
    want = np.asarray(sparse_ttm_chain(coo, factors, mode))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# nnz bucketing + batch padding (the serving plane's shape-stability layer).
# ---------------------------------------------------------------------------

from repro.sparse.layout import bucket_nnz, pad_coo_batch  # noqa: E402


@SETTINGS
@given(nnz=st.integers(0, 5_000), n_shards=st.integers(1, 16),
       base=st.integers(1, 256))
def test_shard_pad_round_trips_with_bucket_nnz(nnz, n_shards, base):
    """The serving bucket grid and the shard grid compose stably: sharding a
    bucket boundary then re-applying either padding is a fixpoint, and the
    composition never drops below either grid alone."""
    b = bucket_nnz(nnz, base=base)
    p = shard_pad_nnz(b, n_shards)
    assert p >= b >= nnz
    assert shard_pad_nnz(p, n_shards) == p  # fixpoint under re-sharding
    assert bucket_nnz(p, base=base) >= p  # re-bucketing never shrinks it
    # and when the shard count divides the bucket boundary, sharding is free
    if b % n_shards == 0:
        assert p == b


@SETTINGS
@given(nnz=st.integers(0, 10_000), base=st.integers(1, 512),
       growth=st.floats(1.1, 4.0, allow_nan=False))
def test_bucket_nnz_properties(nnz, base, growth):
    b = bucket_nnz(nnz, base=base, growth=growth)
    assert b >= nnz and b >= base  # never drops nonzeros, never sub-base
    assert bucket_nnz(b, base=base, growth=growth) == b  # boundaries are fixpoints
    if nnz > base:
        # minimality: the next-smaller grid point is strictly below nnz
        prev = base
        while True:
            nxt = int(np.ceil(prev * growth))
            if nxt >= b:
                break
            prev = nxt
        assert prev < nnz


@SETTINGS
@given(nnz_a=st.integers(0, 500), nnz_b=st.integers(0, 500))
def test_bucket_nnz_monotone(nnz_a, nnz_b):
    lo, hi = sorted((nnz_a, nnz_b))
    assert bucket_nnz(lo) <= bucket_nnz(hi)


@st.composite
def same_shape_coo_batches(draw, max_ndim=3, max_side=5, max_nnz=12, max_k=4):
    ndim = draw(st.integers(2, max_ndim))
    shape = tuple(draw(st.integers(1, max_side)) for _ in range(ndim))
    coos = []
    for _ in range(draw(st.integers(1, max_k))):
        nnz = draw(st.integers(0, max_nnz))
        idx = np.array(
            [[draw(st.integers(0, s - 1)) for s in shape] for _ in range(nnz)],
            dtype=np.int32,
        ).reshape(nnz, ndim)
        vals = np.array(
            [draw(st.floats(-4, 4, allow_nan=False, width=32))
             for _ in range(nnz)],
            dtype=np.float32,
        )
        coos.append(SparseCOO.from_parts(idx, vals, shape))
    return coos


@SETTINGS
@given(coos=same_shape_coo_batches(), extra=st.integers(0, 9))
def test_pad_coo_batch_preserves_each_member_dense(coos, extra):
    shape = coos[0].shape
    nnz_max = max(c.nnz for c in coos)
    idx, val = pad_coo_batch(coos, target_nnz=nnz_max + extra)
    assert idx.shape == (len(coos), nnz_max + extra, len(shape))
    for k, c in enumerate(coos):
        rebuilt = SparseCOO.from_parts(idx[k], val[k], shape)
        np.testing.assert_allclose(
            np.asarray(rebuilt.to_dense()), np.asarray(c.to_dense()),
            rtol=1e-6, atol=1e-6,
        )


# Ragged-nnz batched-decompose parity (ISSUE 4 satellite). The spec is FIXED
# and every batch pads to one bucket boundary so hypothesis explores data, not
# compile-cache keys: the whole property reuses two compiled programs.
_PARITY_SHAPE = (6, 5, 4)
_PARITY_BUCKET = 32


@st.composite
def ragged_coo_batches(draw, k=3, max_nnz=24):
    coos = []
    for _ in range(k):
        nnz = draw(st.integers(1, max_nnz))
        idx = np.array(
            [[draw(st.integers(0, s - 1)) for s in _PARITY_SHAPE]
             for _ in range(nnz)],
            dtype=np.int32,
        ).reshape(nnz, len(_PARITY_SHAPE))
        # bounded away from 0 so no member is an (undefined) all-zero tensor;
        # a width-32 bound must be a float32 value itself
        vals = np.array(
            [draw(st.floats(float(np.float32(0.1)), 4, allow_nan=False,
                            width=32))
             * (-1 if draw(st.booleans()) else 1) for _ in range(nnz)],
            dtype=np.float32,
        )
        coos.append(SparseCOO.from_parts(idx, vals, _PARITY_SHAPE))
    return coos


@settings(max_examples=10, deadline=None)
@given(coos=ragged_coo_batches())
def test_batched_padded_decompose_matches_per_tensor(coos):
    """The serving contract: batched-and-padded results are allclose to
    per-tensor decompose across ragged nnz."""
    from repro import tucker

    spec = tucker.TuckerSpec(shape=_PARITY_SHAPE, ranks=(2, 2, 2),
                             method="gram", n_iter=2)
    plan = tucker.plan(spec)
    got = plan.batch(coos, pad_nnz_to=_PARITY_BUCKET)
    for c, g in zip(coos, got):
        # sequential reference on the SAME padded nnz shape (one compiled
        # per-tensor program for the whole property, not one per drawn nnz)
        ref = plan(c.pad_to(_PARITY_BUCKET))
        np.testing.assert_allclose(np.asarray(g.core), np.asarray(ref.core),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(g.fit_history, ref.fit_history, atol=1e-5)
        for fg, fr in zip(g.factors, ref.factors):
            np.testing.assert_allclose(np.asarray(fg), np.asarray(fr),
                                       rtol=1e-4, atol=1e-4)
