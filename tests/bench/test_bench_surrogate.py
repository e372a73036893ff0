"""The seeded surrogate: exact and distinct nonzeros, a planted structure
that a Tucker model of the planted rank fits to about one half."""
import jax
import numpy as np

from bench import reference, surrogate


def test_exact_distinct_nonzeros_inside_the_shape():
    shape = (37, 29, 41)
    p = surrogate.pattern(shape, 4000, 3, np.random.default_rng(5))
    assert p.indices.shape == (4000, 3) and p.indices.dtype == np.int32
    assert np.all(p.indices >= 0) and np.all(p.indices < np.asarray(shape))
    lin = np.ravel_multi_index(p.indices.T, shape)
    assert np.unique(lin).size == 4000
    planted = p.block >= 0
    assert 0 < planted.sum() < 4000
    v = p.values(np.random.default_rng(6))
    assert v.dtype == np.float32 and np.all(v > 0)
    # the planted blocks hold 3/4 of the energy
    e = np.square(v.astype(np.float64))
    assert abs(e[planted].sum() / e.sum() - 0.75) < 1e-5


def test_the_seed_decides_everything():
    a = surrogate.surrogate((20, 30, 25), 900, 2, np.random.default_rng(2**31 + 77))
    b = surrogate.surrogate((20, 30, 25), 900, 2, np.random.default_rng(2**31 + 77))
    c = surrogate.surrogate((20, 30, 25), 900, 2, np.random.default_rng(2**31 + 78))
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])


def test_fresh_values_keep_the_pattern():
    p = surrogate.pattern((20, 30, 25), 900, 2, np.random.default_rng(3))
    v1, v2 = p.values(np.random.default_rng(1)), p.values(np.random.default_rng(2))
    assert not np.array_equal(v1, v2)


def test_fit_near_one_half_at_the_planted_rank():
    shape = (60, 50, 40)
    idx, val = surrogate.surrogate(shape, 3000, 4, np.random.default_rng(11))
    x = reference.Tensor(idx, val, shape, block=1024, device=jax.devices()[0])
    _, _, hist = reference.hooi(x, (4, 4, 4), 4, jax.random.PRNGKey(0))
    assert 0.45 < hist[-1] < 0.55
    assert hist[-1] <= hist[0]
