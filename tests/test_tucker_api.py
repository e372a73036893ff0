"""Contract tests for the repro.tucker plan/execute front-end.

Acceptance criteria under test (ISSUE 3):

* a ``TuckerPlan`` called twice on distinct same-shape/same-spec tensors
  shows 0 retraces and is bit-identical to a fresh ``decompose`` on both
  engines;
* ``TuckerPlan.batch`` over k tensors matches k sequential calls;
* ``use_kron_reuse`` follows one rule (the engine comes from one
  construction helper);
* ``TuckerResult`` survives an empty fit history (no ``hist[-1]`` crash).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import tucker
from repro.core import engine as E
from repro.core import hooi
from repro.core.coo import SparseCOO
from repro.sparse.generators import random_sparse_tensor

ENGINES = E.available_engines()


def _total_traces():
    return sum(hooi.SWEEP_TRACE_COUNTS.values())


def _spec(shape=(20, 16, 12), ranks=(3, 3, 2), **kw):
    kw.setdefault("method", "gram")
    kw.setdefault("n_iter", 3)
    return tucker.TuckerSpec(shape=shape, ranks=ranks, **kw)


# ---------------------------------------------------------------------------
# TuckerSpec: validated once, frozen, hashable.
# ---------------------------------------------------------------------------


def test_spec_validation_errors():
    with pytest.raises(ValueError, match="engine"):
        _spec(engine="fpga")
    with pytest.raises(ValueError, match="method"):
        _spec(method="qr")
    with pytest.raises(ValueError, match="n_iter"):
        _spec(n_iter=0)
    with pytest.raises(ValueError, match="algorithm"):
        _spec(algorithm="cp")
    with pytest.raises(ValueError, match="order"):
        tucker.TuckerSpec(shape=(4, 4, 4), ranks=(2, 2))
    with pytest.raises(ValueError, match="tol"):
        _spec(tol=-1.0)


def test_spec_normalizes_and_hashes():
    s = tucker.TuckerSpec(shape=[130, 150], ranks=[30, 35])
    # the paper's angiogram rank [30,35] clamps to the representable [30,30]
    assert s.ranks == (30, 30)
    assert s.shape == (130, 150)
    assert hash(s) == hash(tucker.TuckerSpec(shape=(130, 150), ranks=(30, 35)))
    with pytest.raises(Exception):  # frozen
        s.n_iter = 7


def test_spec_dtype_canonicalization():
    assert _spec().dtype == "auto"
    assert _spec(dtype=jnp.float32).dtype == "float32"
    assert _spec(dtype="bfloat16").resolved_dtype() == jnp.bfloat16


def test_plan_cache_lru_eviction_and_hooks():
    tucker.clear_plan_cache()
    evicted = []
    remove = tucker.add_plan_eviction_hook(lambda key, plan: evicted.append(key))
    evictions0 = tucker.plan_cache_info()["evictions"]  # lifetime counter
    try:
        tucker.set_plan_cache_capacity(2)
        s1 = _spec(shape=(10, 8, 6), ranks=(2, 2, 2))
        s2 = _spec(shape=(10, 8, 6), ranks=(3, 2, 2))
        s3 = _spec(shape=(10, 8, 6), ranks=(2, 3, 2))
        p1 = tucker.plan(s1)
        tucker.plan(s2)
        assert tucker.plan(s1) is p1  # refreshes s1's recency
        tucker.plan(s3)  # evicts s2, the least recently used
        assert [k[0] for k in evicted] == [s2]
        assert tucker.plan(s1) is p1  # s1 survived
        assert tucker.plan_cache_info()["size"] == 2
        assert tucker.plan_cache_info()["evictions"] - evictions0 == 1
        # shrinking the capacity evicts immediately
        tucker.set_plan_cache_capacity(1)
        assert tucker.plan_cache_info()["size"] == 1
        with pytest.raises(ValueError, match="capacity"):
            tucker.set_plan_cache_capacity(0)
    finally:
        remove()
        tucker.set_plan_cache_capacity(None)
    # deregistered hook no longer fires
    n = len(evicted)
    tucker.clear_plan_cache()
    assert len(evicted) == n


def test_plan_cache_concurrent_lookup_builds_once():
    """The satellite: concurrent plan() callers of one new spec must share a
    single TuckerPlan (one engine, one schedule cache, one compiled-program
    family) and record one cache miss — a racing builder's transient copy is
    discarded, never returned or executed."""
    import threading

    tucker.clear_plan_cache()
    spec = _spec(shape=(11, 9, 7), ranks=(2, 2, 2))
    misses0 = tucker.plan_cache_info()["misses"]
    built = []
    barrier = threading.Barrier(8)

    def worker():
        barrier.wait()  # maximize the race window
        built.append(tucker.plan(spec))

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len({id(p) for p in built}) == 1
    assert tucker.plan_cache_info()["misses"] - misses0 == 1


def test_plan_cache_returns_same_plan():
    spec = _spec(shape=(10, 8, 6), ranks=(2, 2, 2))
    assert tucker.plan(spec) is tucker.plan(spec)
    # a prebuilt engine bypasses the cache and wraps that engine
    eng = E.make_engine("xla")
    p = tucker.plan(spec, engine=eng)
    assert p is not tucker.plan(spec) and p.engine is eng


def test_plan_rejects_wrong_shape_and_type():
    p = tucker.plan(_spec(shape=(10, 8, 6), ranks=(2, 2, 2)))
    with pytest.raises(ValueError, match="does not match the planned"):
        p(random_sparse_tensor((10, 8, 7), 0.05, seed=0))
    with pytest.raises(TypeError, match="SparseCOO"):
        p(np.zeros((10, 8, 6), np.float32))


# ---------------------------------------------------------------------------
# Acceptance: zero retraces across distinct same-shape tensors, and
# bit-identical results to a fresh plan's — on every engine.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_plan_zero_retrace_and_bit_parity_with_fresh_decompose(engine):
    spec = _spec(engine=engine)
    p = tucker.plan(spec)
    coo_a = random_sparse_tensor(spec.shape, 0.05, seed=61)
    coo_b = random_sparse_tensor(spec.shape, 0.05, seed=62)
    p(coo_a)  # warm: may trace + build schedules
    traces = _total_traces()
    res_a = p(coo_a)
    res_b = p(coo_b)
    assert _total_traces() == traces, "same-spec call retraced"
    assert res_a.retraces == 0 and res_b.retraces == 0
    assert res_a.dispatches == 1  # whole multi-sweep loop is one program
    for coo, res in ((coo_a, res_a), (coo_b, res_b)):
        tucker.clear_plan_cache()  # a fresh plan: new engine, new schedules
        ref = tucker.decompose(coo, spec.ranks, n_iter=spec.n_iter,
                               method=spec.method, engine=engine)
        np.testing.assert_array_equal(np.asarray(res.core), np.asarray(ref.core))
        for a, b in zip(res.factors, ref.factors):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(res.fit_history, ref.fit_history)


# ---------------------------------------------------------------------------
# batch(): one dispatch for k tensors, matching k sequential calls.
# ---------------------------------------------------------------------------


def test_batch_matches_sequential_xla():
    spec = _spec()
    p = tucker.plan(spec)
    # distinct nnz per tensor: exercises the pad-to-max path
    coos = [random_sparse_tensor(spec.shape, d, seed=s)
            for d, s in ((0.05, 71), (0.03, 72), (0.06, 73))]
    seq = [p(c) for c in coos]
    d0 = hooi.SWEEP_DISPATCH_COUNTS[("xla", "scan")]
    got = p.batch(coos)
    assert hooi.SWEEP_DISPATCH_COUNTS[("xla", "scan")] - d0 == 1  # ONE dispatch
    assert len(got) == len(seq)
    for g, s in zip(got, seq):
        # the vmapped and the sequential programs reduce in a different
        # order, so the fit may differ in its last bit
        np.testing.assert_allclose(g.fit_history, s.fit_history,
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(
            np.asarray(g.core), np.asarray(s.core), rtol=1e-5, atol=1e-5
        )
        for fg, fs in zip(g.factors, s.factors):
            np.testing.assert_allclose(
                np.asarray(fg), np.asarray(fs), rtol=1e-5, atol=1e-5
            )


def test_batch_second_call_zero_retraces():
    spec = _spec(shape=(15, 12, 10), ranks=(3, 2, 2))
    p = tucker.plan(spec)
    make = lambda s: [random_sparse_tensor(spec.shape, 0.05, seed=s + i)
                      for i in range(3)]
    p.batch(make(81))  # warm
    traces = _total_traces()
    res = p.batch(make(91))
    assert _total_traces() == traces
    assert res[0].retraces == 0


def test_batch_with_tol_matches_sequential():
    spec = _spec(shape=(15, 12, 10), ranks=(3, 2, 2), n_iter=8, tol=1e-3)
    p = tucker.plan(spec)
    coos = [random_sparse_tensor(spec.shape, 0.06, seed=s) for s in (95, 96)]
    seq = [p(c) for c in coos]
    got = p.batch(coos)
    for g, s in zip(got, seq):
        assert g.n_sweeps == s.n_sweeps  # per-tensor early exit preserved
        np.testing.assert_array_equal(g.fit_history, s.fit_history)


@pytest.mark.parametrize(
    "engine,precision,use_kron_reuse",
    [("pallas", "fp32", False), ("xla", "fp32", True),
     ("xla", "bf16_fp32acc", False)],
)
def test_batch_fallback_configs_match_sequential(engine, precision, use_kron_reuse):
    """Configs whose schedules can't share one vmapped program fall back to
    sequential execution with identical results."""
    if engine not in ENGINES:
        pytest.skip("pallas unavailable")
    spec = _spec(shape=(10, 8, 6), ranks=(2, 2, 2), n_iter=2, engine=engine,
                 precision=precision, use_kron_reuse=use_kron_reuse)
    p = tucker.plan(spec)
    assert not p.supports_batched_dispatch
    coos = [random_sparse_tensor(spec.shape, 0.08, seed=s) for s in (85, 86)]
    seq = [p(c) for c in coos]
    d0 = hooi.SWEEP_DISPATCH_COUNTS[(p.engine.name, "scan")]
    got = p.batch(coos)
    assert hooi.SWEEP_DISPATCH_COUNTS[(p.engine.name, "scan")] - d0 == len(coos)
    for g, s in zip(got, seq):
        np.testing.assert_array_equal(np.asarray(g.core), np.asarray(s.core))


def test_batch_empty_and_zero_nnz_edge_cases():
    """The service-facing edge cases: an empty request list is a defined
    no-op, a zero-nnz member is a clear ValueError (its relative error is
    0/0) — never an opaque XLA shape error or silent NaN."""
    import jax.numpy as jnp

    p = tucker.plan(_spec(shape=(10, 8, 6), ranks=(2, 2, 2)))
    assert p.batch([]) == []
    empty = SparseCOO(jnp.zeros((0, 3), jnp.int32), jnp.zeros((0,), jnp.float32),
                      (10, 8, 6))
    with pytest.raises(ValueError, match="zero stored nonzeros"):
        p.batch([random_sparse_tensor((10, 8, 6), 0.05, seed=3), empty])


def test_batch_pad_nnz_to_bucket_shares_one_program():
    """Padding two different-max-nnz flushes to one bucket boundary must
    produce identical-to-sequential results AND reuse one compiled batched
    program (the serving plane's amortization contract)."""
    from repro.sparse.layout import bucket_nnz

    spec = _spec(shape=(15, 12, 10), ranks=(3, 2, 2))
    p = tucker.plan(spec)
    a = [random_sparse_tensor(spec.shape, d, seed=s)
         for d, s in ((0.05, 11), (0.03, 12))]
    b = [random_sparse_tensor(spec.shape, d, seed=s)
         for d, s in ((0.04, 13), (0.02, 14))]
    bucket = bucket_nnz(max(c.nnz for c in a + b), base=64)
    p.batch(a, pad_nnz_to=bucket)  # warm: compiles the (k=2, bucket) program
    traces = _total_traces()
    got = p.batch(b, pad_nnz_to=bucket)  # different batch max, same bucket
    assert _total_traces() == traces, "bucketed flush retraced"
    for c, g in zip(b, got):
        s = p(c)
        np.testing.assert_allclose(np.asarray(g.core), np.asarray(s.core),
                                   rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="drop nonzeros"):
        p.batch(a, pad_nnz_to=1)


def test_batch_accepts_typed_and_raw_prng_keys():
    """Both key styles flow through the host-side batched key assembly and
    land on the same init as the per-tensor path."""
    spec = _spec(shape=(10, 8, 6), ranks=(2, 2, 2), n_iter=2)
    p = tucker.plan(spec)
    coos = [random_sparse_tensor(spec.shape, 0.06, seed=s) for s in (21, 22)]
    got = p.batch(coos, keys=[jax.random.key(7), jax.random.PRNGKey(9)])
    for c, k, g in zip(coos, (jax.random.PRNGKey(7), jax.random.PRNGKey(9)), got):
        ref = p(c, key=k)
        np.testing.assert_allclose(np.asarray(g.core), np.asarray(ref.core),
                                   rtol=1e-5, atol=1e-5)


def test_batch_nondefault_key_impl_keeps_reproducibility():
    """Non-threefry typed keys (rbg) generate different streams under vmap,
    so batching them must fall back to sequential calls — same key, same
    result, never a silently different init (or a key_data shape crash)."""
    spec = _spec(shape=(10, 8, 6), ranks=(2, 2, 2), n_iter=2)
    p = tucker.plan(spec)
    coos = [random_sparse_tensor(spec.shape, 0.06, seed=s) for s in (23, 24)]
    keys = [jax.random.key(7, impl="rbg"), jax.random.key(9, impl="rbg")]
    d0 = hooi.SWEEP_DISPATCH_COUNTS[("xla", "scan")]
    got = p.batch(coos, keys=keys)
    assert hooi.SWEEP_DISPATCH_COUNTS[("xla", "scan")] - d0 == len(coos)
    for c, k, g in zip(coos, keys, got):
        ref = p(c, key=k)
        np.testing.assert_array_equal(np.asarray(g.core), np.asarray(ref.core))


def test_batch_rejects_mixed_shapes_and_dense_specs():
    p = tucker.plan(_spec(shape=(10, 8, 6), ranks=(2, 2, 2)))
    with pytest.raises(ValueError, match="does not match the planned"):
        p.batch([random_sparse_tensor((10, 8, 6), 0.05, seed=1),
                 random_sparse_tensor((10, 8, 7), 0.05, seed=2)])
    pd = tucker.plan(_spec(shape=(10, 8, 6), ranks=(2, 2, 2), algorithm="dense"))
    with pytest.raises(ValueError, match="algorithm='sparse'"):
        pd.batch([])


# ---------------------------------------------------------------------------
# Satellite: use_kron_reuse follows ONE rule, set by one engine helper.
# ---------------------------------------------------------------------------


def test_kron_reuse_actually_taken():
    spec = _spec(shape=(16, 14, 12), ranks=(3, 3, 2), engine="xla",
                 use_kron_reuse=True)
    p = tucker.plan(spec)
    assert p.engine.use_kron_reuse  # one helper, one rule
    coo = random_sparse_tensor(spec.shape, 0.06, seed=55)
    res = p(coo)
    # the reuse path really ran: the engine built a dedup plan per mode
    assert sorted(p.engine.kron_plans) == [0, 1, 2]
    assert res.schedule_builds > 0
    # and it changed nothing numerically vs the non-reuse plan
    plain = tucker.plan(_spec(shape=spec.shape, ranks=spec.ranks,
                              engine="xla"))(coo)
    np.testing.assert_allclose(res.fit_history, plain.fit_history, atol=1e-5)


def test_prebuilt_engine_reuse_mismatch_warns_both_ways():
    spec = _spec(shape=(10, 8, 6), ranks=(2, 2, 2), use_kron_reuse=True,
                 engine="xla")
    eng = E.make_engine("xla")  # built WITHOUT reuse
    with pytest.warns(RuntimeWarning, match="use_kron_reuse=True is ignored"):
        tucker.plan(spec, engine=eng)
    # and the mirror direction: a reuse engine overriding a non-reuse spec
    spec_plain = _spec(shape=(10, 8, 6), ranks=(2, 2, 2), engine="xla")
    eng_reuse = E.make_engine("xla", use_kron_reuse=True)
    with pytest.warns(RuntimeWarning, match="overrides use_kron_reuse=False"):
        tucker.plan(spec_plain, engine=eng_reuse)


def test_factors_init_survives_donation():
    """Caller-supplied warm-start factors must not be deleted by the donating
    compiled pipeline — a warm-start loop reuses its seed factors."""
    spec = _spec(shape=(12, 10, 8), ranks=(2, 2, 2))
    p = tucker.plan(spec)
    coo = random_sparse_tensor(spec.shape, 0.05, seed=63)
    fs = hooi.init_factors(spec.shape, spec.ranks, jax.random.PRNGKey(1))
    a = p(coo, factors_init=fs)
    b = p(coo, factors_init=fs)  # would raise 'Array has been deleted' before
    np.testing.assert_array_equal(a.fit_history, b.fit_history)
    assert np.isfinite(float(jnp.sum(fs[0])))  # seed factors still alive


# ---------------------------------------------------------------------------
# Satellite: empty fit history must not crash result construction.
# ---------------------------------------------------------------------------


def test_result_from_empty_history():
    res = tucker.TuckerResult.from_history(
        jnp.zeros((2, 2)), [], np.asarray([]), engine="xla"
    )
    assert res.n_sweeps == 0
    assert np.isnan(float(res.rel_error))
    assert res.fit_history.size == 0


def test_driver_survives_all_masked_history(monkeypatch):
    """If every sweep were masked (all-sentinel history), the plan returns an
    empty history and NaN rel_error instead of IndexError on hist[-1]."""
    spec = _spec(shape=(10, 8, 6), ranks=(2, 2, 2), engine="xla")
    p = tucker.plan(spec)
    coo = random_sparse_tensor(spec.shape, 0.05, seed=57)
    p(coo)  # warm, sanity
    monkeypatch.setattr(
        hooi, "_fetch_history",
        lambda x: np.full_like(np.asarray(jax.device_get(x)), hooi._SKIPPED),
    )
    res = p(coo)
    assert res.n_sweeps == 0 and np.isnan(float(res.rel_error))


# ---------------------------------------------------------------------------
# TuckerResult metadata + dense/complete algorithms through the front-end.
# ---------------------------------------------------------------------------


def test_result_metadata_fields():
    spec = _spec(shape=(20, 16, 12), ranks=(3, 3, 2))
    res = tucker.plan(spec)(random_sparse_tensor(spec.shape, 0.05, seed=58))
    assert res.spec == spec  # the cached plan's spec (equal, maybe not identical)
    from repro.core.reconstruct import compression_ratio

    assert res.compression_ratio == pytest.approx(
        compression_ratio(spec.shape, spec.ranks)
    )
    assert res.n_sweeps == len(res.fit_history) == spec.n_iter
    assert res.engine in ("xla", "pallas")


def test_plan_stats_accumulate():
    spec = _spec(shape=(12, 10, 8), ranks=(2, 2, 2))
    p = tucker.TuckerPlan(spec)  # uncached: its stats are this test's alone
    coo = random_sparse_tensor(spec.shape, 0.05, seed=59)
    p(coo)
    p(coo)
    assert p.stats.calls == 2
    assert p.stats.dispatches == 2  # the whole fit is one program: 1/call


def test_dense_plan_warm_start():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((10, 9, 8)).astype(np.float32))
    p = tucker.plan(_spec(shape=(10, 9, 8), ranks=(3, 2, 2), algorithm="dense",
                          method="svd", n_iter=2))
    cold = p(x)
    warm = p(x, factors_init=cold.factors)
    assert float(warm.rel_error) <= float(cold.rel_error) + 1e-6


def test_decompose_infers_algorithm():
    coo = random_sparse_tensor((10, 8, 6), 0.08, seed=60)
    rs = tucker.decompose(coo, (2, 2, 2), n_iter=2, method="gram")
    assert rs.spec.algorithm == "sparse"
    rd = tucker.decompose(coo.to_dense(), (2, 2, 2), n_iter=2, method="gram")
    assert rd.spec.algorithm == "dense"
    np.testing.assert_allclose(
        float(rs.rel_error), float(rd.rel_error), atol=1e-4
    )
