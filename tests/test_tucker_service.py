"""TuckerService: micro-batching, parity, amortization, lifecycle.

Determinism strategy: the MicroBatcher takes time as an argument (tested
with a fake clock, no sleeps), and the service tests avoid waiting out
``max_wait_ms`` wherever possible — either the queue fills (``max_batch``)
or ``flush()`` drains inline on the calling thread. The one timeout-path
test uses a short wait and a generous result timeout.

The ``serve_soak`` tier at the bottom is the CI amortization gate: a few
hundred mixed-nnz requests must produce far fewer dispatches than requests,
with every sampled result allclose to a sequential ``tucker.decompose``.
"""
import threading
import time
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.coo import SparseCOO

from repro import tucker
from repro.serve import (
    AdaptiveBatchPolicy,
    BatchKey,
    LatencyTracker,
    MicroBatcher,
    ServiceConfig,
    ServiceMetrics,
    ServiceOverloadedError,
    TuckerService,
)
from repro.serve.batching import FLUSH_DRAIN, FLUSH_FULL, FLUSH_TIMEOUT
from repro.sparse.generators import random_sparse_tensor
from repro.sparse.layout import bucket_nnz, pad_coo_batch


SPEC = tucker.TuckerSpec(
    shape=(14, 12, 10), ranks=(3, 2, 2), method="gram", n_iter=2
)


def _coos(n, density=0.05, seed0=100, shape=SPEC.shape):
    """n same-nnz tensors (same density+shape => same nnz => one compiled
    program per batch size — keeps the suite fast on cold jit caches)."""
    return [random_sparse_tensor(shape, density, seed=seed0 + i) for i in range(n)]


@pytest.fixture(autouse=True)
def _unbounded_plan_cache():
    """Tests tweak the global plan-cache capacity; always restore."""
    yield
    tucker.set_plan_cache_capacity(None)


# ---------------------------------------------------------------------------
# bucket_nnz: deterministic boundary tests (satellite).
# ---------------------------------------------------------------------------


def test_bucket_boundaries_power_of_two():
    assert bucket_nnz(0) == 512  # empty still pads to one bucket
    assert bucket_nnz(1) == 512
    assert bucket_nnz(512) == 512  # boundary is inclusive
    assert bucket_nnz(513) == 1024  # one past the boundary jumps a bucket
    assert bucket_nnz(1024) == 1024
    assert bucket_nnz(1025) == 2048


def test_bucket_boundaries_fractional_growth():
    # base 100, growth 1.5: 100, 150, 225, 338 (ceil'd), ...
    assert bucket_nnz(100, base=100, growth=1.5) == 100
    assert bucket_nnz(101, base=100, growth=1.5) == 150
    assert bucket_nnz(151, base=100, growth=1.5) == 225
    assert bucket_nnz(226, base=100, growth=1.5) == 338


def test_bucket_validation():
    with pytest.raises(ValueError, match="base"):
        bucket_nnz(5, base=0)
    with pytest.raises(ValueError, match="growth"):
        bucket_nnz(5, growth=1.0)
    with pytest.raises(ValueError, match="nnz"):
        bucket_nnz(-1)


def test_pad_coo_batch_target_and_errors():
    coos = _coos(2)
    idx, val = pad_coo_batch(coos, target_nnz=coos[0].nnz + 7)
    assert idx.shape == (2, coos[0].nnz + 7, 3)
    assert val.shape == (2, coos[0].nnz + 7)
    with pytest.raises(ValueError, match="drop nonzeros"):
        pad_coo_batch(coos, target_nnz=coos[0].nnz - 1)
    with pytest.raises(ValueError, match="at least one"):
        pad_coo_batch([])
    with pytest.raises(ValueError, match="same-shape"):
        pad_coo_batch([coos[0], random_sparse_tensor((14, 12, 11), 0.05, seed=9)])


# ---------------------------------------------------------------------------
# MicroBatcher: pure queue plane with a fake clock.
# ---------------------------------------------------------------------------


def _key(bucket=512, spec=SPEC):
    return BatchKey(spec=spec, bucket=bucket)


def test_batcher_flushes_full_queue_immediately():
    b = MicroBatcher(max_batch=3, max_wait_s=100.0)
    k = _key()
    for i in range(3):
        b.add(k, f"r{i}", now=float(i))
    flush = b.pop_ready(now=2.0)  # no wait needed: the queue is full
    assert flush is not None and flush.reason == FLUSH_FULL
    assert flush.items == ("r0", "r1", "r2")
    assert len(b) == 0 and b.pop_ready(now=2.0) is None


def test_batcher_timeout_flush_earliest_deadline_first():
    b = MicroBatcher(max_batch=8, max_wait_s=1.0)
    early, late = _key(bucket=512), _key(bucket=1024)
    b.add(late, "late", now=0.5)
    b.add(early, "early", now=0.0)
    assert b.pop_ready(now=0.9) is None  # nobody waited 1s yet
    assert b.next_deadline() == pytest.approx(1.0)  # oldest enqueue + wait
    flush = b.pop_ready(now=1.1)
    assert flush.reason == FLUSH_TIMEOUT and flush.items == ("early",)
    assert b.pop_ready(now=1.2) is None  # 'late' is due at 1.5
    assert b.pop_ready(now=1.5).items == ("late",)


def test_batcher_pop_caps_at_max_batch_and_keeps_remainder():
    b = MicroBatcher(max_batch=2, max_wait_s=0.0)
    k = _key()
    for i in range(5):
        b.add(k, i, now=0.0)
    sizes = []
    while True:
        f = b.pop_ready(now=0.0)
        if f is None:
            break
        sizes.append(len(f.items))
    assert sizes == [2, 2, 1]  # FIFO, capped, remainder flushes by timeout 0


def test_batcher_timeout_tie_between_queues():
    """Two queues due at the SAME instant must not crash the pop (BatchKey
    is unordered; a bare tuple-min would compare keys on the tie) — this is
    the scheduler thread's survival on coarse clocks."""
    b = MicroBatcher(max_batch=8, max_wait_s=1.0)
    b.add(_key(512), "a", now=0.0)
    b.add(_key(1024), "b", now=0.0)
    first = b.pop_ready(now=2.0)
    second = b.pop_ready(now=2.0)
    assert first is not None and second is not None
    assert {first.items[0], second.items[0]} == {"a", "b"}


def test_batcher_expired_deadline_beats_full_queue():
    """A cold key past its latency bound must not be starved by a hot key
    whose queue keeps refilling — the max_wait_ms contract under load."""
    b = MicroBatcher(max_batch=2, max_wait_s=1.0)
    b.add(_key(512), "cold", now=0.0)
    b.add(_key(1024), "hot1", now=5.0)
    b.add(_key(1024), "hot2", now=5.0)  # full, but not latency-urgent
    f = b.pop_ready(now=5.0)
    assert f.reason == FLUSH_TIMEOUT and f.items == ("cold",)
    assert b.pop_ready(now=5.0).reason == FLUSH_FULL


def test_batcher_pop_any_drains_everything():
    b = MicroBatcher(max_batch=4, max_wait_s=100.0)
    b.add(_key(512), "a", now=0.0)
    b.add(_key(1024), "b", now=0.0)
    reasons = set()
    drained = []
    while True:
        f = b.pop_any()
        if f is None:
            break
        reasons.add(f.reason)
        drained.extend(f.items)
    assert sorted(drained) == ["a", "b"] and reasons == {FLUSH_DRAIN}
    assert b.next_deadline() is None


def test_batcher_validation():
    with pytest.raises(ValueError, match="max_batch"):
        MicroBatcher(max_batch=0, max_wait_s=1.0)
    with pytest.raises(ValueError, match="max_wait"):
        MicroBatcher(max_batch=1, max_wait_s=float("nan"))


# ---------------------------------------------------------------------------
# Metrics.
# ---------------------------------------------------------------------------


def test_latency_tracker_percentiles():
    t = LatencyTracker(maxlen=100)
    assert np.isnan(t.percentile(50))
    for ms in range(1, 101):
        t.observe(float(ms))
    assert t.percentile(50) == pytest.approx(50.5)
    assert t.summary()["p99_ms"] == pytest.approx(99.01)
    assert t.summary()["count"] == 100


def test_service_metrics_amortization_counters():
    m = ServiceMetrics()
    m.on_submit(8)
    m.on_flush(reason="full", batch_size=8, dispatches=1, nnz_real=800,
               nnz_padded=1024, execute_ms=5.0, queue_ms=[1.0] * 8,
               total_ms=[6.0] * 8)
    assert m.requests_per_dispatch() == 8.0
    assert m.padding_overhead() == pytest.approx(1024 / 800)
    snap = m.snapshot()
    assert snap["pending"] == 0 and snap["flushes"] == {"full": 1}
    assert snap["batch_size_mean"] == 8.0


# ---------------------------------------------------------------------------
# TuckerService: parity, routing, lifecycle.
# ---------------------------------------------------------------------------


def test_service_full_flush_parity_and_timing():
    coos = _coos(4)
    cfg = ServiceConfig(max_batch=4, max_wait_ms=10_000.0, bucket_base=128)
    with TuckerService(cfg) as svc:
        tickets = [
            svc.submit(c.indices, c.values, SPEC) for c in coos
        ]  # 4th submit fills the queue -> immediate 'full' flush
        results = [t.result(timeout=120) for t in tickets]
        snap = svc.metrics.snapshot()
    assert snap["dispatches"] == 1 and snap["flushes"] == {"full": 1}
    bucket = bucket_nnz(coos[0].nnz, base=128)
    for c, r in zip(coos, results):
        ref = tucker.decompose(c, SPEC.ranks, method=SPEC.method,
                               n_iter=SPEC.n_iter)
        np.testing.assert_allclose(np.asarray(r.core), np.asarray(ref.core),
                                   rtol=1e-5, atol=1e-5)
        # bucket padding changes XLA's reduction tree: allclose, not bitwise
        np.testing.assert_allclose(r.fit_history, ref.fit_history, atol=1e-5)
        assert r.timing.batch_size == 4
        assert r.timing.flush_reason == FLUSH_FULL
        assert r.timing.nnz == c.nnz and r.timing.nnz_padded == bucket
        assert r.timing.total_ms >= r.timing.queue_ms
        assert 0.0 <= r.timing.padding_fraction < 1.0


def test_service_flush_drains_partial_batch_inline():
    coos = _coos(2, seed0=300)
    with TuckerService(ServiceConfig(max_batch=8, max_wait_ms=10_000.0)) as svc:
        tickets = [svc.submit_coo(c, SPEC) for c in coos]
        assert not tickets[0].done()  # queue is 2/8 and nobody waited yet
        assert svc.flush() == 2
        assert svc.pending() == 0
        results = [t.result(timeout=5) for t in tickets]
    assert all(r.timing.flush_reason == FLUSH_DRAIN for r in results)


def test_service_timeout_flush_fires():
    coo = _coos(1, seed0=310)[0]
    with TuckerService(ServiceConfig(max_batch=8, max_wait_ms=30.0)) as svc:
        t = svc.submit_coo(coo, SPEC)
        r = t.result(timeout=120)  # scheduler must wake itself up
    assert r.timing.flush_reason == FLUSH_TIMEOUT
    assert r.timing.batch_size == 1


def test_service_routes_buckets_to_separate_batches():
    # nnz 84 vs nnz 672 straddle the base-128 bucket boundary (128 vs 1024):
    # one flush each, never padded into one another's program.
    small = _coos(2, density=0.05, seed0=320)
    big = _coos(2, density=0.4, seed0=330)
    cfg = ServiceConfig(max_batch=2, max_wait_ms=10_000.0, bucket_base=128)
    with TuckerService(cfg) as svc:
        rs = svc.decompose_batch(small + big, SPEC, timeout=120)
        snap = svc.metrics.snapshot()
    assert snap["dispatches"] == 2 and snap["flushes"] == {"full": 2}
    assert {r.timing.nnz_padded for r in rs[:2]} != {
        r.timing.nnz_padded for r in rs[2:]
    }


def test_pad_coo_batch_rejects_mixed_dtypes():
    a = _coos(1, seed0=455)[0]
    b = SparseCOO(a.indices, a.values.astype(jnp.bfloat16), a.shape)
    with pytest.raises(ValueError, match="common value dtype"):
        pad_coo_batch([a, b])


def test_service_auto_dtype_routes_precisions_apart():
    """Under dtype='auto' the observed input dtype is part of the batch key:
    a float32 and a bfloat16 request never share a flush (whose stacking
    would silently promote the narrow member and break parity)."""
    a = _coos(1, seed0=460)[0]
    b0 = _coos(1, seed0=461)[0]
    b = SparseCOO(b0.indices, b0.values.astype(jnp.bfloat16), b0.shape)
    with TuckerService(ServiceConfig(max_batch=2, max_wait_ms=10_000.0)) as svc:
        ta = svc.submit_coo(a, SPEC)
        tb = svc.submit_coo(b, SPEC)
        assert svc.pending() == 2  # different dtype queues: neither is full
        svc.flush()
        ra, rb = ta.result(timeout=120), tb.result(timeout=120)
    assert ra.timing.batch_size == 1 and rb.timing.batch_size == 1


def test_service_routes_specs_to_separate_batches():
    other = tucker.TuckerSpec(shape=SPEC.shape, ranks=(2, 2, 2), method="gram",
                              n_iter=2)
    coos = _coos(2, seed0=340)
    with TuckerService(ServiceConfig(max_batch=2, max_wait_ms=10_000.0)) as svc:
        ta = svc.submit_coo(coos[0], SPEC)
        tb = svc.submit_coo(coos[1], other)
        svc.flush()
        ra, rb = ta.result(timeout=5), tb.result(timeout=5)
    assert ra.spec.ranks == (3, 2, 2) and rb.spec.ranks == (2, 2, 2)
    assert ra.timing.batch_size == 1 and rb.timing.batch_size == 1


def test_service_per_request_keys_respected():
    coo = _coos(1, seed0=350)[0]
    with TuckerService(ServiceConfig(max_batch=2, max_wait_ms=10_000.0)) as svc:
        t0 = svc.submit_coo(coo, SPEC, key=jax.random.PRNGKey(7))
        t1 = svc.submit_coo(coo, SPEC, key=jax.random.PRNGKey(8))
        r0, r1 = t0.result(timeout=120), t1.result(timeout=120)
    ref = tucker.plan(SPEC)(coo, key=jax.random.PRNGKey(7))
    np.testing.assert_allclose(np.asarray(r0.core), np.asarray(ref.core),
                               rtol=1e-5, atol=1e-5)
    # different init keys genuinely flowed through the batched init
    assert not np.allclose(np.asarray(r0.factors[0]), np.asarray(r1.factors[0]))


def test_service_submit_validation():
    coo = _coos(1, seed0=360)[0]
    dense_spec = tucker.TuckerSpec(shape=SPEC.shape, ranks=SPEC.ranks,
                                   algorithm="dense")
    with TuckerService(ServiceConfig(max_wait_ms=10_000.0)) as svc:
        with pytest.raises(ValueError, match="algorithm='sparse'"):
            svc.submit_coo(coo, dense_spec)
        with pytest.raises(ValueError, match="does not match the spec"):
            svc.submit_coo(random_sparse_tensor((14, 12, 11), 0.05, seed=1), SPEC)
        with pytest.raises(ValueError, match="zero stored nonzeros"):
            svc.submit(np.zeros((0, 3), np.int32), np.zeros((0,), np.float32),
                       SPEC)


def test_service_nonbatchable_spec_warns_but_serves():
    # bf16 mixed precision has no vmapped program: the batch is fp32-only
    bfspec = tucker.TuckerSpec(shape=SPEC.shape, ranks=SPEC.ranks,
                               method="gram", n_iter=2, engine="xla",
                               precision="bf16_fp32acc")
    coos = _coos(2, seed0=370)
    with TuckerService(ServiceConfig(max_batch=2, max_wait_ms=10_000.0)) as svc:
        with pytest.warns(RuntimeWarning, match="sequential"):
            tickets = [svc.submit_coo(c, bfspec) for c in coos]
        results = [t.result(timeout=120) for t in tickets]
        snap = svc.metrics.snapshot()
    # correct, but no amortization: one dispatch per member
    assert snap["dispatches"] == len(coos)
    for c, r in zip(coos, results):
        ref = tucker.plan(bfspec)(c)
        np.testing.assert_array_equal(r.fit_history, ref.fit_history)
        # the fallback runs unpadded — metrics must say so, not the bucket
        assert r.timing.nnz_padded == c.nnz
    assert snap["padding_overhead"] == pytest.approx(1.0)


def test_service_key_fallback_padding_metrics_honest():
    """Non-vmappable PRNG keys (rbg impl) push a batchable spec onto the
    sequential fallback — the padding metrics must describe that unpadded
    execution, not the bucket the batch would have padded to."""
    coos = _coos(2, seed0=450)
    with TuckerService(ServiceConfig(max_batch=2, max_wait_ms=10_000.0)) as svc:
        tickets = [
            svc.submit_coo(c, SPEC, key=jax.random.key(i, impl="rbg"))
            for i, c in enumerate(coos)
        ]
        results = [t.result(timeout=120) for t in tickets]
        snap = svc.metrics.snapshot()
    assert snap["dispatches"] == 2  # one per member: no shared program
    for c, r in zip(coos, results):
        assert r.timing.nnz_padded == c.nnz
    assert snap["padding_overhead"] == pytest.approx(1.0)


def test_service_over_mesh_plans_sharded():
    """ServiceConfig(shard=...) constructs the service over a mesh: every
    submitted spec without its own shard plans sharded (one shard_map
    dispatch per request) — and the no-amortization warning stays silent,
    because sequential flushes are the sharded design, not a fallback."""
    shard = tucker.ShardSpec(num_devices=1)  # a 1-device mesh is still the
    coos = _coos(2, seed0=500)               # full shard_map program
    cfg = ServiceConfig(max_batch=2, max_wait_ms=10_000.0, shard=shard)
    with TuckerService(cfg) as svc:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            tickets = [svc.submit_coo(c, SPEC) for c in coos]
        results = [t.result(timeout=120) for t in tickets]
    assert not [w for w in caught if issubclass(w.category, RuntimeWarning)]
    sharded_spec = tucker.TuckerSpec(
        shape=SPEC.shape, ranks=SPEC.ranks, method=SPEC.method,
        n_iter=SPEC.n_iter, shard=shard,
    )
    for c, r in zip(coos, results):
        assert r.spec.shard == shard
        assert r.dispatches == 1  # one mesh-spanning dispatch per request
        assert r.collective_bytes_per_sweep is not None
        assert r.shard_imbalance is not None
        ref = tucker.plan(sharded_spec)(c)
        np.testing.assert_array_equal(r.fit_history, ref.fit_history)


def test_service_sharded_flushes_bucket_pad_no_retrace():
    """Mixed-nnz sharded requests in one bucket must share ONE compiled
    shard_map program: the flush pads members to the bucket (then the even
    shard multiple), so only the first flush of a bucket traces."""
    from repro.core import hooi

    shard = tucker.ShardSpec(num_devices=1)
    spec = tucker.TuckerSpec(shape=(13, 11, 9), ranks=(2, 2, 2),
                             method="gram", n_iter=2)
    # three distinct nnz in the same 512-base bucket
    coos = [random_sparse_tensor(spec.shape, d, seed=600 + i)
            for i, d in enumerate((0.05, 0.06, 0.07))]
    assert len({c.nnz for c in coos}) == 3
    cfg = ServiceConfig(max_batch=1, max_wait_ms=10_000.0, shard=shard)
    with TuckerService(cfg) as svc:
        t0 = svc.submit_coo(coos[0], spec)
        svc.flush()
        r0 = t0.result(timeout=120)
        traces = sum(hooi.SWEEP_TRACE_COUNTS.values())
        tickets = [svc.submit_coo(c, spec) for c in coos[1:]]
        svc.flush()
        results = [t.result(timeout=120) for t in tickets]
    assert sum(hooi.SWEEP_TRACE_COUNTS.values()) == traces, (
        "mixed-nnz sharded flushes recompiled the shard_map program"
    )
    for r, c in zip([r0] + results, coos):
        assert r.timing.nnz_padded == bucket_nnz(c.nnz)  # num_devices=1
        assert r.timing.nnz_padded >= c.nnz


def test_service_sharded_capacity_error_raises_at_submit():
    """A ShardSpec wanting more devices than attached must fail the submit
    call synchronously, like every other spec-validation error — not
    asynchronously as a flush failure on the scheduler thread."""
    too_many = len(jax.devices()) + 1
    cfg = ServiceConfig(shard=tucker.ShardSpec(num_devices=too_many))
    coo = _coos(1, seed0=650)[0]
    with TuckerService(cfg) as svc:
        with pytest.raises(ValueError,
                           match="xla_force_host_platform_device_count"):
            svc.submit_coo(coo, SPEC)


def test_service_close_rejects_new_and_drains_pending():
    coos = _coos(2, seed0=380)
    svc = TuckerService(ServiceConfig(max_batch=8, max_wait_ms=10_000.0))
    tickets = [svc.submit_coo(c, SPEC) for c in coos]
    svc.close(drain=True)
    for t in tickets:
        assert t.result(timeout=5).timing.flush_reason == FLUSH_DRAIN
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit_coo(coos[0], SPEC)
    svc.close()  # idempotent


def test_service_close_without_drain_fails_tickets():
    coo = _coos(1, seed0=390)[0]
    svc = TuckerService(ServiceConfig(max_batch=8, max_wait_ms=10_000.0))
    t = svc.submit_coo(coo, SPEC)
    svc.close(drain=False)
    with pytest.raises(RuntimeError, match="closed before execution"):
        t.result(timeout=5)
    assert svc.metrics.snapshot()["failed"] == 1


def test_close_without_drain_does_not_execute_ready_batches(monkeypatch):
    """close(drain=False) must fail queued-but-ready batches, not run them:
    an in-flight batch finishes, a full queue behind it gets RuntimeError.
    max_inflight_flushes=1 pins a single executor so the second ready batch
    is deterministically still queued when close lands."""
    coos = _coos(4, seed0=440)
    svc = TuckerService(
        ServiceConfig(
            max_batch=2, max_wait_ms=10_000.0, max_inflight_flushes=1
        )
    )
    gate = threading.Event()
    real_batch = tucker.TuckerPlan.batch

    def gated_batch(self, *a, **kw):
        gate.wait(30)
        return real_batch(self, *a, **kw)

    monkeypatch.setattr(tucker.TuckerPlan, "batch", gated_batch)
    t0 = svc.submit_coo(coos[0], SPEC)
    t1 = svc.submit_coo(coos[1], SPEC)  # full -> scheduler pops, blocks on gate
    for _ in range(500):
        if svc.pending() == 0:
            break
        time.sleep(0.01)
    assert svc.pending() == 0  # first batch is in flight
    t2 = svc.submit_coo(coos[2], SPEC)
    t3 = svc.submit_coo(coos[3], SPEC)  # a second FULL (ready) batch queued
    closer = threading.Thread(target=lambda: svc.close(drain=False))
    closer.start()
    time.sleep(0.05)
    gate.set()  # let the in-flight batch finish
    closer.join(60)
    assert not closer.is_alive()
    assert t0.result(timeout=5) is not None and t1.result(timeout=5) is not None
    for t in (t2, t3):  # ready but never executed
        with pytest.raises(RuntimeError, match="closed before execution"):
            t.result(timeout=5)


def test_ticket_timeout():
    coo = _coos(1, seed0=395)[0]
    with TuckerService(ServiceConfig(max_batch=8, max_wait_ms=10_000.0)) as svc:
        t = svc.submit_coo(coo, SPEC)
        with pytest.raises(TimeoutError):
            t.result(timeout=0.05)
        svc.flush()
        assert t.exception(timeout=5) is None


def test_service_survives_execution_failure(monkeypatch):
    """A failing batch fails its tickets but not the scheduler."""
    coos = _coos(2, seed0=400)
    boom = RuntimeError("injected engine failure")
    with TuckerService(ServiceConfig(max_batch=2, max_wait_ms=10_000.0)) as svc:
        monkeypatch.setattr(
            tucker.TuckerPlan, "batch",
            lambda self, *a, **k: (_ for _ in ()).throw(boom),
        )
        tickets = [svc.submit_coo(c, SPEC) for c in coos]
        for t in tickets:
            assert t.exception(timeout=120) is boom
        monkeypatch.undo()
        ok = svc.submit_coo(coos[0], SPEC)  # scheduler still alive
        svc.flush()
        assert ok.result(timeout=120).timing is not None
    assert svc.metrics.snapshot()["failed"] == 2


def test_concurrent_submitters_share_plans_and_get_parity():
    """Many threads hammering submit: every result correct, plan built once
    (the plan-cache lock satellite, exercised through the public surface)."""
    tucker.clear_plan_cache()
    spec = tucker.TuckerSpec(shape=(12, 10, 8), ranks=(2, 2, 2), method="gram",
                             n_iter=2)
    coos = _coos(12, seed0=410, shape=spec.shape)
    misses0 = tucker.plan_cache_info()["misses"]
    results = {}
    with TuckerService(ServiceConfig(max_batch=4, max_wait_ms=10_000.0)) as svc:
        def worker(i):
            results[i] = svc.submit_coo(coos[i], spec)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        svc.flush()  # whatever didn't fill a batch
        out = {i: t.result(timeout=120) for i, t in results.items()}
        snap = svc.metrics.snapshot()
    assert snap["completed"] == 12
    assert snap["dispatches"] <= 3  # ceil(12/4): full amortization
    assert tucker.plan_cache_info()["misses"] - misses0 == 1  # built ONCE
    ref = tucker.plan(spec)(coos[5])
    np.testing.assert_allclose(np.asarray(out[5].core), np.asarray(ref.core),
                               rtol=1e-5, atol=1e-5)


def test_mixed_nnz_in_one_bucket_one_dispatch_per_flush():
    """Requests of different nnz that share one bucket share each flush's
    one dispatch: 8 requests at max_batch 4 are ceil(8 / 4) = 2 dispatches,
    every member padded to the bucket, every result the sequential one."""
    densities = (0.03, 0.04, 0.05)  # nnz 50, 67 and 84: all in bucket 128
    coos = [random_sparse_tensor(SPEC.shape, densities[i % 3], seed=1100 + i)
            for i in range(8)]
    assert len({c.nnz for c in coos}) == 3
    cfg = ServiceConfig(max_batch=4, max_wait_ms=60_000.0, bucket_base=128)
    with TuckerService(cfg) as svc:
        tickets = [svc.submit_coo(c, SPEC) for c in coos]
        results = [t.result(timeout=120) for t in tickets]
        snap = svc.metrics.snapshot()
    assert snap["dispatches"] == 2 and snap["flushes"] == {"full": 2}
    for c, r in zip(coos, results):
        assert r.timing.batch_size == 4 and r.timing.nnz_padded == 128
        ref = tucker.decompose(c, SPEC.ranks, method=SPEC.method,
                               n_iter=SPEC.n_iter)
        np.testing.assert_allclose(np.asarray(r.core), np.asarray(ref.core),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(r.fit_history, ref.fit_history, atol=1e-5)


def test_concurrent_flushes_bitwise_equal_to_sequential_flushes():
    """A mixed-nnz load from two tenants, served once with one flush
    executor and once with four: every result is bitwise the same and the
    dispatch count is the same. Full-only flushes keep each key's batch
    composition FIFO, so concurrency can change nothing but timing."""
    spec_b = tucker.TuckerSpec(shape=SPEC.shape, ranks=(2, 3, 2),
                               method="gram", n_iter=2)
    densities = (0.03, 0.04, 0.05)
    reqs = [(spec, random_sparse_tensor(SPEC.shape, densities[i % 3],
                                        seed=1200 + 10 * t + i))
            for i in range(4) for t, spec in enumerate((SPEC, spec_b))]

    def serve(inflight):
        cfg = ServiceConfig(max_batch=2, max_wait_ms=60_000.0, bucket_base=128,
                            max_inflight_flushes=inflight)
        with TuckerService(cfg) as svc:
            tickets = [svc.submit_coo(c, spec) for spec, c in reqs]
            results = [t.result(timeout=120) for t in tickets]
            return results, svc.metrics.snapshot()["dispatches"]

    seq, d_seq = serve(1)
    conc, d_conc = serve(4)
    assert d_seq == d_conc == 4  # 2 tenants x ceil(4 / 2) full flushes
    for a, b in zip(seq, conc):
        np.testing.assert_array_equal(np.asarray(a.core), np.asarray(b.core))
        for fa, fb in zip(a.factors, b.factors):
            np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
        np.testing.assert_array_equal(a.fit_history, b.fit_history)


def test_service_plan_cache_capacity_and_eviction_hook():
    tucker.clear_plan_cache()
    cfg = ServiceConfig(max_batch=1, max_wait_ms=10_000.0,
                        plan_cache_capacity=1)
    coo = _coos(1, seed0=420)[0]
    specs = [
        tucker.TuckerSpec(shape=SPEC.shape, ranks=(r, 2, 2), method="gram",
                          n_iter=1)
        for r in (2, 3)
    ]
    with TuckerService(cfg) as svc:
        for s in specs:  # max_batch=1: each submit flushes itself
            svc.submit_coo(coo, s).result(timeout=120)
        assert tucker.plan_cache_info()["capacity"] == 1
        assert svc.metrics.snapshot()["plan_evictions"] >= 1
    assert tucker.plan_cache_info()["size"] <= 1
    # the capacity knob is process-global: close() must restore what it found
    assert tucker.plan_cache_info()["capacity"] is None


def test_overlapping_services_capacity_registry():
    """Closing one capacity-setting service must not loosen the bound of a
    still-running one — even when both configured the SAME capacity — and
    the pre-service capacity returns only when the last holder closes."""
    tucker.set_plan_cache_capacity(None)
    a = TuckerService(ServiceConfig(plan_cache_capacity=8))
    b = TuckerService(ServiceConfig(plan_cache_capacity=8))
    try:
        a.close()
        assert tucker.plan_cache_info()["capacity"] == 8  # b still live
    finally:
        b.close()
    assert tucker.plan_cache_info()["capacity"] is None


def test_manual_capacity_set_survives_service_close():
    """An operator's explicit set_plan_cache_capacity() while a service is
    live wins over the service's restore-on-close."""
    tucker.set_plan_cache_capacity(None)
    svc = TuckerService(ServiceConfig(plan_cache_capacity=8))
    try:
        tucker.set_plan_cache_capacity(4)  # manual override mid-flight
    finally:
        svc.close()
    assert tucker.plan_cache_info()["capacity"] == 4


# ---------------------------------------------------------------------------
# serve_soak: the CI amortization gate (also runs in tier-1; kept small).
# ---------------------------------------------------------------------------


@pytest.mark.serve_soak
def test_soak_mixed_nnz_parity_and_amortization():
    """A few hundred mixed-nnz requests: every sampled result matches the
    sequential path, and the dispatch count is far below the request count
    (the whole point of the service)."""
    n_requests = 240
    rng = np.random.default_rng(0)
    # three densities -> three nnz values spanning two buckets under base=128
    densities = rng.choice([0.03, 0.05, 0.12], size=n_requests)
    coos = [
        random_sparse_tensor(SPEC.shape, float(d), seed=500 + i)
        for i, d in enumerate(densities)
    ]
    cfg = ServiceConfig(max_batch=8, max_wait_ms=50.0, bucket_base=128)
    with TuckerService(cfg) as svc:
        tickets = [svc.submit_coo(c, SPEC) for c in coos]
        results = [t.result(timeout=600) for t in tickets]
        snap = svc.metrics.snapshot()
    assert snap["completed"] == n_requests and snap["failed"] == 0
    # far fewer dispatches than requests: >= 4x amortization on average
    assert snap["dispatches"] <= n_requests // 4, snap
    assert snap["requests_per_dispatch"] >= 4.0
    # bucketing bounds padding waste: growth-factor for nnz >= base,
    # base/nnz for sub-base requests (which pad up to one full bucket)
    min_nnz = min(c.nnz for c in coos)
    bound = max(cfg.bucket_growth, cfg.bucket_base / min_nnz)
    assert snap["padding_overhead"] <= bound + 1e-9
    # parity on a deterministic sample across all densities
    for i in (0, 7, 63, 128, 239):
        ref = tucker.decompose(coos[i], SPEC.ranks, method=SPEC.method,
                               n_iter=SPEC.n_iter)
        np.testing.assert_allclose(
            np.asarray(results[i].core), np.asarray(ref.core),
            rtol=1e-4, atol=1e-4,
        )
        np.testing.assert_allclose(results[i].fit_history, ref.fit_history,
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# Concurrent serving plane: race/hang regressions, executor-pool overlap,
# admission control, adaptive batch policy (ISSUE 10).
# ---------------------------------------------------------------------------


def test_concurrent_first_submits_plan_exactly_once(monkeypatch):
    """_warned_specs race regression: concurrent first-submits of one NEW
    spec must run the synchronous tucker.plan() validation exactly once (the
    claim is check-and-add under the service lock) — the old unlocked
    read/mutate let every racer duplicate the call."""
    spec = tucker.TuckerSpec(
        shape=(14, 12, 10), ranks=(4, 2, 2), method="gram", n_iter=2
    )
    coos = _coos(4, seed0=900)
    real_plan = tucker.plan
    calls = []
    start = threading.Barrier(4)

    def counting_plan(s, *a, **kw):
        calls.append(s)
        time.sleep(0.05)  # widen the race window the old code lost
        return real_plan(s, *a, **kw)

    monkeypatch.setattr(tucker, "plan", counting_plan)
    svc = TuckerService(ServiceConfig(max_batch=64, max_wait_ms=60_000.0))
    try:
        errs = []

        def submit(i):
            start.wait(10)
            try:
                svc.submit_coo(coos[i], spec)
            except Exception as exc:  # pragma: no cover - failure detail
                errs.append(exc)

        threads = [
            threading.Thread(target=submit, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errs
        assert len(calls) == 1, f"plan() ran {len(calls)}x for one new spec"
    finally:
        svc.close(drain=False)


def test_failed_spec_plan_releases_first_submit_claim(monkeypatch):
    """If the first-submit plan() raises, the claim must be released so the
    next submit re-validates — not treat a never-planned spec as known."""
    spec = tucker.TuckerSpec(
        shape=(14, 12, 10), ranks=(5, 2, 2), method="gram", n_iter=2
    )
    coo = _coos(1, seed0=920)[0]
    real_plan = tucker.plan
    n_calls = {"n": 0}

    def flaky_plan(s, *a, **kw):
        n_calls["n"] += 1
        if n_calls["n"] == 1:
            raise RuntimeError("transient planning failure")
        return real_plan(s, *a, **kw)

    monkeypatch.setattr(tucker, "plan", flaky_plan)
    with TuckerService(ServiceConfig(max_batch=1, max_wait_ms=60_000.0)) as svc:
        with pytest.raises(RuntimeError, match="transient planning failure"):
            svc.submit_coo(coo, spec)
        t = svc.submit_coo(coo, spec)  # claim released -> validated again
        assert n_calls["n"] >= 2
        assert t.result(timeout=300) is not None


def test_short_batch_results_fail_whole_batch(monkeypatch):
    """zip silent-hang regression: plan.batch returning fewer results than
    requests must fail EVERY ticket with a pointed error — the old bare
    zip dropped the surplus tickets and result() hung forever."""
    coos = _coos(2, seed0=930)
    real_batch = tucker.TuckerPlan.batch

    def short_batch(self, coos_, keys=None, pad_nnz_to=None):
        return real_batch(self, coos_, keys=keys, pad_nnz_to=pad_nnz_to)[:-1]

    monkeypatch.setattr(tucker.TuckerPlan, "batch", short_batch)
    svc = TuckerService(ServiceConfig(max_batch=2, max_wait_ms=60_000.0))
    try:
        t0 = svc.submit_coo(coos[0], SPEC)
        t1 = svc.submit_coo(coos[1], SPEC)
        for t in (t0, t1):
            with pytest.raises(RuntimeError, match="failing the whole batch"):
                t.result(timeout=300)
        assert svc.metrics.failed == 2
    finally:
        svc.close(drain=False)


def test_flush_after_close_raises():
    """flush() on a closed service must raise like submit does — the old
    silent execution ran work on a service whose plan-cache capacity and
    eviction hooks were already uninstalled."""
    svc = TuckerService(ServiceConfig(max_wait_ms=10_000.0))
    svc.close()
    with pytest.raises(RuntimeError, match="TuckerService is closed"):
        svc.flush()


def test_no_ticket_left_unresolved_by_any_execute_path(monkeypatch):
    """Belt-and-braces guard: even when post-dispatch bookkeeping blows up,
    every dequeued ticket resolves (pointed internal error, never a hang)."""
    coo = _coos(1, seed0=935)[0]
    svc = TuckerService(ServiceConfig(max_batch=1, max_wait_ms=60_000.0))

    def boom(*a, **kw):
        raise ZeroDivisionError("bookkeeping bug")

    monkeypatch.setattr(svc.metrics, "on_flush", boom)
    try:
        t = svc.submit_coo(coo, SPEC)
        with pytest.raises(RuntimeError, match="without resolving"):
            t.result(timeout=300)
        assert svc.metrics.failed >= 1
    finally:
        svc.close(drain=False)


def test_distinct_key_flushes_overlap(monkeypatch):
    """Tentpole proof: two executors run flushes of distinct BatchKeys at
    the SAME time — the 2-party barrier inside plan.batch only passes if
    both flushes are simultaneously in flight (a sequential scheduler
    deadlocks it until the 60s timeout breaks the barrier and the test
    fails via the ticket exceptions)."""
    spec_b = tucker.TuckerSpec(
        shape=(14, 12, 10), ranks=(2, 2, 2), method="gram", n_iter=2
    )
    coos = _coos(2, seed0=940)
    barrier = threading.Barrier(2)
    real_batch = tucker.TuckerPlan.batch

    def rendezvous_batch(self, *a, **kw):
        barrier.wait(60)
        return real_batch(self, *a, **kw)

    monkeypatch.setattr(tucker.TuckerPlan, "batch", rendezvous_batch)
    cfg = ServiceConfig(
        max_batch=1, max_wait_ms=60_000.0, max_inflight_flushes=2
    )
    with TuckerService(cfg) as svc:
        t0 = svc.submit_coo(coos[0], SPEC)
        t1 = svc.submit_coo(coos[1], spec_b)
        assert t0.result(timeout=300) is not None
        assert t1.result(timeout=300) is not None
        assert svc.metrics.failed == 0


def test_admission_reject(monkeypatch):
    """backpressure='reject': an over-max_pending submit raises
    ServiceOverloadedError without enqueueing; capacity freed by completed
    flushes admits again; the rejection is counted."""
    coos = _coos(3, seed0=950)
    gate = threading.Event()
    real_batch = tucker.TuckerPlan.batch

    def gated_batch(self, *a, **kw):
        gate.wait(120)
        return real_batch(self, *a, **kw)

    monkeypatch.setattr(tucker.TuckerPlan, "batch", gated_batch)
    cfg = ServiceConfig(
        max_batch=1, max_wait_ms=60_000.0, max_inflight_flushes=2,
        max_pending=2, backpressure="reject",
    )
    svc = TuckerService(cfg)
    try:
        t0 = svc.submit_coo(coos[0], SPEC)
        t1 = svc.submit_coo(coos[1], SPEC)
        with pytest.raises(ServiceOverloadedError, match="max_pending=2"):
            svc.submit_coo(coos[2], SPEC)
        assert svc.metrics.rejected == 1
        assert svc.metrics.snapshot()["rejected"] == 1
        # the rejected request never entered the queue
        assert svc.metrics.submitted == 2
        gate.set()
        assert t0.result(timeout=300) is not None
        assert t1.result(timeout=300) is not None
        t2 = svc.submit_coo(coos[2], SPEC)  # capacity freed -> admitted
        assert t2.result(timeout=300) is not None
    finally:
        gate.set()
        svc.close()


def test_admission_block_waits_for_capacity(monkeypatch):
    """backpressure='block': an over-max_pending submit parks until a flush
    resolves enough requests, then enqueues and completes normally."""
    coos = _coos(2, seed0=960)
    gate = threading.Event()
    real_batch = tucker.TuckerPlan.batch

    def gated_batch(self, *a, **kw):
        gate.wait(120)
        return real_batch(self, *a, **kw)

    monkeypatch.setattr(tucker.TuckerPlan, "batch", gated_batch)
    cfg = ServiceConfig(
        max_batch=1, max_wait_ms=60_000.0, max_inflight_flushes=1,
        max_pending=1, backpressure="block",
    )
    svc = TuckerService(cfg)
    try:
        t0 = svc.submit_coo(coos[0], SPEC)
        got = {}

        def blocked_submit():
            got["ticket"] = svc.submit_coo(coos[1], SPEC)

        th = threading.Thread(target=blocked_submit)
        th.start()
        time.sleep(0.3)
        assert th.is_alive() and "ticket" not in got  # admission-parked
        gate.set()
        th.join(300)
        assert not th.is_alive()
        assert t0.result(timeout=300) is not None
        assert got["ticket"].result(timeout=300) is not None
    finally:
        gate.set()
        svc.close()


def test_blocked_submit_raises_on_close(monkeypatch):
    """A submitter parked on admission must not hang forever when the
    service closes under it — it raises the closed error."""
    coos = _coos(2, seed0=965)
    gate = threading.Event()
    real_batch = tucker.TuckerPlan.batch

    def gated_batch(self, *a, **kw):
        gate.wait(120)
        return real_batch(self, *a, **kw)

    monkeypatch.setattr(tucker.TuckerPlan, "batch", gated_batch)
    cfg = ServiceConfig(
        max_batch=1, max_wait_ms=60_000.0, max_inflight_flushes=1,
        max_pending=1, backpressure="block",
    )
    svc = TuckerService(cfg)
    t0 = svc.submit_coo(coos[0], SPEC)
    errs = []

    def blocked_submit():
        try:
            svc.submit_coo(coos[1], SPEC)
        except RuntimeError as exc:
            errs.append(exc)

    th = threading.Thread(target=blocked_submit)
    th.start()
    time.sleep(0.3)
    assert th.is_alive()
    closer = threading.Thread(target=svc.close)  # drain=True
    closer.start()
    time.sleep(0.2)
    gate.set()  # let the in-flight batch (and close) finish
    th.join(300)
    closer.join(300)
    assert not th.is_alive() and not closer.is_alive()
    assert len(errs) == 1 and "closed" in str(errs[0])
    assert t0.result(timeout=300) is not None


def test_microbatcher_per_key_limits():
    """set_limits overrides flush policy for one key only (adaptive-policy
    plumbing): fullness, timeout, and next_deadline all honor it."""
    mb = MicroBatcher(max_batch=4, max_wait_s=10.0)
    k = BatchKey(spec=SPEC, bucket=512)
    assert mb.limits(k) == (4, 10.0)
    mb.set_limits(k, 2, 0.5)
    assert mb.limits(k) == (2, 0.5)
    mb.add(k, "a", now=0.0)
    assert mb.pop_ready(0.1) is None  # 1 < 2 and 0.1 < 0.5
    assert mb.next_deadline() == pytest.approx(0.5)
    got = mb.pop_ready(0.6)  # overridden wait expired
    assert got is not None and got.reason == FLUSH_TIMEOUT
    mb.add(k, "a", now=1.0)
    mb.add(k, "b", now=1.0)
    got = mb.pop_ready(1.0)  # full at the overridden cap
    assert got is not None and got.reason == FLUSH_FULL
    assert len(got.items) == 2
    # other keys keep the defaults
    k2 = BatchKey(spec=SPEC, bucket=1024)
    assert mb.limits(k2) == (4, 10.0)
    with pytest.raises(ValueError):
        mb.set_limits(k, 0, 1.0)


def test_adaptive_policy_narrows_then_widens():
    """Control law: p99 over target halves (batch, wait); p99 under half
    the target widens back toward the ceilings; floors are respected."""
    pol = AdaptiveBatchPolicy(
        max_batch=8, max_wait_s=0.002, target_p99_ms=10.0,
        window=4, period=2,
    )
    k = BatchKey(spec=SPEC, bucket=512)
    assert pol.limits(k) == (8, 0.002)
    assert pol.observe(k, [50.0, 60.0]) is None  # not an evaluation point
    upd = pol.observe(k, [55.0, 65.0])
    assert upd is not None and upd.direction == "narrow"
    assert upd.max_batch == 4 and upd.max_wait_s == pytest.approx(0.001)
    assert pol.limits(k) == (4, pytest.approx(0.001))
    # sustained overshoot keeps narrowing, but never through the floors
    for _ in range(10):
        pol.observe(k, [100.0])
    assert pol.limits(k)[0] == 1
    assert pol.limits(k)[1] >= 0.0
    # recovery: fast samples roll the slow ones out of the window -> widen
    widened = False
    for _ in range(10):
        upd = pol.observe(k, [1.0, 1.0])
        if upd is not None:
            assert upd.direction == "widen"
            widened = True
    assert widened
    b, w = pol.limits(k)
    assert 1 < b <= 8 and 0.0 < w <= 0.002
    # in-band p99 holds (no update at the evaluation point)
    pol2 = AdaptiveBatchPolicy(
        max_batch=8, max_wait_s=0.002, target_p99_ms=10.0, period=1
    )
    assert pol2.observe(k, [7.0, 8.0]) is None
    with pytest.raises(ValueError, match="target_p99_ms"):
        AdaptiveBatchPolicy(max_batch=8, max_wait_s=0.002, target_p99_ms=0.0)


def test_service_adaptive_policy_narrows_under_slo_pressure():
    """End-to-end adaptation: an unattainable p99 target makes the service
    narrow the key's limits and count the adaptation."""
    coos = _coos(8, seed0=970)
    cfg = ServiceConfig(
        max_batch=4, max_wait_ms=60_000.0, adaptive_target_p99_ms=1e-6
    )
    with TuckerService(cfg) as svc:
        for c in coos:  # one flush per request -> hits evaluation points
            t = svc.submit_coo(c, SPEC)
            svc.flush()
            assert t.result(timeout=300) is not None
        assert svc.metrics.adaptations.get("narrow", 0) >= 1
        snap = svc.metrics.snapshot()
        assert snap["adaptations"].get("narrow", 0) >= 1
        assert snap["failed"] == 0


def test_config_validation():
    with pytest.raises(ValueError, match="max_inflight_flushes"):
        ServiceConfig(max_inflight_flushes=0)
    with pytest.raises(ValueError, match="max_pending"):
        ServiceConfig(max_pending=0)
    with pytest.raises(ValueError, match="backpressure"):
        ServiceConfig(backpressure="drop")
    with pytest.raises(ValueError, match="adaptive_target_p99_ms"):
        ServiceConfig(adaptive_target_p99_ms=-1.0)


def test_hammer_concurrent_submit_flush_close():
    """Multi-threaded hammer: concurrent submitters (two specs), flush()
    callers racing the executor pool, close(drain=True) mid-burst. Every
    accepted ticket resolves successfully; the final snapshot balances."""
    spec_b = tucker.TuckerSpec(
        shape=SPEC.shape, ranks=(3, 3, 2), method="gram", n_iter=2
    )
    coos = _coos(4, seed0=990)
    cfg = ServiceConfig(
        max_batch=3, max_wait_ms=0.5, max_inflight_flushes=3
    )
    svc = TuckerService(cfg)
    tickets, tlock = [], threading.Lock()
    stop = threading.Event()

    def submitter(tid):
        rng = np.random.default_rng(tid)
        while not stop.is_set():
            try:
                t = svc.submit_coo(
                    coos[int(rng.integers(len(coos)))],
                    SPEC if rng.integers(2) == 0 else spec_b,
                )
            except RuntimeError:
                return  # service closed mid-burst
            with tlock:
                tickets.append(t)
            time.sleep(0.002)

    def flusher():
        while not stop.is_set():
            try:
                svc.flush()
            except RuntimeError:
                return  # closed
            time.sleep(0.01)

    threads = [
        threading.Thread(target=submitter, args=(i,)) for i in range(4)
    ] + [threading.Thread(target=flusher)]
    for t in threads:
        t.start()
    time.sleep(1.5)
    svc.close(drain=True)  # mid-burst close: drains everything accepted
    stop.set()
    for t in threads:
        t.join(300)
        assert not t.is_alive()
    assert tickets  # the burst actually submitted work
    for t in tickets:
        assert t.done()  # close(drain=True) resolved every accepted ticket
        assert t.result(timeout=1) is not None
    snap = svc.metrics.snapshot()
    assert snap["completed"] == len(tickets)
    assert snap["failed"] == 0 and snap["pending"] == 0
    assert snap["queue_depth"] == 0 and snap["inflight_flushes"] == 0
