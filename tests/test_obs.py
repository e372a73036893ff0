"""The unified tracing & telemetry plane (``repro.obs``).

Covers the tracer (span nesting, ring bound, noop fast path, Perfetto
export), the metrics registry (typed handles, identity, Prometheus
exposition, thread-safety under a concurrent hammer), the instrumented
stack (``trace_summary`` on results, plan-cache counters), and the serve
plane's ticket-linked submit→enqueue→flush→dispatch→split span chain.

Tracing is process-global state: every test that enables it goes through
the ``traced`` fixture, which restores the disabled default afterwards.
The global registry is cumulative by design, so assertions on it are
deltas, never absolutes.
"""
import json
import threading

import numpy as np
import pytest

import repro.obs as obs
from repro.obs import MetricsRegistry, Tracer

SHAPE = (16, 12, 10)
RANKS = (3, 3, 2)


@pytest.fixture
def traced():
    obs.configure(enabled=True)
    # the ring is process-wide: drop what earlier tests in this worker traced
    obs.tracer.clear()
    try:
        yield obs.tracer
    finally:
        obs.configure(enabled=False)


def _coo(seed=0, density=0.06):
    from repro.sparse.generators import random_sparse_tensor

    return random_sparse_tensor(SHAPE, density, seed=seed)


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_span_nesting_and_attrs():
    tr = Tracer(enabled=True, ring_capacity=64)
    with tr.span("outer", a=1) as outer:
        with tr.span("inner") as inner:
            inner.set_attr("late", "yes")
    evs = tr.events()
    assert [e.name for e in evs] == ["inner", "outer"]  # close order
    by_name = {e.name: e for e in evs}
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["outer"].parent_id is None
    assert by_name["outer"].attrs == {"a": 1}
    assert by_name["inner"].attrs == {"late": "yes"}
    assert by_name["outer"].duration_ms >= by_name["inner"].duration_ms >= 0
    assert outer.span_id != inner.span_id


def test_disabled_tracer_is_noop_singleton():
    tr = Tracer(enabled=False)
    s1 = tr.span("a", x=1)
    s2 = tr.span("b")
    assert s1 is s2  # one shared noop object: no allocation per call
    with s1 as s:
        s.set_attr("ignored", 0)
    tr.event("never")
    assert tr.events() == []


def test_ring_capacity_bounds_and_keeps_newest():
    tr = Tracer(enabled=True, ring_capacity=8)
    for i in range(50):
        tr.event(f"e{i}")
    evs = tr.events()
    assert len(evs) == 8
    assert [e.name for e in evs] == [f"e{i}" for i in range(42, 50)]


def test_span_records_error_attribute():
    tr = Tracer(enabled=True)
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("nope")
    (ev,) = tr.events()
    assert ev.attrs["error"] == "RuntimeError"


def test_subtree_summary_excludes_root_counts_descendants():
    tr = Tracer(enabled=True)
    with tr.span("root") as root:
        with tr.span("child"):
            with tr.span("leaf"):
                pass
        with tr.span("child"):
            pass
        summary = tr.subtree_summary(root.span_id)
    assert set(summary) == {"child", "leaf"}
    assert summary["child"] >= summary["leaf"] >= 0.0


def test_spans_from_threads_record_thread_identity():
    tr = Tracer(enabled=True)

    def work():
        with tr.span("threaded"):
            pass

    t = threading.Thread(target=work, name="obs-worker")
    t.start()
    t.join()
    (ev,) = tr.events()
    assert ev.thread_name == "obs-worker"


def test_perfetto_export_schema(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("parent", k="v"):
        tr.event("marker")
    out = tmp_path / "trace.json"
    n = tr.export_perfetto(str(out))
    doc = json.loads(out.read_text())
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    spans = [e for e in evs if e["ph"] != "M"]
    assert n == len(spans)  # returns span count; metadata events ride along
    phases = {e["name"]: e["ph"] for e in spans}
    assert phases == {"parent": "X", "marker": "i"}
    for e in evs:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] >= 0
            assert e["args"]["k"] == "v"  # span ids ride in args too
        if e["ph"] != "M":  # metadata events need no timestamp
            assert "ts" in e
        assert {"name", "ph", "pid", "tid"} <= set(e)
    # thread metadata present so Perfetto names the tracks
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evs)


def test_session_dump_roundtrip(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("one"):
        pass
    reg = MetricsRegistry()
    reg.counter("repro_test_dump_total").inc(3)
    path = tmp_path / "session.json"
    tr.dump(str(path), metrics=reg.snapshot())
    doc = obs.load_session(str(path))
    assert doc["format"] == "repro-obs-session"
    assert doc["spans"][0]["name"] == "one"
    assert doc["metrics"]["repro_test_dump_total"] == 3


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_registry_identity_and_kinds():
    reg = MetricsRegistry()
    c1 = reg.counter("repro_x_total", "help")
    c2 = reg.counter("repro_x_total")
    assert c1 is c2
    assert reg.counter("repro_x_total", labels={"k": "a"}) is not c1
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("repro_x_total")
    reg.histogram("repro_h_ms", buckets=(1.0, 2.0))
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("repro_h_ms", buckets=(1.0, 3.0))
    with pytest.raises(ValueError):
        reg.counter("0bad")


def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("repro_c_total")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("repro_g")
    g.set(7)
    g.inc(2)
    g.dec(4)
    assert g.value == 5
    h = reg.histogram("repro_h_ms", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 3 and snap["sum"] == pytest.approx(55.5)
    assert snap["buckets"] == {"1.0": 1, "10.0": 2, "+Inf": 3}


def test_render_prometheus_format():
    reg = MetricsRegistry()
    reg.counter("repro_p_total", "a counter", labels={"kind": "x"}).inc(2)
    reg.gauge("repro_p_gauge", "a gauge").set(1.5)
    reg.histogram("repro_p_ms", "a histogram", buckets=(1.0,)).observe(0.5)
    text = reg.render_prometheus()
    assert "# HELP repro_p_total a counter\n# TYPE repro_p_total counter" in text
    assert 'repro_p_total{kind="x"} 2' in text
    assert "repro_p_gauge 1.5" in text
    assert 'repro_p_ms_bucket{le="1.0"} 1' in text
    assert 'repro_p_ms_bucket{le="+Inf"} 1' in text
    assert "repro_p_ms_sum 0.5" in text and "repro_p_ms_count 1" in text


def test_registry_hammer_exact_totals():
    """N threads x M increments: counters lose nothing, histograms count
    every observation."""
    reg = MetricsRegistry()
    c = reg.counter("repro_hammer_total")
    g = reg.gauge("repro_hammer_gauge")
    h = reg.histogram("repro_hammer_ms", buckets=(1.0, 10.0))
    N, M = 8, 500

    def work():
        for _ in range(M):
            c.inc()
            g.inc(2)
            g.dec()
            h.observe(0.5)

    threads = [threading.Thread(target=work) for _ in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * M
    assert g.value == N * M
    snap = h.snapshot()
    assert snap["count"] == N * M
    assert snap["buckets"]["+Inf"] == N * M


def test_service_metrics_hammer_consistent_snapshots():
    """ServiceMetrics under concurrent submit/flush/failure traffic: exact
    totals at the end, and every mid-flight snapshot() internally
    consistent (pending = submitted - completed - failed >= 0)."""
    from repro.serve.metrics import ServiceMetrics

    m = ServiceMetrics(latency_window=64)
    N, M = 6, 200
    stop = threading.Event()
    bad = []

    def producer():
        for _ in range(M):
            m.on_submit()
            m.on_flush(
                reason="full", batch_size=1, dispatches=1, nnz_real=10,
                nnz_padded=16, execute_ms=1.0, queue_ms=[0.5],
                total_ms=[1.5],
            )
        m.on_submit(2)
        m.on_failure(2)
        m.on_retry()

    def reader():
        while not stop.is_set():
            s = m.snapshot()
            if s["pending"] < 0 or s["completed"] > s["submitted"]:
                bad.append(s)

    threads = [threading.Thread(target=producer) for _ in range(N)]
    watcher = threading.Thread(target=reader)
    watcher.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    watcher.join()
    assert bad == []
    s = m.snapshot()
    assert s["submitted"] == N * (M + 2)
    assert s["completed"] == N * M
    assert s["failed"] == 2 * N
    assert s["pending"] == 0
    assert s["dispatches"] == N * M
    assert s["flushes"] == {"full": N * M}
    assert s["retries"] == N
    assert s["requests_per_dispatch"] == pytest.approx(1.0)
    assert s["padding_overhead"] == pytest.approx(1.6)
    assert s["queue"]["count"] == N * M and s["queue"]["window"] == 64


def test_latency_tracker_window_vs_count():
    from repro.serve.metrics import LatencyTracker

    t = LatencyTracker(maxlen=4)
    empty = t.summary()
    assert empty["count"] == 0 and empty["window"] == 0
    assert np.isnan(empty["p50_ms"])
    for v in range(10):
        t.observe(float(v))
    s = t.summary()
    assert s["count"] == 10 and s["window"] == 4
    # percentiles computed over the retained window (6..9), not lifetime
    assert s["max_ms"] == 9.0 and s["p50_ms"] == pytest.approx(7.5)


def test_service_metrics_visible_in_prometheus():
    from repro.serve.metrics import ServiceMetrics

    m = ServiceMetrics()
    m.on_submit(3)
    m.on_flush(
        reason="timeout", batch_size=3, dispatches=1, nnz_real=30,
        nnz_padded=48, execute_ms=2.0, queue_ms=[0.1, 0.2, 0.3],
        total_ms=[2.1, 2.2, 2.3],
    )
    text = obs.registry.render_prometheus()
    svc = f'service="{m.service}"'
    assert f"repro_serve_submitted_total{{{svc}}} 3" in text
    assert f"repro_serve_dispatches_total{{{svc}}} 1" in text
    assert f'repro_serve_flushes_total{{reason="timeout",{svc}}} 1' in text
    assert f"repro_serve_queue_latency_ms_count{{{svc}}} 3" in text


# ---------------------------------------------------------------------------
# Instrumented stack
# ---------------------------------------------------------------------------


def test_trace_summary_none_when_disabled():
    from repro import tucker

    spec = tucker.TuckerSpec(shape=SHAPE, ranks=RANKS, n_iter=1, engine="xla")
    res = tucker.plan(spec)(_coo())
    assert res.trace_summary is None


def test_trace_summary_and_lifecycle_spans(traced):
    from repro import tucker

    hits0 = obs.registry.counter("repro_plan_cache_hits_total").value
    spec = tucker.TuckerSpec(
        shape=SHAPE, ranks=RANKS, n_iter=2, engine="xla", method="gram",
        tol=0.0,
    )
    plan = tucker.plan(spec)
    res = plan(_coo())
    res2 = tucker.plan(spec)(_coo(seed=1))  # second lookup: a cache hit
    assert res.trace_summary is not None
    assert "sweep.dispatch" in res.trace_summary
    assert res.trace_summary["sweep.dispatch"] > 0.0
    assert res2.trace_summary is not None
    names = {e.name for e in traced.events()}
    assert {"plan.call", "plan.cache.lookup", "sweep.dispatch"} <= names
    # second plan() call for the same spec was a registry-visible cache hit
    assert (
        obs.registry.counter("repro_plan_cache_hits_total").value > hits0
    )
    dispatch = [e for e in traced.events() if e.name == "sweep.dispatch"]
    assert all(e.attrs["program"] == "scan" for e in dispatch)
    assert all("retraces" in e.attrs for e in dispatch)


def test_serve_spans_linked_by_ticket(traced, tmp_path):
    """The acceptance criterion: ONE exported Perfetto trace shows a
    request's submit→enqueue→flush→dispatch→split chain linked by its
    ticket id, across the producer and scheduler threads."""
    from repro import tucker
    from repro.serve import ServiceConfig, TuckerService

    spec = tucker.TuckerSpec(shape=SHAPE, ranks=RANKS, n_iter=1, engine="xla")
    coos = [_coo(seed=s) for s in range(4)]
    with TuckerService(ServiceConfig(max_batch=4, max_wait_ms=50.0)) as svc:
        results = svc.decompose_batch(coos, spec, timeout=300)
    assert len(results) == 4

    evs = traced.events()
    submits = [e for e in evs if e.name == "serve.submit"]
    assert len(submits) == 4
    tid = submits[0].attrs["ticket"]

    def links(e):
        return e.attrs.get("ticket") == tid or (
            tid in (e.attrs.get("tickets") or [])
        )

    chain = {e.name for e in evs if links(e)}
    assert {"serve.submit", "serve.enqueue", "serve.flush",
            "serve.dispatch", "serve.split"} <= chain
    # the flush chain ran on a different thread than the submit
    sub_tid = submits[0].thread_id
    flush = next(e for e in evs if e.name == "serve.flush" and links(e))
    assert flush.thread_id != sub_tid

    out = tmp_path / "serve.json"
    traced.export_perfetto(str(out))
    doc = json.loads(out.read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"serve.submit", "serve.flush", "serve.dispatch",
            "serve.split"} <= names


def test_env_override_parsing():
    try:
        # off-ish values leave tracing alone, no dump path
        for v in (None, "", "0", "off", "FALSE", "no"):
            assert obs._apply_env(v) is None
            assert not obs.enabled()
        # on values enable, still no dump path
        assert obs._apply_env("1") is None
        assert obs.enabled()
        obs.configure(enabled=False)
        # anything else is a session dump path (and enables)
        assert obs._apply_env("/tmp/obs-session.json") == "/tmp/obs-session.json"
        assert obs.enabled()
    finally:
        obs.configure(enabled=False)


def test_obs_cli_offline_modes(tmp_path, capsys):
    from repro.obs.__main__ import main as obs_main

    tr = Tracer(enabled=True)
    with tr.span("plan.call"):
        with tr.span("sweep.dispatch"):
            pass
    reg = MetricsRegistry()
    reg.counter("repro_cli_total").inc(2)
    session = tmp_path / "s.json"
    tr.dump(str(session), metrics=reg.snapshot())

    assert obs_main([str(session), "--summary"]) == 0
    out = capsys.readouterr().out
    assert "plan.call" in out and "sweep.dispatch" in out

    perf = tmp_path / "p.json"
    assert obs_main([str(session), "--perfetto", str(perf)]) == 0
    capsys.readouterr()
    doc = json.loads(perf.read_text())
    assert {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"} == {
        "plan.call", "sweep.dispatch"
    }

    assert obs_main([str(session), "--prom"]) == 0
    assert "repro_cli_total 2" in capsys.readouterr().out
