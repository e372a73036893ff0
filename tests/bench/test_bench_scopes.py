"""Device time by the program's stage scopes (``bench/scopes.py``) and the
front end's tracing and lowering time (``lower_ms.serve``), on hand-made
inputs and on a trace recorded on a TPU v5e (``record_trace.py``: a
one-chip Pallas decomposition of a small surrogate, two calls inside the
``bench.window`` annotation, the program's spans on)."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, loads, scopes, trace, xplane
from repro.obs import SpanEvent

DATA = Path(__file__).resolve().parent / "data"
SCOPED = DATA / "pallas_scoped.xplane.pb"
SPANS = DATA / "pallas_scoped.spans.json"
PALLAS = DATA / "pallas_small.xplane.pb"


def _span(name, t0, t1, thread=1):
    return SpanEvent(name=name, t0=t0, t1=t1, span_id=0, parent_id=None, thread_id=thread,
                     thread_name=f"t{thread}", attrs={})


def _ctx(spans, n=4):
    recs = [loads.Record(i, 10 + i, 10 + i, 11 + i, answer=1) for i in range(n)]
    return harness.Context(cell={}, config={}, traffic={}, window=loads.Window(10.0, 3.0, recs),
                           setup_s=1.0, peaks={}, spans=spans)


def test_lower_reader_takes_the_union_per_thread_in_the_window():
    spans = [
        _span("jit.trace", 10.5, 10.9),
        _span("jit.trace", 10.6, 10.7),  # traced inside the outer trace: counted once
        _span("jit.lower", 10.9, 11.0),
        _span("jit.compile", 11.0, 12.0),  # compile_ms.serve's
        _span("jit.trace", 10.5, 10.8, thread=2),  # another thread counts on its own
        _span("jit.trace", 9.0, 9.5),  # set-up
        _span("sweep.dispatch", 10.4, 12.1),
    ]
    lower = harness.reader("lower_ms.serve")
    assert lower.read(_ctx(spans)) == pytest.approx((0.4 + 0.1 + 0.3) / 4 * 1e3)
    # an untraced run, and a program that records no jit.* spans, give nothing
    assert lower.read(_ctx(None)) is None
    assert lower.read(_ctx([_span("sweep.dispatch", 10.4, 12.1)])) is None


def test_stage_of_an_operation_path():
    assert scopes.stage("jit(_scan_sweeps_impl)/while/body/closed_call/tucker.kron/"
                        "pallas_call:") == "tucker.kron"
    assert scopes.stage("jit(_batched_scan_sweeps)/vmap(tucker.init)/jit(_normal)/add:") == (
        "tucker.init")
    assert scopes.stage("jit(qr)/householder_product:") is None


def test_stage_seconds_and_scoped_share_by_hand():
    names = {0: "%a = f32[8] fusion(%x)", 1: "%b = f32[8] fusion(%y)", 2: "%c = f32[8] fusion(%z)"}
    iv = np.array([[0, 40], [40, 60], [70, 80], [80, 90]], dtype=np.float64)
    dev = trace.Device(names, np.array([0, 1, 2, 0]), iv, trace.union(iv))
    r = trace.Reduced((0.0, 100.0), [dev], [])
    paths = [{0: "jit(f)/while/body/tucker.order_gather/gather:", 1: "jit(f)/tucker.qrp/dot:",
              2: "jit(qr)/geqrf:"}]
    assert scopes.stage_seconds(r, paths) == {"tucker.order_gather": pytest.approx(50e-9),
                                              "tucker.qrp": pytest.approx(20e-9)}
    assert scopes.scoped_share(r, paths) == pytest.approx(70 / 80)


def test_a_trace_without_scopes_has_no_stages():
    r = trace.reduce(str(PALLAS), 1)
    paths = scopes.op_paths(str(PALLAS), 1)
    # the operations have paths, none of them a stage's
    assert len(paths) == 1 and paths[0]
    assert scopes.stage_seconds(r, paths) == {}
    assert scopes.scoped_share(r, paths) == 0.0


def test_recorded_scoped_trace():
    r = trace.reduce(str(SCOPED), 1)
    paths = scopes.op_paths(str(SCOPED), 1)
    st = scopes.stage_seconds(r, paths)
    assert set(st) == {"tucker.order_gather", "tucker.row_gather", "tucker.kron",
                       "tucker.qrp", "tucker.core"}
    assert sum(st.values()) <= r.busy_s
    # every kernel kron_ms.decompose finds by name is under tucker.kron; the
    # rest of the stage is the operand preparation inside the kernels'
    # wrapper (the values and rows broadcast to columns)
    kron = harness.reader("kron_ms.decompose")
    d, p = r.devices[0], paths[0]
    assert all(scopes.stage(p[k]) == "tucker.kron" for k, hlo in d.names.items()
               if kron.matches(hlo))
    wrapper = 0.0
    for k, (s0, s1) in zip(d.ids, d.spans):
        if scopes.stage(p.get(int(k), "")) == "tucker.kron" and not kron.matches(d.names[int(k)]):
            assert "jit(_fused_call)" in p[int(k)]
            wrapper += (s1 - s0) / 1e9
    assert st["tucker.kron"] == pytest.approx(r.op_seconds(kron.matches) + wrapper, rel=1e-9)
    assert 0 < wrapper < r.op_seconds(kron.matches)
    # at this size the eager preamble (starting factors, norm), which runs
    # outside the program and so unscoped, is still a few percent
    assert scopes.scoped_share(r, paths) >= 0.95


def test_spans_land_where_their_annotations_are():
    """The harness places the program's spans on the trace's clock through
    the window annotation's start; each span's own profiler annotation
    shows where it really was."""
    rec = json.loads(SPANS.read_text())
    spans = [SimpleNamespace(**s) for s in rec["spans"]]
    r = trace.reduce(str(SCOPED), 1, spans, rec["anchor"])
    annotated = {}
    for p in xplane.read(str(SCOPED)):
        if p.name.startswith("/host:"):
            for ln in p.lines:
                for k, s, e in zip(ln.ids, ln.start_ns, ln.end_ns):
                    annotated.setdefault(p.names[int(k)], []).append((s, e))
    checked = 0
    for s, e, name in r.host:
        if name.startswith("jit.") or name not in annotated:
            continue  # jit.* spans are recorded after the fact, unannotated
        (s_ann, e_ann) = min(annotated[name], key=lambda iv: abs(iv[0] - s))
        assert abs(s - s_ann) < 1e5  # 0.1 ms
        assert abs((e - s) - (e_ann - s_ann)) < 1e6  # 1 ms
        checked += 1
    assert checked >= 4  # plan.call and sweep.dispatch of each of the two calls


def test_traced_serving_run_reports_tracing_and_lowering(tmp_path, cpu_run, monkeypatch):
    # as in test_bench_harness: the CPU's profile has no device plane, so the
    # reduction reads a recorded trace; the program's spans are this run's
    import tiny

    recorded = trace.reduce
    monkeypatch.setattr(trace, "reduce", lambda path, chips, spans, anchor:
                        recorded(str(PALLAS), chips, spans, anchor))
    root = tiny.make(tmp_path, serve={"spec": {"engine": "pallas"}})
    rc, res, _ = cpu_run(root, "tiny.open", trace=1)
    assert rc == 0 and res["correct"] is True
    assert {"compile_ms.serve", "lower_ms.serve", "schedule_ms.serve"} <= set(res["metrics"])
    assert res["metrics"]["lower_ms.serve"]["value"] > 0
    assert res["metrics"]["lower_ms.serve"]["unit"] == "ms/request"
