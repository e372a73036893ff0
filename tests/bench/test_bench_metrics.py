"""End-to-end metric arithmetic over a window, the open loop's timing from
due time, and the Kronecker accumulation's operation and byte count."""
import time

import numpy as np
import pytest

from bench import harness, loads


def _ctx(window, **kw):
    return harness.Context(cell={}, config=kw.get("config", {}), traffic={}, window=window,
                           setup_s=kw.get("setup_s", 3.0), peaks=kw.get("peaks", {}),
                           compiles=kw.get("compiles", []))


def _read(name, ctx):
    return harness.reader(name).read(ctx)


def test_decompose_s_is_the_whole_window_over_the_calls():
    # four calls of 2.5 s from t0 = 100; the last ends past the nominal 9 s
    recs = [loads.Record(i, 100 + 2.5 * i, 100 + 2.5 * i, 102.5 + 2.5 * i, answer=1)
            for i in range(4)]
    ctx = _ctx(loads.Window(100.0, 9.0, recs))
    assert _read("decompose_s", ctx) == pytest.approx(10.0 / 4)
    assert _read("setup_s", ctx) == 3.0


def test_serving_percentiles_and_rate_over_all_requests():
    t0 = 50.0
    lat = [0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0]
    recs = [loads.Record(i, t0 + i, t0 + i, t0 + i + v, answer=object())
            for i, v in enumerate(lat)]
    recs[9].error = RuntimeError("failed")  # counts as the window's length
    ctx = _ctx(loads.Window(t0, 10.0, recs))
    values = lat[:9] + [10.0]
    assert _read("serve_p50_ms", ctx) == pytest.approx(np.percentile(values, 50) * 1e3)
    assert _read("serve_p90_ms", ctx) == pytest.approx(np.percentile(values, 90) * 1e3)
    # nine answered; the window runs to the last answer (t0 + 8.9) or its end
    assert _read("serve_rps", ctx) == pytest.approx(9 / 10.0)
    assert ctx.window.failed == 1


def test_arrivals_fill_the_window_with_an_exact_count():
    a = loads.arrivals(500, 50.0, 3)
    b = loads.arrivals(500, 50.0, 3)
    c = loads.arrivals(500, 50.0, 4)
    assert len(a) == 500 and np.array_equal(a, b)
    assert 0 < a[0] and a[-1] < 50.0 and np.all(np.diff(a) > 0)
    # the schedule is the traffic file's, not the run's: another arrival seed
    # is another schedule
    assert not np.array_equal(a, c)
    assert loads.offered({"rate_per_s": 3.5}, 50) == 175


def test_open_loop_times_from_due_time_through_a_stall():
    def request(i):
        if i == 0:
            time.sleep(0.3)  # the one client is held: later requests go out late
        return i

    offsets = np.array([0.0, 0.05, 0.1])
    w = loads.open_loop(request, 0.2, offsets, clients=1)
    lat = w.latencies_s()
    assert lat[0] >= 0.3
    # due at 0.05 but sent only after the stall: its latency carries the wait
    assert lat[1] >= 0.25 and w.records[1].sent - w.records[1].due >= 0.2
    assert lat[2] >= 0.2
    assert [r.answer for r in w.records] == [0, 1, 2]


def test_closed_loop_runs_the_last_call_to_its_end():
    calls = []

    def request(i):
        time.sleep(0.04)
        calls.append(i)
        return i

    w = loads.closed(request, 0.1)
    assert len(w.records) == len(calls) >= 2
    assert w.t1 >= w.t0 + 0.1
    assert all(r.done >= r.sent for r in w.records)


def test_kron_work_counted_by_hand():
    m = harness.reader("kron_roofline.decompose")
    shape, ranks, nnz = (4, 5, 6), (2, 3, 4), 10
    # mode 0: the row is U_1(j) (x) U_2(k), K = 12 products; scale and add
    ops, nbytes = m.mode_work(shape, ranks, nnz, 0)
    assert ops == 10 * (12 + 2 * 12)
    # indices and value per nonzero, the other two factors once, Y_(0) once
    assert nbytes == 4 * (10 * 4 + (5 * 3 + 6 * 4) + 4 * 12)
    ops2, nbytes2 = m.mode_work(shape, ranks, nnz, 2)
    assert ops2 == 10 * (6 + 12) and nbytes2 == 4 * (40 + (4 * 2 + 5 * 3) + 6 * 6)
    peaks = {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    per = [max(o, b) for o, b in (m.mode_work(shape, ranks, nnz, n) for n in range(3))]
    total_ops = sum(m.mode_work(shape, ranks, nnz, n)[0] for n in range(3))
    total_bytes = sum(m.mode_work(shape, ranks, nnz, n)[1] for n in range(3))
    assert m.least_seconds(shape, ranks, nnz, 2, peaks) == pytest.approx(
        2 * max(total_ops, total_bytes) / 1e3)
    assert max(per) > 0


def test_nell2_kron_accumulation_is_memory_bound_on_v5e():
    m = harness.reader("kron_roofline.decompose")
    peaks = harness.peaks_for("TPU v5 lite")
    shape, ranks, nnz = (12092, 9184, 28818), (16, 16, 16), 2**22
    ops = sum(m.mode_work(shape, ranks, nnz, n)[0] for n in range(3))
    nbytes = sum(m.mode_work(shape, ranks, nnz, n)[1] for n in range(3))
    assert ops / nbytes < peaks["flops_per_s"] / peaks["hbm_bytes_per_s"]
    assert m.least_seconds(shape, ranks, nnz, 5, peaks) == pytest.approx(
        5 * nbytes / peaks["hbm_bytes_per_s"])


def test_queue_reader_and_readers_without_a_trace():
    class Timing:
        def __init__(self, q):
            self.queue_ms = q

    class Answer:
        def __init__(self, q):
            self.timing = Timing(q)

    recs = [loads.Record(i, 0, 0, 1, answer=Answer(q)) for i, q in enumerate([1.0, 5.0, 3.0])]
    ctx = _ctx(loads.Window(0.0, 1.0, recs))
    assert _read("queue_ms.serve", ctx) == pytest.approx(3.0)
    assert _read("schedule_ms.serve", ctx) is None  # no spans: an untraced run
    assert _read("kron_ms.decompose", ctx) is None  # no trace


def test_compile_reader_counts_backend_compiles_inside_the_window():
    recs = [loads.Record(i, 10 + i, 10 + i, 11 + i, answer=1) for i in range(4)]
    backend = "/jax/core/compile/backend_compile_duration"
    compiles = [(9.0, backend, 5.0),  # set-up: before the window
                (11.0, backend, 0.4), (12.5, backend, 0.6),
                (12.6, "/jax/core/compile/jaxpr_trace_duration", 0.3),
                (13.9, backend, 1.0)]
    ctx = _ctx(loads.Window(10.0, 3.0, recs), compiles=compiles)
    # the window runs to the last answer at 14: three compiles of 2 s in all
    assert _read("compile_ms.serve", ctx) == pytest.approx(2.0 / 4 * 1e3)
    assert _read("compile_ms.serve", _ctx(loads.Window(10.0, 3.0, recs))) == 0.0


def test_every_seed_serves_the_same_days():
    import json
    from pathlib import Path

    from bench import systems

    config = json.loads((Path(harness.ROOT) / "bench/configs/uber_day.json").read_text())
    config = dict(config, days=5)
    traffic = {"loop": "open", "rate_per_s": 1.0}
    mixes = []
    for seed in (1, 2**31 + 5):
        system = systems.ServiceSystem(config, traffic, seed, 7)
        reqs = system.window_requests(np.random.default_rng(seed))
        mixes.append([(r.tenant, r.pattern.nnz) for r in reqs])
    # seven requests over five days: days 0 and 1 come round again, to other tenants
    assert len(mixes[0]) == 7 and len(set(mixes[0])) == 7
    assert sorted(mixes[0]) == sorted(mixes[1]) and mixes[0] != mixes[1]
    assert sorted(t for t, _ in mixes[0]) == [0, 0, 1, 1, 2, 2, 3]
    lo, hi = config["nnz_per_request"]
    assert all(lo <= n <= hi for _, n in mixes[0])
