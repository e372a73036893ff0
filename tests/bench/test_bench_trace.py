"""The trace reduction: busy union, idle share, kernel time by name, exposed
collective time and the breakdown, on hand-made intervals and on a trace
recorded on a TPU v5e (a one-chip Pallas decomposition of a small surrogate,
two calls inside the ``bench.window`` annotation)."""
from pathlib import Path

import numpy as np
import pytest

from bench import harness, trace

DATA = Path(__file__).resolve().parent / "data"
PALLAS = DATA / "pallas_small.xplane.pb"


def _is_all_reduce(hlo):
    return " all-reduce(" in hlo


def _device(spans, names):
    iv = np.asarray(spans, dtype=np.float64)
    ids = np.arange(len(names))
    return trace.Device(dict(enumerate(names)), ids, iv, trace.union(iv))


def test_union_measure_and_intersect():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9], [10, 11]], dtype=float)
    u = trace.union(iv)
    assert u.tolist() == [[0, 3], [5, 9], [10, 11]]
    assert trace.measure(u) == 8
    assert trace.intersect(u, np.array([[2.0, 6.0], [8.5, 10.5]])) == pytest.approx(
        1 + 1 + 0.5 + 0.5)
    assert trace.measure(trace.union(np.zeros((0, 2)))) == 0


def test_idle_kernel_and_exposed_time():
    names = ["%psum.1 = f32[8] all-reduce(%a)", "%fusion.2 = f32[8] fusion(%b)",
             "%k.3 = f32[8] custom-call(%c), custom_call_target=\"tpu_custom_call\""]
    dev = _device([[0, 40], [30, 60], [70, 80]], names)
    r = trace.Reduced((0.0, 100.0), [dev], [(65.0, 69.0, "plan.call"), (0.0, 100.0, "x")])
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(70e-9)
    assert r.idle_share() == pytest.approx(0.3)
    is_psum = _is_all_reduce
    assert r.op_seconds(is_psum) == pytest.approx(40e-9)
    # 0-30 of the all-reduce runs with nothing else on the chip
    assert r.exposed_seconds(is_psum) == [pytest.approx(30e-9)]
    b = r.breakdown()
    assert b["device_ops"][0] == ["psum.1 f32[8]", pytest.approx(40e-9)]
    # the longest gap, 80-100, has only the outer span open; 60-70 the inner
    assert b["idle_gaps"][0] == ["x", pytest.approx(20e-9)]
    assert b["idle_gaps"][1] == ["plan.call", pytest.approx(10e-9)]


def test_control_flow_is_not_an_operation():
    assert trace.is_container("%while.72 = (s32[], f32[4]{0}) while(%tuple.1), condition=%c")
    assert trace.is_container("%cond.3 = (f32[4]) conditional(%p, %a, %b)")
    assert not trace.is_container("%custom-call.4 = f32[16] custom-call(), "
                                  "custom_call_target=\"AllocateBuffer\"")
    assert not trace.is_container("%fusion.1 = f32[4] fusion(%a), kind=kLoop, "
                                  "calls=%fused_computation")
    assert trace.op_name("%fusion.12 = f32[2] fusion(%x)") == "fusion.12"
    assert trace.op_label("%fusion.12 = f32[4208640]{0:T(1024)S(1)} fusion(%x)") == (
        "fusion.12 f32[4208640]")
    assert trace.op_label("%while.1 = (s32[], f32[2]) while(%t)") == "while.1"


@pytest.mark.skipif(not PALLAS.exists(), reason="recorded trace not present")
def test_recorded_pallas_trace():
    r = trace.reduce(str(PALLAS), 1)
    assert r.n_devices == 1
    # read off this file once: a change to the reduction shows here
    assert r.window_s == pytest.approx(0.018184259)
    assert r.busy_s == pytest.approx(0.0023747163, rel=1e-6)
    kron = harness.reader("kron_ms.decompose")
    k = r.op_seconds(kron.matches)
    assert k == pytest.approx(0.00072915242, rel=1e-6)
    # three modes' fused Kron-scatter kernels, one sweep per call, two calls
    names = {trace.op_name(r.devices[0].names[int(i)]) for i in r.devices[0].ids
             if kron.matches(r.devices[0].names[int(i)])}
    assert {n.split(".")[0] for n in names} == {"_fused_call"} and len(names) == 3
    assert 0 < k < r.busy_s
    b = r.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert sum(v for _, v in b["device_ops"]) <= r.busy_s + 1e-9
    assert r.op_seconds(_is_all_reduce) == 0
