"""``psum_ms.decompose`` on hand-made two-chip traces: only the part of an
all-reduce that no other operation on its chip covers counts, averaged over
the chips and divided by the calls; without a trace, or without an
all-reduce, it reads nothing."""
import numpy as np
import pytest

from bench import harness, loads, trace

SYNC = "%psum.18 = f32[12092,256]{1,0} all-reduce(%fusion.10), channel_id=1"
START = "%all-reduce-start.2 = f32[9184,256]{1,0} all-reduce-start(%fusion.11)"
DONE = "%all-reduce-done.2 = f32[9184,256]{1,0} all-reduce-done(%all-reduce-start.2)"
FUSION = "%fusion.10 = f32[12092,256]{1,0} fusion(%p0, %p1), kind=kLoop"
# an operand named like an all-reduce is not one
USES = "%fusion.7 = f32[9184,256]{1,0} fusion(%all-reduce.3, %x), kind=kLoop"


def _device(spans, names):
    iv = np.asarray(spans, dtype=np.float64)
    return trace.Device(dict(enumerate(names)), np.arange(len(names)), iv, trace.union(iv))


def _ctx(reduced, calls):
    recs = [loads.Record(i, 0, 0, 1, answer=1) for i in range(calls)]
    return harness.Context(cell={}, config={}, traffic={}, window=loads.Window(0.0, 1.0, recs),
                           setup_s=1.0, peaks={}, trace=reduced)


def _read(ctx):
    return harness.reader("psum_ms.decompose").read(ctx)


def test_exposed_all_reduce_over_chips_and_calls():
    # chip 0: a synchronous all-reduce over 0-40 ms, a fusion over 10-60 (10 ms
    # exposed); chip 1: an async pair, start 0-10 and done 20-50, a
    # fusion over 5-25 (5 + 25 = 30 ms exposed), and a fusion that reads an
    # all-reduce's result
    ms = 1e6
    chip0 = _device([[0, 40 * ms], [10 * ms, 60 * ms]], [SYNC, FUSION])
    chip1 = _device([[0, 10 * ms], [20 * ms, 50 * ms], [5 * ms, 25 * ms], [60 * ms, 70 * ms]],
                    [START, DONE, FUSION, USES])
    r = trace.Reduced((0.0, 100 * ms), [chip0, chip1], [])
    assert _read(_ctx(r, 1)) == pytest.approx((10 + 30) / 2)
    assert _read(_ctx(r, 4)) == pytest.approx((10 + 30) / 2 / 4)


def test_fully_overlapped_all_reduce_reads_zero():
    chip = _device([[10, 20], [0, 30]], [SYNC, FUSION])
    r = trace.Reduced((0.0, 100.0), [chip], [])
    assert _read(_ctx(r, 2)) == 0.0


def test_reads_nothing_without_a_trace_or_an_all_reduce():
    assert _read(_ctx(None, 3)) is None
    one_chip = trace.Reduced((0.0, 100.0), [_device([[0, 50], [60, 70]], [FUSION, USES])], [])
    assert _read(_ctx(one_chip, 3)) is None
