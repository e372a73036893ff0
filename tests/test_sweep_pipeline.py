"""Regression harness for the compiled scan-over-sweeps program (core.hooi).

Four contracts:

1. *Early exit*: the ``tol`` early exit stops at the first sweep where the
   independent reference's fit history (``bench/reference.py``) changes by
   less than ``tol``, with the same per-sweep errors, on every engine. (Fit
   parity of every compiled program with that reference is
   ``tests/test_reference_parity.py``.)
2. *No retrace*: a second call on a same-shape tensor must hit the
   compiled-sweep jit cache (zero new traces) and dispatch exactly one XLA
   program regardless of ``n_iter``.
3. *Single transfer*: the fit history crosses device->host exactly once per
   call.
4. *Schedules*: the vectorized ``build_schedule`` matches the original
   per-row-block reference loop, device schedules upload once, and a rebound
   engine does not pin the previous tensor's indices.
"""
import gc
import os
import sys
import weakref

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import tucker
from repro.core import engine as E
from repro.core import hooi
from repro.sparse.generators import random_sparse_tensor
from repro.sparse.layout import DeviceSchedule, build_schedule

# the plain reference is imported as the ``bench`` package from the repo root
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import reference  # noqa: E402

ENGINES = E.available_engines()


def _total_traces():
    return sum(hooi.SWEEP_TRACE_COUNTS.values())


def _dispatches(engine):
    return hooi.SWEEP_DISPATCH_COUNTS[(engine, "scan")]


def _fit(coo, ranks, n_iter, engine, tol=0.0, method="gram"):
    return tucker.decompose(coo, ranks, n_iter=n_iter, method=method,
                            engine=engine, tol=tol)


def test_unknown_pipeline_raises():
    """The spec refuses what no program runs: zero sweeps, an unknown
    engine."""
    coo = random_sparse_tensor((8, 8, 8), 0.05, seed=34)
    with pytest.raises(ValueError, match="n_iter"):
        _fit(coo, (2, 2, 2), 0, "xla")
    with pytest.raises(ValueError, match="engine"):
        _fit(coo, (2, 2, 2), 1, "fpga")


# ---------------------------------------------------------------------------
# 1. tol early exit: the reference's stop sweep and errors, both engines.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_tol_early_exit_parity(engine):
    coo = random_sparse_tensor((25, 20, 15), 0.05, seed=3)
    ranks, tol, n_iter = (3, 3, 2), 1e-3, 10
    key = jax.random.PRNGKey(0)
    x = reference.Tensor(np.asarray(coo.indices), np.asarray(coo.values),
                         coo.shape, block=4096)
    _, _, ref = reference.hooi(x, ranks, n_iter, key)
    # the reference stops at the first sweep whose error moved less than tol
    stop = next(s for s in range(2, n_iter + 1)
                if abs(ref[s - 1] - ref[s - 2]) < tol)
    # the early exit fires (otherwise this test checks nothing), and the
    # crossing is clear of tol by far more than the 1e-5 fit tolerance
    assert stop < n_iter
    assert all(abs(abs(ref[s - 1] - ref[s - 2]) - tol) > 1e-4
               for s in range(2, stop + 1))
    res = tucker.plan(tucker.TuckerSpec(
        shape=coo.shape, ranks=ranks, method="gram", engine=engine,
        n_iter=n_iter, tol=tol))(coo, key=key)
    assert res.engine == engine
    assert len(res.fit_history) == stop
    np.testing.assert_allclose(res.fit_history, ref[:stop], atol=1e-5)


def test_tol_zero_runs_all_sweeps():
    coo = random_sparse_tensor((15, 12, 10), 0.05, seed=4)
    res = _fit(coo, (3, 3, 2), 4, "xla", tol=0.0)
    assert len(res.fit_history) == 4
    # the emitted history contains real errors, not skip sentinels
    assert (res.fit_history >= 0).all()


def test_tol_change_does_not_retrace():
    """tol is a dynamic argument of the compiled program — sweeping it (e.g.
    a tolerance study) must not recompile."""
    coo = random_sparse_tensor((15, 12, 10), 0.05, seed=5)
    _fit(coo, (3, 3, 2), 4, "xla", tol=1e-2)
    before = _total_traces()
    for tol in (0.0, 1e-5, 0.3):
        _fit(coo, (3, 3, 2), 4, "xla", tol=tol)
    assert _total_traces() == before


# ---------------------------------------------------------------------------
# 2. No-retrace + dispatch-count regression (the perf contract).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_no_retrace_on_same_shape(engine):
    """Two same-shape tensors: the second call must hit the compiled
    sweep's jit cache — zero new traces — and cost exactly one dispatch,
    independent of n_iter."""
    shape, ranks, n_iter = (20, 16, 12), (3, 3, 2), 4
    coo_a = random_sparse_tensor(shape, 0.05, seed=41)
    coo_b = random_sparse_tensor(shape, 0.05, seed=42)
    _fit(coo_a, ranks, n_iter, engine)  # warm (may trace)
    traces = _total_traces()
    cache = hooi._scan_sweeps._cache_size()
    d0 = _dispatches(engine)
    res = _fit(coo_b, ranks, n_iter, engine)
    assert _total_traces() == traces, "same-shape call retraced the pipeline"
    assert hooi._scan_sweeps._cache_size() == cache
    assert _dispatches(engine) - d0 == 1  # 1 dispatch per call, not per sweep
    assert len(res.fit_history) == n_iter


# ---------------------------------------------------------------------------
# 3. Single device->host transfer for the fit history.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_single_history_transfer(engine, monkeypatch):
    """The scan program fetches the fit history with exactly one device_get;
    nothing else in the call forces a device->host sync."""
    coo = random_sparse_tensor((20, 16, 12), 0.05, seed=44)
    spec = tucker.TuckerSpec(shape=coo.shape, ranks=(3, 3, 2), method="gram",
                             engine=engine, n_iter=5)
    p = tucker.plan(spec, engine=E.make_engine(engine))
    p(coo)  # warm: schedules + compile
    calls = []

    def counting_fetch(x):
        calls.append(1)
        return jax.device_get(x)

    monkeypatch.setattr(hooi, "_fetch_history", counting_fetch)
    res = p(coo)
    assert len(calls) == 1
    assert len(res.fit_history) == 5


# ---------------------------------------------------------------------------
# 4. Schedules: vectorized builder, one-time upload, no tensor pinning.
# ---------------------------------------------------------------------------


def _build_schedule_reference(rows, n_rows, bn, bi):
    """The original per-row-block Python loop, kept as the oracle for the
    vectorized build_schedule."""
    rows = np.asarray(rows).astype(np.int64)
    nnz = rows.shape[0]
    n_row_blocks = max(1, -(-n_rows // bi))
    perm = np.argsort(rows, kind="stable")
    sorted_rows = rows[perm]
    grp_bounds = np.searchsorted(sorted_rows, np.arange(0, n_row_blocks + 1) * bi)
    order_parts, blkmap, first, last = [], [], [], []
    for g in range(n_row_blocks):
        lo, hi = int(grp_bounds[g]), int(grp_bounds[g + 1])
        if hi == lo:
            continue
        members = perm[lo:hi]
        pad = (-members.size) % bn
        padded = np.concatenate([members, np.full((pad,), -1, dtype=np.int64)])
        order_parts.append(padded)
        n_blocks = padded.size // bn
        blkmap.extend([g] * n_blocks)
        first.extend([1] + [0] * (n_blocks - 1))
        last.extend([0] * (n_blocks - 1) + [1])
    if not order_parts:
        order_parts = [np.full((bn,), -1, dtype=np.int64)]
        blkmap, first, last = [0], [1], [1]
    order = np.concatenate(order_parts)
    valid = (order >= 0).astype(np.float32)
    safe = np.where(order >= 0, order, 0)
    rel = rows[safe] % bi if nnz else np.zeros_like(safe)
    rel = np.where(order >= 0, rel, 0)
    return (safe.astype(np.int32), valid, rel.astype(np.int32),
            np.asarray(blkmap, dtype=np.int32), np.asarray(first, dtype=np.int32),
            np.asarray(last, dtype=np.int32), n_row_blocks)


@pytest.mark.parametrize("case", [
    dict(n_rows=37, nnz=200, bn=16, bi=8, seed=0),
    dict(n_rows=64, nnz=1, bn=32, bi=16, seed=1),
    dict(n_rows=5, nnz=300, bn=8, bi=4, seed=2),     # dense-ish, multi-block rows
    dict(n_rows=1000, nnz=50, bn=128, bi=128, seed=3),  # mostly-empty groups
    dict(n_rows=10, nnz=0, bn=32, bi=8, seed=4),     # empty tensor
])
def test_build_schedule_matches_reference_loop(case):
    rng = np.random.default_rng(case["seed"])
    rows = rng.integers(0, case["n_rows"], size=case["nnz"])
    got = build_schedule(rows, case["n_rows"], case["bn"], case["bi"])
    want = _build_schedule_reference(rows, case["n_rows"], case["bn"], case["bi"])
    for g, w, name in zip(got[:7], want, ("order", "valid", "rel", "blkmap",
                                          "first", "last", "n_row_blocks")):
        np.testing.assert_array_equal(g, w, err_msg=name)


def test_device_schedule_uploaded_once():
    coo = random_sparse_tensor((20, 16, 12), 0.05, seed=45)
    eng = E.make_engine("pallas") if "pallas" in ENGINES else E.make_engine("xla")
    if eng.name != "pallas":
        pytest.skip("needs the pallas schedule path")
    s0 = eng.device_schedule(coo, 0)
    assert isinstance(s0, DeviceSchedule)
    assert isinstance(s0.order, jax.Array)  # device-resident, not numpy
    assert eng.device_schedule(coo, 0) is s0  # cached: no re-upload per sweep


def test_xla_engine_needs_no_schedule():
    coo = random_sparse_tensor((12, 10, 8), 0.05, seed=46)
    eng = E.make_engine("xla")
    assert eng.device_schedule(coo, 0) is None


# ---------------------------------------------------------------------------
# 5. TuckerPlan reuse: the serving steady state is zero retraces AND zero
#    schedule rebuilds (per-call counters on TuckerResult / SweepEngine).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_plan_reuse_zero_retrace_zero_schedule_rebuilds(engine):
    """Second call of a TuckerPlan on the SAME tensor must hit every cache:
    zero new traces of the compiled sweep and zero schedule builds/uploads.
    A DISTINCT same-shape tensor still retraces nothing (schedules alone may
    rebuild — they are per-tensor data)."""
    spec = tucker.TuckerSpec(shape=(20, 16, 12), ranks=(3, 3, 2),
                             method="gram", engine=engine, n_iter=3)
    p = tucker.plan(spec)
    coo = random_sparse_tensor(spec.shape, 0.05, seed=51)
    warm = p(coo)  # may trace + build schedules
    traces = _total_traces()
    builds = p.engine.schedule_builds
    res = p(coo)
    assert _total_traces() == traces, "same-tensor call retraced the pipeline"
    assert p.engine.schedule_builds == builds, "same-tensor call rebuilt schedules"
    assert res.retraces == 0 and res.schedule_builds == 0
    np.testing.assert_array_equal(res.fit_history, warm.fit_history)
    # a different tensor of the same shape: zero retraces (the compile cache
    # is keyed on the spec, not the tensor)
    coo_b = random_sparse_tensor(spec.shape, 0.05, seed=52)
    res_b = p(coo_b)
    assert _total_traces() == traces
    assert res_b.retraces == 0
    if engine == "xla":  # plain XLA needs no schedules at all
        assert res_b.schedule_builds == 0


def test_plan_reuse_kron_schedules_cached():
    """Kron-reuse dedup plans are per-tensor schedules too: cached on the
    plan's engine, rebuilt only when the tensor changes."""
    spec = tucker.TuckerSpec(shape=(16, 14, 12), ranks=(3, 3, 2),
                             method="gram", engine="xla", n_iter=2,
                             use_kron_reuse=True)
    p = tucker.plan(spec)
    coo = random_sparse_tensor(spec.shape, 0.06, seed=53)
    first = p(coo)
    assert first.schedule_builds > 0  # dedup plan built + uploaded once
    res = p(coo)
    assert res.schedule_builds == 0 and res.retraces == 0


def test_rebound_engine_does_not_pin_old_tensor():
    """Satellite regression: after rebinding to a new tensor, the engine must
    not keep the previous tensor's indices (and device buffer) alive."""
    eng = E.make_engine("pallas") if "pallas" in ENGINES else E.make_engine("xla")
    coo_a = random_sparse_tensor((20, 16, 12), 0.05, seed=47)
    fs = [jnp.zeros((s, 3), jnp.float32) for s in coo_a.shape]
    if eng.name == "pallas":
        eng.mode_unfolding(coo_a, fs, 0)
    else:
        eng.device_schedule(coo_a, 0)
    ref = weakref.ref(coo_a.indices)
    del coo_a, fs
    gc.collect()
    assert ref() is None, "engine pinned the rebound-away tensor's indices"
    # and the engine still works on a fresh tensor after the referent died
    coo_b = random_sparse_tensor((20, 16, 12), 0.05, seed=48)
    fs_b = [jnp.zeros((s, 3), jnp.float32) for s in coo_b.shape]
    out = eng.mode_unfolding(coo_b, fs_b, 0)
    assert np.asarray(out).shape == (20, 9)


# ---------------------------------------------------------------------------
# 6. The nonzeros are ordered once per program call: on the pallas engine
#    the gathers of ``indices`` and ``values`` into each mode's schedule
#    order run before the sweep loop, which reads only their results.
# ---------------------------------------------------------------------------


def _pallas_program_jaxpr(shape, ranks, program, fuse_core=False):
    coo = random_sparse_tensor(shape, 0.08, seed=61)
    eng = E.make_engine("pallas", interpret=True)
    scheds = tuple(eng.device_schedule(coo, m) for m in range(len(shape)))
    fs = tuple(jnp.ones((s, r), jnp.float32) for s, r in zip(shape, ranks))
    statics = dict(shape=tuple(coo.shape), ranks=ranks, method="householder",
                   engine_name="pallas", interpret=True, use_reuse=False,
                   fuse_core=fuse_core)
    one, zero = jnp.float32(1), jnp.float32(0)
    if program == "scan":
        traced = hooi._scan_sweeps.trace(coo.indices, coo.values, fs, one, zero, scheds,
                                         n_iter=3, **statics)
    else:
        traced = hooi._segment_scan_sweeps.trace(
            coo.indices, coo.values, fs, jnp.zeros(ranks, jnp.float32), one, zero,
            jnp.float32(np.inf), jnp.asarray(False), jnp.int32(0), jnp.int32(3), scheds,
            segment_len=3, **statics)
    return traced.jaxpr.jaxpr


@pytest.mark.parametrize("shape,ranks,program,fuse_core", [
    ((12, 10, 8), (3, 3, 2), "scan", False),
    ((12, 10, 8), (3, 3, 2), "segment", False),
    ((10, 9, 8, 7), (3, 2, 2, 2), "scan", False),
    ((12, 10, 8), (3, 3, 2), "scan", True),
])
def test_order_gathers_run_once_before_the_sweeps(shape, ranks, program, fuse_core):
    jaxpr = _pallas_program_jaxpr(shape, ranks, program, fuse_core)
    indices, values = jaxpr.invars[:2]
    (loop,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    # the sweep loop is not handed the tensor, so no gather in its body can
    # read it; it reads the sorted operands the program made before it
    assert indices not in loop.invars and values not in loop.invars
    for arg in (indices, values):
        readers = [e.primitive.name for e in jaxpr.eqns if arg in e.invars]
        # one order gather per mode, fused core or not, at any order
        assert readers == ["gather"] * len(shape)


def test_pallas_program_reads_each_calls_values():
    """The sorted values are made from the values of the call: a re-fit of
    the same nonzeros with new counts on a warm plan gives what a fresh plan
    gives on them."""
    from repro.core.coo import SparseCOO

    spec = tucker.TuckerSpec(shape=(14, 12, 10), ranks=(3, 2, 2), method="householder",
                             engine="pallas", n_iter=3)
    coo = random_sparse_tensor(spec.shape, 0.1, seed=62)
    warm = tucker.plan(spec)
    warm(coo)
    counts = jnp.asarray(np.random.default_rng(63).poisson(3.0, coo.nnz) + 1, jnp.float32)
    res = warm(SparseCOO(coo.indices, counts, spec.shape))
    assert res.schedule_builds == 0  # the same nonzeros: schedules reused
    fresh = tucker.plan(spec)(SparseCOO(jnp.array(coo.indices), counts, spec.shape))
    np.testing.assert_array_equal(res.fit_history, fresh.fit_history)
    np.testing.assert_array_equal(np.asarray(res.core), np.asarray(fresh.core))


@pytest.mark.parametrize("run", ["segment2", "segment5"])
def test_pallas_pipelines_give_the_same_decomposition(run, tmp_path):
    """The scan program and the snapshot segment program run the same order
    gathers and kernels on one tensor."""
    kw = dict(shape=(14, 12, 10), ranks=(3, 2, 2), method="householder", engine="pallas",
              n_iter=5, tol=0.0)
    coo = random_sparse_tensor(kw["shape"], 0.1, seed=64)
    want = tucker.plan(tucker.TuckerSpec(**kw))(coo)
    every = int(run[len("segment"):])
    spec = tucker.TuckerSpec(snapshot=tucker.SnapshotSpec(
        every_n_sweeps=every, directory=str(tmp_path)), **kw)
    got = tucker.plan(spec)(coo)
    np.testing.assert_array_equal(np.asarray(got.core), np.asarray(want.core))
    for a, b in zip(got.factors, want.factors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(got.fit_history, want.fit_history)


@pytest.mark.parametrize("fuse_core", [False, True])
def test_pallas_program_on_an_empty_tensor(fuse_core):
    """With no nonzeros there is nothing to order: every unfolding the
    program builds is the zero unfolding, so the core is zero."""
    from repro.core.coo import SparseCOO

    coo = SparseCOO.from_parts(np.zeros((0, 3), np.int32), np.zeros((0,), np.float32),
                               (10, 8, 6))
    p = tucker.plan(tucker.TuckerSpec(shape=coo.shape, ranks=(3, 3, 2), engine="pallas",
                                      n_iter=2))
    p.engine.fuse_core = fuse_core
    res = p(coo)
    assert res.core.shape == (3, 3, 2) and not np.asarray(res.core).any()
