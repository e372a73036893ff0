"""Differential harness for fault-tolerant resumable sweeps (TuckerSpec.snapshot).

The contract: a spec with ``snapshot=SnapshotSpec(every_n_sweeps=k, ...)``
runs the SAME per-sweep math as the uninterrupted pipeline in k-sweep
segments, spilling the carry to an atomic checkpoint after each — so killing
the job at any segment boundary and resuming (``tucker.resume``) produces
final factors/core/fit bit-compatible with a run that was never interrupted.
One compiled segment program serves every segment and resume offset (the
no-retrace contract), transient dispatch failures retry in place, and a
sharded job resumes elastically onto a DIFFERENT device count: the carry is
replicated, only the plan re-shards.

Multi-device coverage runs in subprocesses under
``XLA_FLAGS=--xla_force_host_platform_device_count={4,2}`` (the main test
process keeps the real 1-device backend): one 4-device process kills and
resumes a sharded job, leaving a second job dead mid-fit; a separate
2-device process then resumes that orphan — a genuine cross-device-count
restart, asserted here from the JSON reports.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

SHAPE, RANKS, N_ITER, EVERY = (14, 12, 10), (3, 2, 2), 12, 5
KILL_AT = 5  # a segment boundary: the step-5 snapshot exists when it fires


def _coo():
    from repro.core.coo import SparseCOO
    from repro.sparse.generators import random_sparse_tensor

    full = random_sparse_tensor(SHAPE, 0.25, seed=11)
    # ragged on purpose (neither 2 nor 4 divides it): the sharded resume
    # cases below re-pad the same nonzeros for every mesh size.
    return SparseCOO(full.indices[:397], full.values[:397], SHAPE)


def _spec(tmp_path, *, tol=0.0, engine="xla", every=EVERY, n_iter=N_ITER,
          **snap_kw):
    from repro import tucker

    return tucker.TuckerSpec(
        shape=SHAPE, ranks=RANKS, method="gram", engine=engine,
        n_iter=n_iter, tol=tol,
        snapshot=tucker.SnapshotSpec(
            every_n_sweeps=every, directory=str(tmp_path), **snap_kw
        ),
    )


# ---------------------------------------------------------------------------
# Spec surface
# ---------------------------------------------------------------------------


def test_snapshot_spec_validation():
    from repro import tucker

    with pytest.raises(ValueError, match="every_n_sweeps"):
        tucker.SnapshotSpec(every_n_sweeps=0, directory="d")
    with pytest.raises(ValueError, match="directory"):
        tucker.SnapshotSpec(every_n_sweeps=1, directory="")
    with pytest.raises(ValueError, match="keep"):
        tucker.SnapshotSpec(every_n_sweeps=1, directory="d", keep=0)
    with pytest.raises(ValueError, match="max_retries"):
        tucker.SnapshotSpec(every_n_sweeps=1, directory="d", max_retries=-1)


def test_snapshot_spec_wall_clock_cadence_validation():
    from repro import tucker

    with pytest.raises(ValueError, match="cadence"):
        tucker.SnapshotSpec(directory="d")  # neither cadence set
    with pytest.raises(ValueError, match="every_seconds"):
        tucker.SnapshotSpec(every_seconds=-1.0, directory="d")
    with pytest.raises(ValueError, match="every_seconds"):
        tucker.SnapshotSpec(every_seconds=float("nan"), directory="d")
    # wall-clock-only cadence: segments fall back to single sweeps
    snap = tucker.SnapshotSpec(every_seconds=30.0, directory="d")
    assert snap.every_n_sweeps is None and snap.segment_len == 1
    # both cadences compose
    both = tucker.SnapshotSpec(every_n_sweeps=3, every_seconds=1.5,
                               directory="d")
    assert both.segment_len == 3 and both.every_seconds == 1.5


def test_wall_clock_cadence_gates_interval_spills(tmp_path):
    """every_seconds gates the per-boundary writes: a huge interval writes
    only the initial and final snapshots; interval 0.0 writes every
    boundary. The final state is identical either way — the cadence only
    decides which intermediate boundaries spill."""
    from repro import tucker

    def run(sub, **snap_kw):
        spec = tucker.TuckerSpec(
            shape=SHAPE, ranks=RANKS, method="gram", engine="xla",
            n_iter=4, tol=0.0,
            snapshot=tucker.SnapshotSpec(
                directory=str(tmp_path / sub), **snap_kw
            ),
        )
        return tucker.plan(spec)(_coo())

    sparse_res = run("sparse", every_n_sweeps=1, every_seconds=1e9)
    assert sparse_res.n_sweeps == 4
    assert sparse_res.snapshots_written == 2  # step-0 initial + final only

    dense_res = run("dense", every_n_sweeps=1, every_seconds=0.0)
    assert dense_res.snapshots_written == 5  # initial + all 4 boundaries
    np.testing.assert_allclose(
        sparse_res.fit_history, dense_res.fit_history, atol=1e-6
    )

    # the final snapshot is a valid resume point even when every
    # intermediate boundary was skipped
    state = tucker.load_snapshot(str(tmp_path / "sparse"))
    assert state.sweeps_done == 4
    assert state.meta["spec"]["every_seconds"] == 1e9


def test_wall_clock_skip_decisions_traced(tmp_path):
    """Skipped boundaries surface as snapshot.skip events and spills carry
    their decision ('initial'/'wall-clock'/'final') as a span attribute."""
    import repro.obs as obs
    from repro import tucker

    obs.configure(enabled=True)
    try:
        spec = tucker.TuckerSpec(
            shape=SHAPE, ranks=RANKS, method="gram", engine="xla",
            n_iter=3, tol=0.0,
            snapshot=tucker.SnapshotSpec(
                every_n_sweeps=1, every_seconds=1e9,
                directory=str(tmp_path),
            ),
        )
        tucker.plan(spec)(_coo())
        evs = obs.tracer.events()
        spills = [e for e in evs if e.name == "snapshot.spill"]
        skips = [e for e in evs if e.name == "snapshot.skip"]
        assert [s.attrs["decision"] for s in spills] == ["initial", "final"]
        assert len(skips) == 2  # boundaries 1 and 2 skipped
        assert all(s.attrs["decision"] == "wall-clock" for s in skips)
    finally:
        obs.configure(enabled=False)


def test_tucker_spec_snapshot_constraints(tmp_path):
    from repro import tucker

    snap = tucker.SnapshotSpec(every_n_sweeps=2, directory=str(tmp_path))
    kw = dict(shape=SHAPE, ranks=RANKS, snapshot=snap)
    with pytest.raises(ValueError, match="sparse"):
        tucker.TuckerSpec(algorithm="dense", **kw)
    # a snapshot job is one long-running fit: never vmap-batched
    spec = tucker.TuckerSpec(**kw)
    assert not spec.supports_batched_dispatch


def test_batch_rejects_snapshot_spec(tmp_path):
    from repro import tucker

    plan = tucker.plan(_spec(tmp_path))
    with pytest.raises(ValueError, match="checkpoint directory"):
        plan.batch([_coo(), _coo()])


def test_service_rejects_snapshot_spec(tmp_path):
    from repro.serve import ServiceConfig, TuckerService

    coo = _coo()
    with TuckerService(ServiceConfig(max_batch=2)) as svc:
        with pytest.raises(ValueError, match="snapshot"):
            svc.submit_coo(coo, _spec(tmp_path))


def test_resume_requires_snapshot_spec():
    from repro import tucker

    spec = tucker.TuckerSpec(shape=SHAPE, ranks=RANKS)
    with pytest.raises(ValueError, match="SnapshotSpec"):
        tucker.resume(spec, _coo())


# ---------------------------------------------------------------------------
# Single-device differential matrix: {xla, pallas} x {fresh, kill+resume}
# ---------------------------------------------------------------------------

ENGINES = ("xla", "pallas")  # pallas resolves to interpret mode off-TPU


def _baseline(engine):
    """Uninterrupted run of the same problem WITHOUT a snapshot spec."""
    from repro import tucker

    spec = tucker.TuckerSpec(shape=SHAPE, ranks=RANKS, method="gram",
                             engine=engine, n_iter=N_ITER, tol=0.0)
    return tucker.plan(spec)(_coo())


def _assert_parity(res, ref, atol=1e-5):
    np.testing.assert_allclose(res.fit_history, ref.fit_history, atol=atol)
    np.testing.assert_allclose(
        np.asarray(res.core), np.asarray(ref.core), atol=atol
    )
    for a, b in zip(res.factors, ref.factors):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)


@pytest.mark.parametrize("engine", ENGINES)
def test_snapshot_run_matches_uninterrupted(tmp_path, engine):
    """Fresh snapshot run: segmented execution is bit-compatible with the
    unsegmented pipeline, 12 sweeps at every=5 -> 3 segments, 4 snapshots
    (step 0 included)."""
    from repro import tucker

    res = tucker.plan(_spec(tmp_path, engine=engine))(_coo())
    _assert_parity(res, _baseline(engine))
    assert res.dispatches == 3  # ceil(12 / 5)
    assert res.snapshots_written == 4  # steps 0, 5, 10, 12
    assert res.resumed_from_sweep is None
    assert res.retries == 0


@pytest.mark.parametrize("engine", ENGINES)
def test_kill_and_resume_matches_uninterrupted(tmp_path, engine):
    """The tentpole gate: kill at sweep KILL_AT, resume from the snapshot,
    final state matches the run that was never interrupted — and the resume
    reuses the already-compiled segment program (zero retraces)."""
    from repro import tucker
    from repro.runtime.fault_tolerance import FailureInjector

    spec = _spec(tmp_path, engine=engine)
    coo = _coo()
    inj = FailureInjector(fail_at=[KILL_AT])
    with pytest.raises(RuntimeError, match="injected failure"):
        tucker.plan(spec)(coo, injector=inj)

    res = tucker.resume(spec, coo)
    _assert_parity(res, _baseline(engine))
    assert res.resumed_from_sweep == KILL_AT
    assert res.dispatches == 2  # sweeps 5..10, 10..12
    assert res.retraces == 0  # the killed run's program serves the resume
    assert res.n_sweeps == N_ITER


def test_retry_in_place(tmp_path):
    """max_retries > 0: a transient segment failure retries without dying
    (the one-shot injector fires once), the job completes with full parity
    and the retry surfaces on the result."""
    from repro import tucker
    from repro.runtime.fault_tolerance import FailureInjector

    spec = _spec(tmp_path, max_retries=2, retry_backoff_s=0.0)
    inj = FailureInjector(fail_at=[KILL_AT])
    res = tucker.plan(spec)(_coo(), injector=inj)
    _assert_parity(res, _baseline("xla"))
    assert res.retries == 1


def test_kron_reuse_snapshot_parity(tmp_path):
    """The Kron-reuse dedup engine rides the same segment skeleton."""
    from repro import tucker

    coo = _coo()
    ref_spec = tucker.TuckerSpec(shape=SHAPE, ranks=RANKS, method="gram",
                                 engine="xla", n_iter=N_ITER, tol=0.0,
                                 use_kron_reuse=True)
    ref = tucker.plan(ref_spec)(coo)
    spec = tucker.TuckerSpec(
        shape=SHAPE, ranks=RANKS, method="gram", engine="xla",
        n_iter=N_ITER, tol=0.0, use_kron_reuse=True,
        snapshot=tucker.SnapshotSpec(every_n_sweeps=EVERY,
                                     directory=str(tmp_path)),
    )
    _assert_parity(tucker.plan(spec)(coo), ref)


def test_tol_early_exit_with_snapshots(tmp_path):
    """The dynamic-tol early exit fires identically under segmenting, and
    segments after convergence never dispatch."""
    from repro import tucker

    coo = _coo()
    tol = 1e-3
    ref = tucker.plan(
        tucker.TuckerSpec(shape=SHAPE, ranks=RANKS, method="gram",
                          engine="xla", n_iter=N_ITER, tol=tol)
    )(coo)
    res = tucker.plan(_spec(tmp_path, tol=tol, every=2))(coo)
    assert res.n_sweeps == ref.n_sweeps < N_ITER
    np.testing.assert_allclose(res.fit_history, ref.fit_history, atol=1e-6)
    # the loop stopped at the converged segment, not the sweep budget
    assert res.dispatches == -(-ref.n_sweeps // 2)


def test_resume_of_completed_job_is_a_noop(tmp_path):
    """Resuming a finished job returns its final state with zero dispatches
    (and writes no new snapshots)."""
    from repro import tucker

    spec = _spec(tmp_path)
    coo = _coo()
    done = tucker.plan(spec)(coo)
    res = tucker.resume(spec, coo)
    _assert_parity(res, done, atol=0.0)
    assert res.dispatches == 0
    assert res.snapshots_written == 0
    assert res.resumed_from_sweep == N_ITER


def test_resume_rejects_mismatched_problem(tmp_path):
    """A snapshot only resumes the problem it came from: changed ranks (or
    shape/method) must be a clear error, not silently wrong math."""
    import dataclasses

    from repro import tucker

    spec = _spec(tmp_path)
    coo = _coo()
    tucker.plan(spec)(coo)
    other = dataclasses.replace(spec, ranks=(2, 2, 2))
    with pytest.raises(ValueError, match="ranks"):
        tucker.resume(other, coo)
    with pytest.raises(ValueError, match="method"):
        tucker.resume(dataclasses.replace(spec, method="svd"), coo)


def test_resume_with_no_checkpoint_raises(tmp_path):
    from repro import tucker

    with pytest.raises(FileNotFoundError):
        tucker.resume(_spec(tmp_path / "nothing-here"), _coo())


def test_crash_mid_save_leaves_resumable_state(tmp_path):
    """A stale tmp dir from a crashed save neither blocks nor corrupts a
    resume: the manager sweeps it and the latest COMPLETE snapshot wins."""
    from repro import tucker
    from repro.runtime.fault_tolerance import FailureInjector

    spec = _spec(tmp_path)
    coo = _coo()
    inj = FailureInjector(fail_at=[KILL_AT])
    with pytest.raises(RuntimeError):
        tucker.plan(spec)(coo, injector=inj)
    # simulate a crash mid-save: a torn tmp dir next to the good snapshots
    torn = tmp_path / "step_00000007.tmp"
    torn.mkdir()
    (torn / "shard_00000.npz").write_bytes(b"not an npz")
    res = tucker.resume(spec, coo)
    _assert_parity(res, _baseline("xla"))
    assert not torn.exists()


# ---------------------------------------------------------------------------
# Sharded + elastic matrix (subprocesses; the main process stays 1-device)
# ---------------------------------------------------------------------------

_COMMON = """
    import json, warnings, numpy as np, jax
    from repro import tucker
    from repro.core.coo import SparseCOO
    from repro.runtime.fault_tolerance import FailureInjector
    from repro.sparse.generators import random_sparse_tensor

    SHAPE, RANKS, N_ITER, EVERY, KILL_AT = %(shape)r, %(ranks)r, %(n_iter)d, %(every)d, %(kill)d
    full = random_sparse_tensor(SHAPE, 0.25, seed=11)
    coo = SparseCOO(full.indices[:397], full.values[:397], SHAPE)

    # the reference is deterministic across processes: same seed, same
    # default PRNGKey(0) factor init, single-device XLA pipeline.
    ref = tucker.plan(tucker.TuckerSpec(
        shape=SHAPE, ranks=RANKS, method="gram", engine="xla",
        n_iter=N_ITER, tol=0.0))(coo)

    def parity(res):
        return {
            "fit_maxdiff": float(np.abs(np.asarray(res.fit_history)
                                        - np.asarray(ref.fit_history)).max()),
            "core_maxdiff": float(np.abs(np.asarray(res.core)
                                         - np.asarray(ref.core)).max()),
            "factor_maxdiff": float(max(
                np.abs(np.asarray(a) - np.asarray(b)).max()
                for a, b in zip(res.factors, ref.factors))),
            "n_sweeps": res.n_sweeps,
            "resumed_from": res.resumed_from_sweep,
            "dispatches": res.dispatches,
            "retraces": res.retraces,
        }

    def sharded_spec(directory, n_devices):
        return tucker.TuckerSpec(
            shape=SHAPE, ranks=RANKS, method="gram", n_iter=N_ITER, tol=0.0,
            shard=tucker.ShardSpec(num_devices=n_devices),
            snapshot=tucker.SnapshotSpec(every_n_sweeps=EVERY,
                                         directory=directory))
"""

_SCRIPT_4DEV = _COMMON + """
    out = {"n_devices": len(jax.devices())}

    # job1: kill at a boundary, resume IN PROCESS on the same 4-device mesh
    spec1 = sharded_spec(%(dir1)r, 4)
    inj = FailureInjector(fail_at=[KILL_AT])
    try:
        tucker.plan(spec1)(coo, injector=inj)
        out["job1_killed"] = False
    except RuntimeError:
        out["job1_killed"] = True
    out["resume_4dev"] = parity(tucker.resume(spec1, coo))

    # job2: kill and leave dead — the 2-device process resumes this orphan
    spec2 = sharded_spec(%(dir2)r, 4)
    inj2 = FailureInjector(fail_at=[KILL_AT])
    try:
        tucker.plan(spec2)(coo, injector=inj2)
        out["job2_killed"] = False
    except RuntimeError:
        out["job2_killed"] = True
    print(json.dumps(out))
"""

_SCRIPT_2DEV = _COMMON + """
    out = {"n_devices": len(jax.devices())}
    # the orphaned 4-device job resumes here on 2 devices: the spec still
    # says num_devices=4, resume() clamps it with a warning and the
    # ShardSchedule redistributes over the smaller mesh.
    spec = sharded_spec(%(dir2)r, 4)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        res = tucker.resume(spec, coo)
        out["clamp_warned"] = any("clamping" in str(x.message) for x in w)
    out["resume_2dev"] = parity(res)
    print(json.dumps(out))
"""


def _run_forced(code: str, n_devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout)


@pytest.fixture(scope="module")
def elastic(tmp_path_factory):
    """Kill two sharded jobs on 4 devices; resume one there, the other in a
    fresh 2-device process (the genuine cross-device-count restart)."""
    dir1 = str(tmp_path_factory.mktemp("ckpt-4to4"))
    dir2 = str(tmp_path_factory.mktemp("ckpt-4to2"))
    fmt = {"shape": SHAPE, "ranks": RANKS, "n_iter": N_ITER, "every": EVERY,
           "kill": KILL_AT, "dir1": dir1, "dir2": dir2}
    a = _run_forced(_SCRIPT_4DEV % fmt, 4)
    b = _run_forced(_SCRIPT_2DEV % fmt, 2)
    return {"a": a, "b": b}


@pytest.mark.slow
def test_sharded_kill_resume_same_device_count(elastic):
    a = elastic["a"]
    assert a["n_devices"] == 4
    assert a["job1_killed"] and a["job2_killed"]
    r = a["resume_4dev"]
    assert r["resumed_from"] == KILL_AT
    assert r["n_sweeps"] == N_ITER
    assert r["fit_maxdiff"] < 1e-5
    assert r["core_maxdiff"] < 5e-4
    assert r["factor_maxdiff"] < 5e-4
    # the killed run already compiled the segment program on this mesh
    assert r["retraces"] == 0
    assert r["dispatches"] == 2  # sweeps 5..10, 10..12


@pytest.mark.slow
def test_sharded_resume_on_fewer_devices(elastic):
    """The elastic gate: a job snapshotted by a 4-device mesh finishes on 2
    devices, matching the uninterrupted single-device run — replicated carry
    restores unchanged, nonzeros re-shard, the spec's stale device count is
    clamped with a warning instead of dying."""
    b = elastic["b"]
    assert b["n_devices"] == 2
    assert b["clamp_warned"]
    r = b["resume_2dev"]
    assert r["resumed_from"] == KILL_AT
    assert r["n_sweeps"] == N_ITER
    assert r["fit_maxdiff"] < 1e-5
    assert r["core_maxdiff"] < 5e-4
    assert r["factor_maxdiff"] < 5e-4
