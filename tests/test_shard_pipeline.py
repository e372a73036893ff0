"""Differential harness for the sharded sweep pipeline (TuckerSpec.shard).

The contract: a spec with ``shard=ShardSpec(num_devices=d)`` compiles ONE
shard_map-wrapped scan program whose results match the single-device pipeline
to fp tolerance (the only divergence is psum reduction order), across device
counts, QRP methods and ragged (non-divisible) nnz — and its steady state is
the same as the single-device pipeline's: one dispatch per decompose, zero
retraces when only nnz values change, plan-cache hit on an identical mesh.

Multi-device coverage runs in ONE subprocess under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the main test process
keeps the real 1-device backend); the whole differential matrix is computed
there once and asserted here from its JSON report.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

DEVICE_COUNTS = (1, 2, 4)
METHODS = ("svd", "gram")
# ragged on purpose: 397 is odd and divides by neither 2 nor 4, so every
# multi-device case exercises the shard padding path.
RAGGED_NNZ = 397

_MATRIX_SCRIPT = """
    import json, numpy as np, jax
    from repro import tucker
    from repro.core import hooi
    from repro.core.coo import SparseCOO
    from repro.sparse.generators import random_sparse_tensor

    SHAPE, RANKS, N_ITER = (18, 15, 12), (3, 2, 2), 3
    DEVICE_COUNTS, METHODS, RAGGED_NNZ = %(devices)r, %(methods)r, %(nnz)d

    full = random_sparse_tensor(SHAPE, 0.25, seed=11)
    assert full.nnz >= RAGGED_NNZ
    coo = SparseCOO(full.indices[:RAGGED_NNZ], full.values[:RAGGED_NNZ], SHAPE)

    out = {"n_devices": len(jax.devices()), "cases": []}
    refs = {}
    for method in METHODS:
        spec = tucker.TuckerSpec(shape=SHAPE, ranks=RANKS, method=method,
                                 engine="xla", n_iter=N_ITER)
        refs[method] = tucker.plan(spec)(coo)

    for d in DEVICE_COUNTS:
        for method in METHODS:
            spec = tucker.TuckerSpec(
                shape=SHAPE, ranks=RANKS, method=method, n_iter=N_ITER,
                shard=tucker.ShardSpec(num_devices=d))
            plan = tucker.plan(spec)
            res = plan(coo)
            ref = refs[method]
            out["cases"].append({
                "devices": d, "method": method,
                "fit_maxdiff": float(np.abs(res.fit_history - ref.fit_history).max()),
                "core_maxdiff": float(np.abs(np.asarray(res.core)
                                             - np.asarray(ref.core)).max()),
                "factor_maxdiff": float(max(
                    np.abs(np.asarray(a) - np.asarray(b)).max()
                    for a, b in zip(res.factors, ref.factors))),
                "n_sweeps": res.n_sweeps,
                "dispatches": res.dispatches,
                "retraces": res.retraces,
                "collective_bytes_per_sweep": res.collective_bytes_per_sweep,
                "shard_imbalance": res.shard_imbalance,
                "cache_hit_on_replan": tucker.plan(spec) is plan,
            })

    # -- no-retrace when only nnz values change (same indices object) -------
    spec = tucker.TuckerSpec(shape=SHAPE, ranks=RANKS, method="gram",
                             n_iter=N_ITER, shard=tucker.ShardSpec(num_devices=4))
    plan = tucker.plan(spec)
    base = plan(coo)
    scaled = SparseCOO(coo.indices, coo.values * 1.7, SHAPE)
    t0 = sum(hooi.SWEEP_TRACE_COUNTS.values())
    d0 = hooi.SWEEP_DISPATCH_COUNTS[("sharded", "scan")]
    res = plan(scaled)
    out["value_change"] = {
        "retraces": sum(hooi.SWEEP_TRACE_COUNTS.values()) - t0,
        "dispatches": hooi.SWEEP_DISPATCH_COUNTS[("sharded", "scan")] - d0,
        # the decomposition is scale-equivariant: core(1.7 X) == 1.7 core(X).
        # A stale cached ShardSchedule (old values) would break this.
        "core_scaling_maxdiff": float(np.abs(
            np.asarray(res.core) - 1.7 * np.asarray(base.core)).max()),
    }

    # -- bucket-padded call: program shape stable, imbalance still honest ----
    spec_pad = tucker.TuckerSpec(shape=SHAPE, ranks=RANKS, method="gram",
                                 n_iter=N_ITER,
                                 shard=tucker.ShardSpec(num_devices=4))
    plan_pad = tucker.plan(spec_pad)
    r1 = plan_pad(coo, pad_nnz_to=1024)
    t0 = sum(hooi.SWEEP_TRACE_COUNTS.values())
    smaller = SparseCOO(coo.indices[:RAGGED_NNZ - 60],
                        coo.values[:RAGGED_NNZ - 60], SHAPE)
    r2 = plan_pad(smaller, pad_nnz_to=1024)
    out["bucket_pad"] = {
        "retraces": sum(hooi.SWEEP_TRACE_COUNTS.values()) - t0,
        # 397 real nnz over 4 shards of 256 slots: some shard is all padding
        "imbalance_r1": r1.shard_imbalance,
        "fit_maxdiff_vs_unpadded": float(np.abs(
            r1.fit_history - refs["gram"].fit_history).max()),
    }

    # -- tol early-exit parity on the sharded program ------------------------
    tol = 1e-3
    a = tucker.plan(tucker.TuckerSpec(shape=SHAPE, ranks=RANKS, method="gram",
                                      engine="xla", n_iter=10, tol=tol))(coo)
    b = tucker.plan(tucker.TuckerSpec(
        shape=SHAPE, ranks=RANKS, method="gram", n_iter=10, tol=tol,
        shard=tucker.ShardSpec(num_devices=4)))(coo)
    out["tol"] = {"single_sweeps": a.n_sweeps, "sharded_sweeps": b.n_sweeps,
                  "fit_maxdiff": float(np.abs(a.fit_history
                                              - b.fit_history).max())}
    print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def matrix():
    """Run the whole differential matrix once, in one 4-device subprocess."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = textwrap.dedent(_MATRIX_SCRIPT % {
        "devices": DEVICE_COUNTS, "methods": METHODS, "nnz": RAGGED_NNZ,
    })
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout)


@pytest.mark.slow
def test_forced_host_device_count(matrix):
    assert matrix["n_devices"] == 4


@pytest.mark.slow
@pytest.mark.parametrize("devices", DEVICE_COUNTS)
@pytest.mark.parametrize("method", METHODS)
def test_sharded_matches_single_device(matrix, devices, method):
    """Factors/core/fit parity with the single-device pipeline across
    device counts x methods on ragged nnz (the tentpole acceptance gate)."""
    case = next(c for c in matrix["cases"]
                if c["devices"] == devices and c["method"] == method)
    assert case["fit_maxdiff"] < 1e-5
    assert case["core_maxdiff"] < 5e-4
    assert case["factor_maxdiff"] < 5e-4
    assert case["n_sweeps"] == 3


@pytest.mark.slow
@pytest.mark.parametrize("devices", DEVICE_COUNTS)
def test_sharded_single_dispatch_and_counters(matrix, devices):
    """One XLA dispatch per decompose, psum bytes independent of the device
    count, imbalance only when the shard count does not divide the nnz."""
    cases = [c for c in matrix["cases"] if c["devices"] == devices]
    for c in cases:
        assert c["dispatches"] == 1
        # N psums of I_n x prod_{t != n} R_t f32: 18*4 + 15*6 + 12*6 rows...
        # computed once here from shape/ranks rather than trusted from repro
        shape, ranks = (18, 15, 12), (3, 2, 2)
        want = sum(
            dim * int(np.prod([r for t, r in enumerate(ranks) if t != m])) * 4
            for m, dim in enumerate(shape)
        )
        assert c["collective_bytes_per_sweep"] == want
        if RAGGED_NNZ % devices == 0:
            assert c["shard_imbalance"] == 0.0
        else:
            assert 0.0 < c["shard_imbalance"] < 0.2


@pytest.mark.slow
def test_replan_identical_mesh_is_cache_hit(matrix):
    assert all(c["cache_hit_on_replan"] for c in matrix["cases"])


@pytest.mark.slow
def test_no_retrace_when_only_values_change(matrix):
    """Same indices, new values: zero new traces, one dispatch — and the
    rebuilt shard schedule really carries the NEW values (scale test)."""
    vc = matrix["value_change"]
    assert vc["retraces"] == 0
    assert vc["dispatches"] == 1
    assert vc["core_scaling_maxdiff"] < 5e-4


@pytest.mark.slow
def test_bucket_padded_calls_share_program_with_honest_imbalance(matrix):
    """pad_nnz_to stabilizes the shard_map program shape across mixed-nnz
    calls (zero retraces) without changing results — and the imbalance
    counter keeps describing the REAL nonzeros, not the padding."""
    bp = matrix["bucket_pad"]
    assert bp["retraces"] == 0
    assert bp["fit_maxdiff_vs_unpadded"] < 1e-5
    # 397 real nnz across 4 shards of 256 padded slots each: the last shard
    # holds no real nonzeros at all -> imbalance 1.0 (a pre-padded tensor
    # would have mis-reported 0.0 here)
    assert bp["imbalance_r1"] == 1.0


@pytest.mark.slow
def test_tol_early_exit_parity_sharded(matrix):
    t = matrix["tol"]
    assert t["sharded_sweeps"] == t["single_sweeps"] < 10
    assert t["fit_maxdiff"] < 1e-5


# ---------------------------------------------------------------------------
# In-process coverage (1 real device is enough): spec validation, the
# shard_nonzeros axis-name fix, and the mesh capacity error.
# ---------------------------------------------------------------------------


def test_shard_spec_validation():
    from repro import tucker

    with pytest.raises(ValueError, match="num_devices"):
        tucker.ShardSpec(num_devices=0)
    with pytest.raises(ValueError, match="axis"):
        tucker.ShardSpec(num_devices=1, axis="")
    with pytest.raises(ValueError, match="factor_policy"):
        tucker.ShardSpec(num_devices=1, factor_policy="sharded")


def test_tucker_spec_shard_constraints():
    from repro import tucker

    shard = tucker.ShardSpec(num_devices=1)
    kw = dict(shape=(8, 8, 8), ranks=(2, 2, 2), shard=shard)
    with pytest.raises(ValueError, match="XLA engine"):
        tucker.TuckerSpec(engine="pallas", **kw)
    with pytest.raises(ValueError, match="kron_reuse"):
        tucker.TuckerSpec(use_kron_reuse=True, **kw)
    with pytest.raises(ValueError, match="sparse"):
        tucker.TuckerSpec(algorithm="dense", **kw)
    # a sharded spec never vmap-batches: its one program spans the mesh
    spec = tucker.TuckerSpec(**kw)
    assert not spec.supports_batched_dispatch


def test_shard_nonzeros_rejects_unknown_axis():
    """Satellite regression: a missing nnz-axis name must be a clear
    ValueError up front, not an opaque KeyError deep in device_put."""
    from repro.core.distributed import shard_nonzeros
    from repro.sparse.generators import random_sparse_tensor
    import jax
    from jax.sharding import AxisType

    coo = random_sparse_tensor((6, 5, 4), 0.2, seed=0)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    with pytest.raises(ValueError, match="bogus.*not mesh axes|not mesh axes"):
        shard_nonzeros(coo, mesh, ("bogus",))
    with pytest.raises(ValueError, match="at least one"):
        shard_nonzeros(coo, mesh, ())
    # the happy path still pads + shards
    sharded = shard_nonzeros(coo, mesh, ("data",))
    assert sharded.nnz >= coo.nnz


def test_mesh_for_shard_capacity_error_names_the_recipe():
    """Asking for more devices than attached must point at the forced-host
    -device-count recipe instead of failing inside mesh construction."""
    import jax

    from repro import tucker

    too_many = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="xla_force_host_platform_device_count"):
        tucker.mesh_for_shard(tucker.ShardSpec(num_devices=too_many))


def test_mesh_fingerprint_distinguishes_layouts():
    from repro import tucker
    import jax
    from jax.sharding import AxisType

    m1 = jax.make_mesh((1,), ("nnz",), axis_types=(AxisType.Auto,))
    m2 = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    assert tucker.mesh_fingerprint(m1) != tucker.mesh_fingerprint(m2)
    assert tucker.mesh_fingerprint(m1) == tucker.mesh_fingerprint(
        jax.make_mesh((1,), ("nnz",), axis_types=(AxisType.Auto,))
    )


def test_shard_schedule_counters_are_pure_math():
    """shard_counts / imbalance are host-side math over (nnz, nnz_padded,
    n_shards) — unit-checked here without any device mesh."""
    from repro.sparse.layout import ShardSchedule

    s = ShardSchedule(indices=None, values=None, mesh=None, nnz_axes=("nnz",),
                      n_shards=4, nnz=5, nnz_padded=8)
    assert list(s.shard_counts) == [2, 2, 1, 0]
    assert s.imbalance == 1.0  # one shard is all padding
    even = ShardSchedule(indices=None, values=None, mesh=None,
                         nnz_axes=("nnz",), n_shards=4, nnz=8, nnz_padded=8)
    assert even.imbalance == 0.0


def test_build_shard_schedule_target_keeps_real_nnz():
    """A raised pad floor (serving bucket) must not masquerade as real
    nonzeros in the schedule's counters."""
    from repro.sparse.generators import random_sparse_tensor
    from repro.sparse.layout import build_shard_schedule
    import jax
    from jax.sharding import AxisType

    coo = random_sparse_tensor((6, 5, 4), 0.2, seed=1)
    mesh = jax.make_mesh((1,), ("nnz",), axis_types=(AxisType.Auto,))
    sched = build_shard_schedule(coo, mesh, ("nnz",), target_nnz=64)
    assert sched.nnz == coo.nnz  # real, not the padded 64
    assert sched.nnz_padded == 64
    assert int(sched.shard_counts.sum()) == coo.nnz


def test_sharded_plan_single_device_inprocess():
    """ShardSpec(num_devices=1) runs in the main process (a 1-device mesh is
    still the full shard_map program) and matches the plain pipeline."""
    from repro import tucker
    from repro.sparse.generators import random_sparse_tensor

    coo = random_sparse_tensor((10, 9, 8), 0.1, seed=3)
    ref = tucker.decompose(coo, (2, 2, 2), method="gram", engine="xla", n_iter=2)
    spec = tucker.TuckerSpec(shape=coo.shape, ranks=(2, 2, 2), method="gram",
                             n_iter=2, shard=tucker.ShardSpec(num_devices=1))
    res = tucker.plan(spec)(coo)
    np.testing.assert_allclose(res.fit_history, ref.fit_history, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res.core), np.asarray(ref.core),
                               rtol=1e-4, atol=1e-4)
    assert res.dispatches == 1
    assert res.collective_bytes_per_sweep is not None
    assert res.shard_imbalance == 0.0


@pytest.mark.parametrize("snapshot", [False, True])
def test_collective_bytes_counter_counts_sweeps_run(tmp_path, snapshot):
    """``repro_collective_bytes_total`` grows by the psum payload of a sweep
    times the sweeps run, on the plain sharded program and on the sharded
    snapshot segments alike (one device: the same shard_map program)."""
    from repro import obs, tucker
    from repro.core.distributed import psum_bytes_per_sweep
    from repro.sparse.generators import random_sparse_tensor

    coo = random_sparse_tensor((10, 9, 8), 0.1, seed=3)
    snap = (tucker.SnapshotSpec(every_n_sweeps=2, directory=str(tmp_path))
            if snapshot else None)
    spec = tucker.TuckerSpec(shape=coo.shape, ranks=(2, 2, 2), method="gram",
                             n_iter=3, shard=tucker.ShardSpec(num_devices=1),
                             snapshot=snap)
    counter = obs.registry.counter("repro_collective_bytes_total")
    before = counter.value
    res = tucker.plan(spec)(coo)
    assert res.n_sweeps == 3
    want = psum_bytes_per_sweep(coo.shape, (2, 2, 2)) * 3
    assert res.collective_bytes_per_sweep * 3 == want
    assert counter.value - before == want
    assert "repro_collective_bytes_total" in obs.registry.render_prometheus()
