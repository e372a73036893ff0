"""Kernel autotuner contract tests (ISSUE 7 tentpole).

Acceptance criteria under test:

* the tuning-table fingerprint is stable, nnz-bucketed, and sensitive to
  every axis it claims to key on;
* candidate generation always leads with the hand-picked default and never
  emits a config that blows the VMEM budget;
* the on-disk table round-trips atomically and tolerates corruption;
* a COLD ``tucker.plan`` with ``autotune=True`` searches exactly once and a
  WARM plan (fresh process-state plan, same table) pays ZERO searches and
  ZERO trials — the tentpole's headline counter assertion;
* ``TuckerPlan.analyze`` reports the roofline fields the bench suite and CI
  gate consume.
"""
import json

import numpy as np
import pytest

from repro import tucker
from repro.kernels import autotune as at
from repro.sparse.generators import random_sparse_tensor


@pytest.fixture(autouse=True)
def _fresh_counters():
    at.reset_counters()
    yield
    at.reset_counters()


def _cheap_trials(monkeypatch, times=None):
    """Replace the timed trial with a deterministic table lookup so search
    tests stay fast; the counter bump is preserved (it IS the contract)."""
    calls = []

    def fake(cfg, shape, ranks, nnz, **kw):
        at.COUNTERS["trials"] += 1
        calls.append(cfg)
        return (times or {}).get(cfg, 1.0)

    monkeypatch.setattr(at, "trial_time_ms", fake)
    return calls


# ---------------------------------------------------------------------------
# fingerprint + nnz bucketing
# ---------------------------------------------------------------------------


def test_nnz_bucket_powers_of_two():
    assert at.nnz_bucket(1) == 1
    assert at.nnz_bucket(5) == 8
    assert at.nnz_bucket(1024) == 1024
    assert at.nnz_bucket(1025) == 2048
    assert at.nnz_bucket(0) == 1  # degenerate input never crashes


def test_fingerprint_stable_and_sensitive():
    base = dict(dtype="float32", precision="fp32", backend="cpu")
    fp = at.fingerprint((20, 16, 12), (3, 3, 2), 500, **base)
    assert fp == at.fingerprint((20, 16, 12), (3, 3, 2), 500, **base)
    # nnz jitter INSIDE one power-of-2 bucket maps to the same entry...
    assert fp == at.fingerprint((20, 16, 12), (3, 3, 2), 400, **base)
    # ...but every other axis separates entries.
    assert fp != at.fingerprint((20, 16, 12), (3, 3, 2), 5000, **base)
    assert fp != at.fingerprint((20, 16, 13), (3, 3, 2), 500, **base)
    assert fp != at.fingerprint((20, 16, 12), (3, 3, 3), 500, **base)
    assert fp != at.fingerprint(
        (20, 16, 12), (3, 3, 2), 500,
        dtype="float32", precision="bf16_fp32acc", backend="cpu",
    )
    assert fp != at.fingerprint(
        (20, 16, 12), (3, 3, 2), 500,
        dtype="bfloat16", precision="fp32", backend="cpu",
    )


# ---------------------------------------------------------------------------
# candidate generation: prune + ranking
# ---------------------------------------------------------------------------


def test_candidates_default_first_and_vmem_pruned():
    cands = at.candidate_configs((200, 200, 200), (16, 16, 16), 4000)
    assert cands[0] == at.DEFAULT_CONFIG
    assert len(set(cands)) == len(cands)
    for c in cands[1:]:
        assert at.vmem_bytes(c, (200, 200, 200), (16, 16, 16)) \
            <= at.VMEM_BUDGET_BYTES


def test_candidates_fused_layout_only_for_order3():
    c3 = at.candidate_configs((50, 40, 30), (4, 4, 4), 1000)
    assert any(c.layout == "fused" for c in c3)
    c4 = at.candidate_configs((20, 20, 20, 20), (3, 3, 3, 3), 1000)
    assert all(c.layout == "split" for c in c4)


def test_vmem_model_monotone_in_blocks():
    small = at.BlockConfig(bl=128, bk=256, bn=64, bi=64)
    big = at.BlockConfig(bl=512, bk=512, bn=256, bi=256)
    shape, ranks = (100, 100, 100), (8, 8, 8)
    assert at.vmem_bytes(small, shape, ranks) < at.vmem_bytes(big, shape, ranks)
    # bf16 operands shrink the footprint
    assert at.vmem_bytes(big, shape, ranks, "bf16_fp32acc") \
        < at.vmem_bytes(big, shape, ranks, "fp32")


# ---------------------------------------------------------------------------
# persistent table
# ---------------------------------------------------------------------------


def test_table_roundtrip(tmp_path):
    path = str(tmp_path / "tab.json")
    t = at.TuningTable(path)
    assert len(t) == 0
    cfg = at.BlockConfig(128, 256, 64, 64, "fused")
    t.put("abc", cfg, key={"shape": [4, 4, 4]}, trial_ms=1.5)
    t.save()
    t2 = at.TuningTable(path)
    assert "abc" in t2 and t2.get("abc") == cfg
    assert t2.get("missing") is None


def test_table_tolerates_corrupt_and_versioned_files(tmp_path):
    path = tmp_path / "tab.json"
    path.write_text("{not json")
    assert len(at.TuningTable(str(path))) == 0  # corrupt -> empty, no crash
    path.write_text(json.dumps({"version": 999, "entries": {"x": {}}}))
    assert len(at.TuningTable(str(path))) == 0  # future version -> ignored


# ---------------------------------------------------------------------------
# the search: cold vs warm
# ---------------------------------------------------------------------------


def test_autotune_cold_searches_warm_hits(tmp_path, monkeypatch):
    _cheap_trials(monkeypatch)
    path = str(tmp_path / "tab.json")
    kw = dict(dtype="float32", precision="fp32", backend="cpu")

    cfg = at.autotune((20, 16, 12), (3, 3, 2), 300,
                      table=at.TuningTable(path), max_trials=3, **kw)
    assert isinstance(cfg, at.BlockConfig)
    assert at.COUNTERS == {"searches": 1, "trials": 3, "table_hits": 0}

    # warm: a FRESH table object reloads the file -> pure hit, zero trials.
    cfg2 = at.autotune((20, 16, 12), (3, 3, 2), 300,
                       table=at.TuningTable(path), max_trials=3, **kw)
    assert cfg2 == cfg
    assert at.COUNTERS == {"searches": 1, "trials": 3, "table_hits": 1}


def test_autotune_picks_fastest_candidate(tmp_path, monkeypatch):
    # rig the trial clock so a specific non-default candidate wins
    cands = at.candidate_configs((20, 16, 12), (3, 3, 2), 300)[:4]
    times = {c: 5.0 for c in cands}
    times[cands[2]] = 0.5
    _cheap_trials(monkeypatch, times)
    cfg = at.autotune(
        (20, 16, 12), (3, 3, 2), 300,
        table=at.TuningTable(str(tmp_path / "t.json")),
        max_trials=4, backend="cpu",
    )
    assert cfg == cands[2]


def test_autotune_survives_crashing_trials(tmp_path, monkeypatch):
    def boom(cfg, *a, **kw):
        at.COUNTERS["trials"] += 1
        if cfg != at.DEFAULT_CONFIG:
            raise RuntimeError("untunable candidate")
        return 1.0

    monkeypatch.setattr(at, "trial_time_ms", boom)
    cfg = at.autotune(
        (20, 16, 12), (3, 3, 2), 300,
        table=at.TuningTable(str(tmp_path / "t.json")),
        max_trials=4, backend="cpu",
    )
    assert cfg == at.DEFAULT_CONFIG  # crashes lose, never propagate


def test_autotune_trial_errors_propagate_on_tpu(tmp_path, monkeypatch):
    """On the chip the VMEM model has already pruned what cannot fit, so a
    failing trial is a compiler refusal: it must raise, not silently persist
    the default config with no timing."""
    import jax

    def boom(cfg, *a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(at, "trial_time_ms", boom)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    table = at.TuningTable(str(tmp_path / "t.json"))
    with pytest.raises(RuntimeError, match="Mosaic"):
        at.autotune((20, 16, 12), (3, 3, 2), 300, table=table,
                    max_trials=2, backend="tpu")
    assert not (tmp_path / "t.json").exists()  # nothing persisted


def test_autotune_real_trial_smoke(tmp_path):
    """One REAL timed trial end-to-end (no monkeypatch): the trial path must
    compile and run a sweep under the candidate's blocks."""
    cfg = at.autotune(
        (12, 10, 8), (3, 3, 2), 150,
        table=at.TuningTable(str(tmp_path / "t.json")),
        max_trials=1, interpret=True,
    )
    assert cfg == at.DEFAULT_CONFIG  # max_trials=1 trials only the default
    assert at.COUNTERS["searches"] == 1 and at.COUNTERS["trials"] == 1


# ---------------------------------------------------------------------------
# through the plan layer
# ---------------------------------------------------------------------------


def test_plan_autotune_cold_then_warm_zero_search(tmp_path, monkeypatch):
    """The tentpole counter assertion: first plan searches once; a fresh
    plan on the same problem is a pure table hit — zero searches, zero
    trials — and decomposes to the same answer."""
    monkeypatch.setenv(at.TABLE_ENV, str(tmp_path / "tab.json"))
    _cheap_trials(monkeypatch)
    coo = random_sparse_tensor((20, 16, 12), 0.05, seed=0)
    spec = tucker.TuckerSpec(
        shape=coo.shape, ranks=(3, 3, 2), method="gram", n_iter=2,
        engine="pallas", autotune=True,
    )

    tucker.clear_plan_cache()
    res1 = tucker.plan(spec)(coo)
    assert res1.tuned_blocks is not None
    assert at.COUNTERS["searches"] == 1
    trials_after_cold = at.COUNTERS["trials"]
    assert trials_after_cold >= 1

    tucker.clear_plan_cache()  # forget the plan, keep the on-disk table
    res2 = tucker.plan(spec)(coo)
    assert at.COUNTERS["searches"] == 1, "warm plan must not re-search"
    assert at.COUNTERS["trials"] == trials_after_cold, \
        "warm plan must not re-trial"
    assert at.COUNTERS["table_hits"] >= 1
    assert res2.tuned_blocks == res1.tuned_blocks
    np.testing.assert_allclose(
        np.asarray(res2.core), np.asarray(res1.core), rtol=1e-6, atol=1e-6
    )


def test_plan_autotune_applies_blocks_to_engine(tmp_path, monkeypatch):
    monkeypatch.setenv(at.TABLE_ENV, str(tmp_path / "tab.json"))
    cands = at.candidate_configs((20, 16, 12), (3, 3, 2), 200)[:2]
    winner = cands[1]
    _cheap_trials(monkeypatch, {cands[0]: 9.0, winner: 0.1})
    coo = random_sparse_tensor((20, 16, 12), 0.05, seed=1)
    spec = tucker.TuckerSpec(
        shape=coo.shape, ranks=(3, 3, 2), method="gram", n_iter=2,
        engine="pallas", autotune=True,
    )
    tucker.clear_plan_cache()
    p = tucker.plan(spec)
    res = p(coo)
    assert tuple(res.tuned_blocks) == tuple(winner)
    assert (p.engine.bn, p.engine.bi) == (winner.bn, winner.bi)
    assert (p.engine.bl, p.engine.bk) == (winner.bl, winner.bk)
    assert p.engine.fuse_core == (winner.layout == "fused")


def test_spec_autotune_validation():
    with pytest.raises(ValueError, match="autotune"):
        tucker.TuckerSpec(shape=(8, 8), ranks=(2, 2), algorithm="dense",
                          autotune=True)
    # no autotune -> result records no tuned blocks
    coo = random_sparse_tensor((10, 8, 6), 0.05, seed=2)
    res = tucker.decompose(coo, (2, 2, 2), n_iter=2, engine="xla")
    assert res.tuned_blocks is None


# ---------------------------------------------------------------------------
# plan.analyze(): the roofline fields CI gates on
# ---------------------------------------------------------------------------


def test_plan_analyze_reports_roofline_fields():
    coo = random_sparse_tensor((16, 12, 10), 0.05, seed=3)
    spec = tucker.TuckerSpec(shape=coo.shape, ranks=(3, 3, 2),
                             method="gram", n_iter=4, engine="xla")
    tucker.clear_plan_cache()
    s = tucker.plan(spec).analyze(coo)
    assert s["dot_flops"] > 0 and s["hbm_bytes"] > 0
    assert s["dot_flops_per_sweep"] == pytest.approx(s["dot_flops"] / 4)
    assert s["hbm_bytes_per_sweep"] == pytest.approx(s["hbm_bytes"] / 4)
    assert s["arithmetic_intensity"] == pytest.approx(
        s["dot_flops"] / s["hbm_bytes"]
    )
    assert s["engine"] == "xla" and s["precision"] == "fp32"
    assert s["fuse_core"] is False and s["tuned_blocks"] is None
