"""repro.analysis: one seeded violation per lint family (the CI gate must
be able to fail), clean passes on the real compiled programs, and the
plan.lint()/analyze() surface on snapshot-segmented and sharded programs."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro.analysis import (
    Baseline,
    Finding,
    Suppression,
    collective_lint,
    donation_lint,
    precision_lint,
    retrace_hazard_lint,
    scatter_race_lint_schedule,
    transfer_lint,
    transfer_lint_jaxpr,
)
from repro.sparse.generators import random_sparse_tensor
from repro.sparse.layout import build_mode_layout
from repro.tucker import SnapshotSpec, TuckerSpec
from repro.tucker.planning import TuckerPlan

SHAPE, RANKS = (12, 10, 8), (3, 3, 2)


@pytest.fixture(scope="module")
def coo():
    return random_sparse_tensor(SHAPE, 0.08, seed=0)


@pytest.fixture(scope="module")
def xla_plan():
    return TuckerPlan(
        TuckerSpec(
            shape=SHAPE, ranks=RANKS, method="gram", engine="xla", n_iter=3
        )
    )


@pytest.fixture(scope="module")
def xla_lowered(xla_plan, coo):
    return xla_plan.lower_hlo(coo)


# ---------------------------------------------------------------------------
# seeded violations: every family must be able to fire, and fire precisely.
# ---------------------------------------------------------------------------

# a trip-4 sweep loop with a host outfeed smuggled into the body — the
# canonical "second transfer" violation.
_TRANSFER_HLO = textwrap.dedent(
    """\
    HloModule bad_transfer

    %body.1 (p.2: (f32[8], token[])) -> (f32[8], token[]) {
      %p.2 = (f32[8]{0}, token[]) parameter(0)
      %gte.2 = f32[8]{0} get-tuple-element((f32[8]{0}, token[]) %p.2), index=0
      %tok.2 = token[] get-tuple-element((f32[8]{0}, token[]) %p.2), index=1
      %out.2 = token[] outfeed(f32[8]{0} %gte.2, token[] %tok.2)
      ROOT %tuple.2 = (f32[8]{0}, token[]) tuple(f32[8]{0} %gte.2, token[] %out.2)
    }

    %cond.1 (p.3: (f32[8], token[])) -> pred[] {
      %p.3 = (f32[8]{0}, token[]) parameter(0)
      ROOT %c.3 = pred[] constant(false)
    }

    ENTRY %main.1 (a.1: f32[8]) -> f32[8] {
      %a.1 = f32[8]{0} parameter(0)
      %tok.1 = token[] after-all()
      %tuple.1 = (f32[8]{0}, token[]) tuple(f32[8]{0} %a.1, token[] %tok.1)
      %while.1 = (f32[8]{0}, token[]) while((f32[8]{0}, token[]) %tuple.1), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"4"}}
      ROOT %gte.1 = f32[8]{0} get-tuple-element((f32[8]{0}, token[]) %while.1), index=0
    }
    """
)


def test_transfer_lint_seeded_outfeed():
    findings = transfer_lint(_TRANSFER_HLO, where="cell")
    assert len(findings) == 1
    (f,) = findings
    assert f.check == "transfer" and f.severity == "error"
    assert "outfeed" in f.message and "x4" in f.message


def test_transfer_lint_seeded_callback():
    text = _TRANSFER_HLO.replace(
        "%out.2 = token[] outfeed(f32[8]{0} %gte.2, token[] %tok.2)",
        '%out.2 = token[] custom-call(f32[8]{0} %gte.2), '
        'custom_call_target="xla_python_cpu_callback", '
        "custom_call_has_side_effect=true",
    )
    findings = transfer_lint(text, where="cell")
    assert len(findings) == 1
    assert "custom-call" in findings[0].message


def test_transfer_lint_jaxpr_seeded():
    def leaky(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )
        return y * 2.0

    closed = jax.make_jaxpr(leaky)(jnp.ones(3))
    findings = transfer_lint_jaxpr(closed, where="cell")
    assert len(findings) == 1
    assert "callback" in findings[0].message

    clean = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones(3))
    assert transfer_lint_jaxpr(clean, where="cell") == []


def test_donation_lint_seeded_undonated_carry(xla_lowered):
    text, meta = xla_lowered
    # claim one more donated factor than the executable aliases: exactly
    # that parameter must be reported.
    bogus = tuple(meta["donated_params"]) + (17,)
    findings = donation_lint(text, donated_params=bogus, where="cell")
    assert len(findings) == 1
    assert findings[0].check == "donation"
    assert "parameter 17" in findings[0].message


_BF16_ACC_HLO = textwrap.dedent(
    """\
    ENTRY %main.1 (a.1: bf16[16,16], b.1: bf16[16,16]) -> f32[16,16] {
      %a.1 = bf16[16,16]{1,0} parameter(0)
      %b.1 = bf16[16,16]{1,0} parameter(1)
      %dot.1 = bf16[16,16]{1,0} dot(bf16[16,16]{1,0} %a.1, bf16[16,16]{1,0} %b.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      ROOT %convert.1 = f32[16,16]{1,0} convert(bf16[16,16]{1,0} %dot.1)
    }
    """
)


def test_precision_lint_seeded_bf16_accumulator():
    findings = precision_lint(
        _BF16_ACC_HLO, precision="bf16_fp32acc", where="cell"
    )
    assert len(findings) == 1
    assert findings[0].check == "precision"
    assert "'dot'" in findings[0].message

    # the same dot accumulating to f32 from bf16 operands is the contract
    # working as intended.
    good = _BF16_ACC_HLO.replace(
        "%dot.1 = bf16[16,16]{1,0} dot", "%dot.1 = f32[16,16]{1,0} dot"
    ).replace(
        "ROOT %convert.1 = f32[16,16]{1,0} convert(bf16[16,16]{1,0} %dot.1)",
        "ROOT %convert.1 = f32[16,16]{1,0} convert(f32[16,16]{1,0} %dot.1)",
    )
    assert precision_lint(good, precision="bf16_fp32acc", where="cell") == []


def test_precision_lint_fp32_program_rejects_bf16():
    findings = precision_lint(_BF16_ACC_HLO, precision="fp32", where="cell")
    assert len(findings) == 1
    assert "fp32-precision program" in findings[0].message


_UNSHARDED_COLLECTIVE_HLO = textwrap.dedent(
    """\
    %sum.1 (x.2: f32[], y.2: f32[]) -> f32[] {
      %x.2 = f32[] parameter(0)
      %y.2 = f32[] parameter(1)
      ROOT %add.2 = f32[] add(f32[] %x.2, f32[] %y.2)
    }

    ENTRY %main.1 (a.1: f32[12,6]) -> f32[12,6] {
      %a.1 = f32[12,6]{1,0} parameter(0)
      ROOT %ar.1 = f32[12,6]{1,0} all-reduce(f32[12,6]{1,0} %a.1), replica_groups={}, to_apply=%sum.1
    }
    """
)


def test_collective_lint_seeded_unsharded():
    findings = collective_lint(
        _UNSHARDED_COLLECTIVE_HLO, sharded=False, where="cell"
    )
    assert len(findings) == 1
    assert findings[0].check == "collective"
    assert "unsharded" in findings[0].message


def test_collective_lint_seeded_wrong_count_and_bytes():
    # one 288-byte psum in an unlooped program, against a 3-mode 2-sweep
    # contract: the mode-bytes check passes (288 IS mode 0's unfolding)
    # but count (1 != 6) and total bytes must both fire.
    findings = collective_lint(
        _UNSHARDED_COLLECTIVE_HLO,
        sharded=True,
        shape=SHAPE,
        ranks=RANKS,
        n_sweeps=2,
        where="cell",
    )
    msgs = "\n".join(f.message for f in findings)
    assert len(findings) == 2
    assert "expected exactly 6" in msgs
    assert "psum_bytes_per_sweep predicts" in msgs

    # a payload that is NO mode's unfolding also trips the shape check.
    bad = _UNSHARDED_COLLECTIVE_HLO.replace("f32[12,6]", "f32[12,7]")
    findings = collective_lint(
        bad, sharded=True, shape=SHAPE, ranks=RANKS, n_sweeps=2, where="cell"
    )
    assert any("no mode's partial unfolding" in f.message for f in findings)


def test_retrace_hazard_lint_seeded():
    @dataclasses.dataclass(frozen=True)
    class NanKey:  # accepts NaN: cache-defeating
        tol: float = 0.0

    findings = retrace_hazard_lint(
        classes=(NanKey,), templates=(NanKey(),), where="t"
    )
    assert len(findings) == 1
    assert findings[0].check == "retrace-hazard"
    assert "accepts NaN" in findings[0].message

    @dataclasses.dataclass(frozen=True, eq=True)
    class ListKey:
        items: list = dataclasses.field(default_factory=list)

    findings = retrace_hazard_lint(
        classes=(ListKey,), templates=(), where="t"
    )
    # the mutable annotation alone must be caught statically (frozen=True
    # list-field instances are unhashable too, but the template probe
    # can't even construct a hashable one).
    assert any("mutable container" in f.message for f in findings)

    @dataclasses.dataclass
    class Unfrozen:
        n: int = 1

    findings = retrace_hazard_lint(
        classes=(Unfrozen,), templates=(), where="t"
    )
    assert any("not frozen" in f.message for f in findings)
    assert any("unhashable" in f.message for f in findings)


def test_retrace_hazard_lint_nan_template():
    @dataclasses.dataclass(frozen=True)
    class Key:
        tol: float

    findings = retrace_hazard_lint(
        classes=(), templates=(Key(tol=float("nan")),), where="t"
    )
    assert any("NaN-valued member" in f.message for f in findings)


def test_retrace_hazard_lint_repo_specs_clean():
    assert retrace_hazard_lint() == []


def test_scatter_race_lint_seeded(coo):
    lay = build_mode_layout(coo, 0, bn=8, bi=4)
    rows = np.asarray(coo.indices)[:, 0]
    assert scatter_race_lint_schedule(lay, rows, where="m0") == []

    # corrupt one valid slot's rel_row: its one-hot write now lands in
    # another block's row window — exactly one cross-block race finding.
    rel = np.array(lay.rel_row)
    slot = int(np.argmax(np.asarray(lay.valid) > 0))
    rel[slot] = (rel[slot] + 1) % lay.bi
    bad = lay._replace(rel_row=rel)
    findings = scatter_race_lint_schedule(bad, rows, where="m0")
    assert len(findings) == 1
    assert findings[0].check == "scatter-race"
    assert "write race" in findings[0].message or "clobber" in findings[0].message

    # drop a first-flag: the stale-accumulator hazard (and the derived
    # last-flags disagree too).
    first = np.array(lay.first)
    if first.sum() > 1:
        first[np.flatnonzero(first)[1]] = 0
        bad = lay._replace(first=first)
        findings = scatter_race_lint_schedule(bad, rows, where="m0")
        assert any("not zeroed on group entry" in f.message for f in findings)

    # a dropped nonzero: no longer a permutation.
    order = np.array(lay.order)
    v = np.flatnonzero(np.asarray(lay.valid) > 0)
    order[v[0]] = order[v[1]]
    bad = lay._replace(order=order)
    findings = scatter_race_lint_schedule(bad, rows, where="m0")
    assert any("not a permutation" in f.message for f in findings)


# ---------------------------------------------------------------------------
# clean passes + the plan surface
# ---------------------------------------------------------------------------


def test_xla_scan_plan_lints_clean(xla_plan, coo):
    assert xla_plan.lint(coo) == []


def test_pallas_plan_lints_clean(coo):
    plan = TuckerPlan(
        TuckerSpec(
            shape=SHAPE, ranks=RANKS, method="gram", engine="pallas", n_iter=2
        )
    )
    assert plan.lint(coo) == []


def test_snapshot_segment_plan_lint_and_analyze(coo, tmp_path):
    plan = TuckerPlan(
        TuckerSpec(
            shape=SHAPE, ranks=RANKS, method="gram", engine="xla", n_iter=5,
            snapshot=SnapshotSpec(every_n_sweeps=2, directory=str(tmp_path)),
        )
    )
    assert plan.lint(coo) == []
    a = plan.analyze(coo)
    assert a["program"] == "segment"
    assert a["n_sweeps_traced"] == 2
    assert a["dot_flops"] > 0
    # the segment program does NOT donate factors (the host spills the
    # carry right after dispatch) — the linter must not demand aliases.
    text, meta = plan.lower_hlo(coo)
    assert meta["donated_params"] == ()


def test_batched_plan_lints_clean(xla_plan):
    """The vmapped batch() program holds the same transfer/precision
    contracts as the per-tensor pipeline, and donates nothing (caller-owned
    member/key buffers)."""
    coos = [
        random_sparse_tensor(SHAPE, 0.06 * (1 + i), seed=40 + i)
        for i in range(3)
    ]
    assert xla_plan.lint_batch(coos) == []
    text, meta = xla_plan.lower_batch_hlo(coos)
    assert meta["kind"] == "batched"
    assert meta["batch"] == 3
    assert meta["donated_params"] == ()
    # mixed-nnz members lower at the padded batch max
    assert meta["padded_nnz"] == max(c.nnz for c in coos)


def test_batched_lint_rejects_fallback_plans(coo):
    plan = TuckerPlan(
        TuckerSpec(
            shape=SHAPE, ranks=RANKS, method="gram", engine="pallas", n_iter=2
        )
    )
    with pytest.raises(ValueError, match="sequential fallback"):
        plan.lower_batch_hlo([coo, coo])


def test_batched_cell_in_default_matrix():
    cells = {c.name: c for c in analysis.default_matrix()}
    assert "xla/batched/fp32" in cells
    assert cells["xla/batched/fp32"].batch > 0


def test_baseline_suppression_roundtrip(tmp_path):
    f1 = Finding("transfer", "error", "cell/comp", "an outfeed happened")
    f2 = Finding("donation", "error", "cell/param2", "donation dropped")
    base = Baseline(
        suppressions=[
            Suppression(
                check="transfer", where="cell/*", match="outfeed",
                reason="known CPU-backend artifact",
            )
        ]
    )
    kept, suppressed = base.filter([f1, f2])
    assert kept == [f2] and suppressed == [f1]

    path = tmp_path / "baseline.json"
    base.save(str(path))
    reloaded = Baseline.load(str(path))
    assert reloaded.suppressions == base.suppressions
    kept, suppressed = reloaded.filter([f1, f2])
    assert kept == [f2] and suppressed == [f1]


def test_finding_validation():
    with pytest.raises(ValueError, match="unknown check"):
        Finding("nonsense", "error", "x", "y")
    with pytest.raises(ValueError, match="unknown severity"):
        Finding("transfer", "fatal", "x", "y")


def test_cli_single_cell(tmp_path):
    from repro.analysis.__main__ import main

    out = tmp_path / "report.json"
    rc = main(["--cell", "xla/scan/fp32", "--json", str(out), "--no-baseline"])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["ok"] is True
    names = [c["name"] for c in report["cells"]]
    assert "plan-cache" in names and "xla/scan/fp32" in names


@pytest.mark.slow
def test_sharded_analyze_and_lint_subprocess():
    """Sharded (plain + resumable) programs on 2 forced host devices:
    lint comes back clean and analyze's collective bytes match the
    psum_bytes_per_sweep oracle exactly."""
    code = textwrap.dedent(
        """
        import numpy as np
        from repro.core.distributed import psum_bytes_per_sweep
        from repro.sparse.generators import random_sparse_tensor
        from repro.tucker import ShardSpec, SnapshotSpec, TuckerSpec
        from repro.tucker.planning import TuckerPlan

        shape, ranks = (12, 10, 8), (3, 3, 2)
        coo = random_sparse_tensor(shape, 0.08, seed=0)
        base = dict(shape=shape, ranks=ranks, method="gram", engine="xla",
                    n_iter=3, shard=ShardSpec(num_devices=2))

        plan = TuckerPlan(TuckerSpec(**base))
        assert plan.lint(coo) == [], plan.lint(coo)
        a = plan.analyze(coo)
        assert a["program"] == "sharded"
        per_sweep = psum_bytes_per_sweep(shape, ranks)
        assert a["collective_bytes_per_sweep"] == per_sweep, a
        assert a["collective_bytes"] == per_sweep * 3, a

        plan = TuckerPlan(TuckerSpec(
            snapshot=SnapshotSpec(every_n_sweeps=2, directory="/tmp/lint-snap"),
            **base))
        assert plan.lint(coo) == [], plan.lint(coo)
        a = plan.analyze(coo)
        assert a["program"] == "sharded-segment"
        assert a["n_sweeps_traced"] == 2
        assert a["collective_bytes"] == per_sweep * 2, a
        print("sharded lint/analyze OK")
        """
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "sharded lint/analyze OK" in proc.stdout
