"""``correct`` comes out false for the lower-precision control and for each
fault the cells can have, planted under the timed path of a whole run (the
look for a chip skipped, on the CPU at a tiny size), and true without one.
The runs share two processes, one with a single device and one with four."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
# On the chip the control is the program under the `high` matmul precision;
# the CPU computes float32 at any setting, so here the program's own
# bfloat16 path stands in as the lower precision.
CONTROL = {"precision": "bf16_fp32acc"}
ONE = [("none", "tiny.steady", {}), ("none", "tiny.open", {}),
       ("unchanged", "tiny.steady", {}), ("half", "tiny.steady", {}),
       ("altered", "tiny.steady", {}), ("half", "tiny.open", {}),
       ("altered", "tiny.open", {}), ("none", "tiny.steady", CONTROL)]
FOUR = [("none", "tiny4.steady", {}), ("nopsum", "tiny4.steady", {})]


def _runs(cases, devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    p = subprocess.run([sys.executable, str(HERE / "run_fault.py"), json.dumps(cases)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = {}
    for line in p.stdout.splitlines():
        if line.startswith('{"case"'):
            row = json.loads(line)
            fault, cell, plan = row["case"]
            assert row["rc"] == 0 and row["result"] is not None, row
            out[(fault, cell, json.dumps(plan))] = row["result"]
    return out


@pytest.fixture(scope="module")
def results():
    return {**_runs(ONE, 1), **_runs(FOUR, 4)}


def _get(results, fault, cell, plan=None):
    return results[(fault, cell, json.dumps(plan or {}))]


@pytest.mark.parametrize("cell", ["tiny.steady", "tiny.open", "tiny4.steady"])
def test_sound_run_is_correct(results, cell):
    res = _get(results, "none", cell)
    assert res["correct"] is True and res["failed"] == 0, res["checks"]


@pytest.mark.parametrize("fault,cell", [
    ("unchanged", "tiny.steady"),
    ("half", "tiny.steady"),
    ("altered", "tiny.steady"),
    ("half", "tiny.open"),
    ("altered", "tiny.open"),
    ("nopsum", "tiny4.steady"),
])
def test_fault_is_caught(results, fault, cell):
    res = _get(results, fault, cell)
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_lower_precision_control_is_caught(results):
    res = _get(results, "none", "tiny.steady", CONTROL)
    assert res["correct"] is False
    assert res["checks"]["core_gap"]["value"] > res["checks"]["core_gap"]["limit"]
