"""The four-chip nell-2 configuration as its cell ``nell2x4.steady`` runs it:
the file agrees with the cell, its spec shards the nonzeros over four chips
on the XLA engine, and a whole run through a copy of the benchmark on four
CPU devices is correct while a run with the cross-chip sum left out is not.

The runs keep the configuration's shape, ranks, precision, planted
components and pattern seed, and cut what a CPU can hold in a few seconds:
2^14 nonzeros (4,096 a device), 2 sweeps, and reference blocks of 4,096."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from bench import harness, systems

HERE = Path(__file__).resolve().parent
CELL = "nell2x4.steady"
CPU_CUT = {"nnz": 2**14, "n_iter": 2, "reference_block": 4096}

_SCRIPT = """
    import json, sys
    sys.path[:0] = [%(root)r, %(src)r, %(here)r]
    from bench import harness, systems
    from repro import tucker
    import run_fault

    _, config, _ = harness.cell_parts(harness.benchmark(), %(cell)r)
    plan = tucker.plan(systems._spec(config, config["ranks"], int(config["chips"])))
    print(json.dumps({"engine": plan.engine.name,
                      "mesh_devices": int(plan.mesh.devices.size)}), flush=True)
    tucker.clear_plan_cache()
    sys.exit(run_fault.main([%(cases)r]))
"""


def _config():
    cell, config, _ = harness.cell_parts(harness.benchmark(), CELL)
    return cell, config


def _overrides(config):
    keep = ("shape", "ranks", "precision", "matmul_precision", "spec", "components",
            "pattern_seed")
    return dict({k: config[k] for k in keep}, **CPU_CUT)


def test_config_matches_its_cell():
    cell, config = _config()
    assert config["chips"] == cell["chips"] == 4
    assert config["nnz"] % config["chips"] == 0
    assert "nnz" in config["reduced"]
    entry = {c["name"]: c for c in harness.benchmark()["configs"]}[cell["config"]]
    assert entry["reduced"] == list(config["reduced"])
    spec = systems._spec(config, config["ranks"], config["chips"])
    assert spec.shard is not None and spec.shard.num_devices == 4
    assert spec.engine != "pallas" and spec.precision == "fp32"
    assert config["control"] == {"matmul_precision": "high"}


@pytest.fixture(scope="module")
def four_device_run():
    _, config = _config()
    over = _overrides(config)
    cases = json.dumps([["none", "tiny4.steady", over], ["nopsum", "tiny4.steady", over]])
    code = textwrap.dedent(_SCRIPT % {"root": str(HERE.parents[1]),
                                      "src": str(HERE.parents[1] / "src"),
                                      "here": str(HERE), "cell": CELL, "cases": cases})
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines() if ln.startswith("{")]
    plan = lines[0]
    results = {row["case"][0]: row for row in lines[1:]}
    return plan, results


def test_sharded_plan_resolves_to_the_xla_engine(four_device_run):
    plan, _ = four_device_run
    assert plan == {"engine": "xla", "mesh_devices": 4}


def test_sound_four_device_run_is_correct(four_device_run):
    row = four_device_run[1]["none"]
    assert row["rc"] == 0 and row["result"]["device"]["count"] == 4
    res = row["result"]
    assert res["correct"] is True and res["failed"] == 0, res["checks"]


def test_missing_psum_is_caught(four_device_run):
    res = four_device_run[1]["nopsum"]["result"]
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
