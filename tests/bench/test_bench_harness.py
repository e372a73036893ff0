"""The harness finds every piece by name, refuses to run off the chip, and
prints the contract's result line."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import tiny
from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def _run_script(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nell2.steady", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu_only_jax_without_a_result():
    p = _run_script(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_a_checkout_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d, ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_script(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_file_keeps_the_contract():
    bench = harness.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert all((ROOT / d).is_dir() for d in bench["paths"])
    configs = {c["name"] for c in bench["configs"]}
    assert configs == {w["config"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        reported = {m["name"] for m in harness.metrics_for(bench, w["name"], "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        layers = harness.metrics_for(bench, w["name"], "per_layer")
        assert layers and all(m["moves"] in reported for m in layers)


@pytest.mark.parametrize("cell", [w["name"] for w in harness.benchmark()["workloads"]])
def test_every_cell_resolves_by_name(cell):
    bench = harness.benchmark()
    found, config, traffic = harness.cell_parts(bench, cell)
    assert found["name"] == cell
    assert config["entry"] in ("plan", "service")
    assert traffic["loop"] in ("closed", "open")
    assert set(config["limits"]) >= {"core_gap", "fit_gap"}
    for kind in ("end_to_end", "per_layer"):
        for m in harness.metrics_for(bench, cell, kind):
            assert callable(harness.reader(m["name"]).read)


def test_an_added_config_is_found_without_editing_a_file(tmp_path):
    before = {p: p.read_bytes() for p in (ROOT / "bench").rglob("*") if p.is_file()
              and "__pycache__" not in p.parts}
    root = tiny.make(tmp_path)
    bench = harness.benchmark(root)
    cell, config, traffic = harness.cell_parts(bench, "tiny.steady", root)
    assert config["shape"] == tiny.PLAN["shape"] and traffic["loop"] == "closed"
    cell, config, traffic = harness.cell_parts(bench, "tiny.open", root)
    assert config["entry"] == "service" and traffic == tiny.OPEN
    for m in harness.metrics_for(bench, "tiny.open", "per_layer"):
        assert callable(harness.reader(m["name"], root).read)
    copied = {p.relative_to(root / "bench"): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    for p, data in before.items():
        assert copied[p.relative_to(ROOT / "bench")] == data
    names = {str(p) for p in copied} - {str(p.relative_to(ROOT / "bench")) for p in before}
    assert names == {"configs/tiny.json", "configs/tiny4.json", "configs/tiny_serve.json",
                     "traffic/tiny_open.json"}


def test_an_unknown_device_kind_raises():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.Refused, match="no peaks"):
        harness.peaks_for("TPU v99 imaginary")


def test_result_line_has_the_contract_keys(tmp_path, cpu_run):
    root = tiny.make(tmp_path)
    rc, res, err = cpu_run(root, "tiny.steady")
    assert rc == 0
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"setup_s", "decompose_s"}
    assert all(set(v) == {"value", "unit"} for v in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(res["checks"]) == {"core_gap", "fit_gap"}
    # the compared numbers are the last lines of standard error as well
    tail = err.strip().splitlines()[-2:]
    assert [line.split()[1] for line in tail] == sorted(res["checks"])


def test_traced_run_reports_the_per_layer_metrics(tmp_path, cpu_run, monkeypatch):
    # the CPU's profile has no device plane: the reduction reads the trace
    # recorded on a v5e instead, and everything around it runs for real
    from bench import trace

    recorded = trace.reduce
    data = str(Path(__file__).resolve().parent / "data" / "pallas_small.xplane.pb")
    monkeypatch.setattr(trace, "reduce", lambda path, chips, spans, anchor:
                        recorded(data, chips, spans, anchor))
    root = tiny.make(tmp_path)
    rc, res, _ = cpu_run(root, "tiny.steady", trace=1)
    assert rc == 0 and res["correct"] is True
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device", "breakdown",
                         "checks"]
    assert set(res["metrics"]) == {"kron_ms.decompose", "kron_roofline.decompose",
                                   "device_idle.decompose"}
    assert 0 < res["metrics"]["kron_roofline.decompose"]["value"] < 100
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in res["breakdown"].values())


def test_the_window_compiles_without_the_persistent_cache():
    import jax

    assert jax.config.jax_enable_compilation_cache
    with harness.fresh_compiles():
        assert not jax.config.jax_enable_compilation_cache
    assert jax.config.jax_enable_compilation_cache


def test_serving_window_reports_its_compiles(tmp_path, cpu_run):
    # the Pallas engine, as on the chip, compiles a program for each new day
    root = tiny.make(tmp_path, serve={"spec": {"engine": "pallas"}})
    rc, res, err = cpu_run(root, "tiny.open")
    assert rc == 0 and res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "serve_rps"}
    # every request is a day of its own: a program the window has not seen
    untraced = next(line for line in err.splitlines() if line.startswith("bench: untraced"))
    readings = dict(kv.split("=") for kv in untraced.split()[2:])
    assert set(readings) == {"compile_ms.serve", "queue_ms.serve"}
    assert float(readings["compile_ms.serve"]) > 0
