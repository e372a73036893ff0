"""A copy of the benchmark in a temporary root, with tiny configurations
and cells added as new files, for runs on the CPU."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

PLAN = {
    "source": "test", "tensor": "tiny", "entry": "plan",
    "shape": [60, 50, 40], "nnz": 3000, "ranks": [4, 4, 4], "n_iter": 3,
    "precision": "fp32", "spec": {}, "components": 4, "pattern_seed": 7, "chips": 1,
    "reference_block": 1024, "reduced": {}, "assumed": {},
}
SERVE = {
    "source": "test", "tensor": "tiny", "entry": "service",
    "shape": [6, 40, 50], "nnz_per_request": [500, 900],
    "tenant_ranks": [[2, 4, 4], [3, 4, 4]], "n_iter": 3, "precision": "fp32",
    "spec": {}, "service": {}, "components": 2, "pattern_seed": 7, "days": 3,
    "chips": 1, "reference_block": 1024, "check_requests": 4,
    "reduced": {}, "assumed": {},
}
OPEN = {"loop": "open", "rate_per_s": 8.0}


def make(tmp: Path, limits=None, plan=None, serve=None) -> Path:
    """A root at ``tmp`` holding the benchmark plus cells ``tiny.steady``,
    ``tiny4.steady`` (four chips) and ``tiny.open``."""
    root = Path(tmp) / "root"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    lim = limits or {"core_gap": 1e-4, "fit_gap": 1e-4}
    plan = dict(PLAN, **(plan or {}), limits=lim)
    plan4 = dict(plan, chips=4)
    serve = dict(SERVE, **(serve or {}), limits=lim)
    for name, cfg in (("tiny", plan), ("tiny4", plan4), ("tiny_serve", serve)):
        (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    (root / "bench" / "traffic" / "tiny_open.json").write_text(json.dumps(OPEN))
    bench["workloads"] += [
        {"name": "tiny.steady", "config": "tiny", "traffic": "steady", "chips": 1, "why": "t"},
        {"name": "tiny4.steady", "config": "tiny4", "traffic": "steady", "chips": 4, "why": "t"},
        {"name": "tiny.open", "config": "tiny_serve", "traffic": "tiny_open", "chips": 1,
         "why": "t"},
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        w = m.get("workloads")
        if w is None:
            continue
        if "nell2.steady" in w:
            w += ["tiny.steady", "tiny4.steady"]
        if "uber.open" in w:
            w.append("tiny.open")
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
