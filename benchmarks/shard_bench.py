"""Sharded sweep-pipeline benchmark (TuckerSpec.shard) -> BENCH_shard.json.

Times the single-device compiled scan pipeline against the shard_map-wrapped
sharded pipeline across device counts, on a CPU mesh forced with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set by this script
BEFORE the first jax import, unless the caller already exported it — the
same recipe tests and CI use for multi-device coverage on a 1-CPU host).

Honesty note: forced host devices share the same physical cores, so CPU
"speedups" here measure overhead, not scaling — the record that matters is
the structural one: 1 dispatch per decompose, 0 retraces during timing,
sharded fit within 1e-5 of single-device (the CI gate), and psum bytes per
sweep independent of the device count.

  BENCH_shard.json = {
    "benchmark": "shard_bench", "smoke": bool, "jax": .., "devices": N,
    "cases": [{
       "shape", "density", "nnz", "nnz_padded", "ranks", "method", "n_iter",
       "devices",                    # shard count of this case
       "single_s", "single_iqr_s",   # single-device median wall-clock (s)
       "sharded_s", "sharded_iqr_s", # sharded median wall-clock (s)
       "overhead",                   # sharded_s / single_s on a forced mesh
       "fit_maxdiff",                # MUST be < 1e-5 (CI gate)
       "dispatches_per_call",        # MUST be 1
       "retraces_during_timing",     # MUST be 0
       "collective_bytes_per_sweep", "shard_imbalance",
    }, ...]
  }

    PYTHONPATH=src:. python benchmarks/shard_bench.py [--smoke] [--out PATH]
        [--devices N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional


def _parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / few iters (CI gate)")
    ap.add_argument("--out", default="BENCH_shard.json")
    ap.add_argument("--devices", type=int, default=4,
                    help="host devices to force (ignored if XLA_FLAGS is "
                         "already exported)")
    return ap.parse_args(argv)


def bench_case(shape, density, ranks, method, n_iter, devices, warmup, iters,
               label=""):
    import jax
    import numpy as np

    from repro import tucker
    from repro.core import hooi
    from repro.sparse.generators import random_sparse_tensor

    coo = random_sparse_tensor(shape, density, seed=0)
    single = tucker.plan(tucker.TuckerSpec(
        shape=tuple(shape), ranks=tuple(ranks), method=method, engine="xla",
        n_iter=n_iter))
    sharded = tucker.plan(tucker.TuckerSpec(
        shape=tuple(shape), ranks=tuple(ranks), method=method, n_iter=n_iter,
        shard=tucker.ShardSpec(num_devices=devices)))

    def timed(plan):
        t0 = time.perf_counter()
        out = plan(coo)
        jax.block_until_ready(out.core)
        return time.perf_counter() - t0, out

    for _ in range(max(1, warmup)):
        for plan in (single, sharded):
            timed(plan)
    traces_before = sum(hooi.SWEEP_TRACE_COUNTS.values())
    samples = {"single": [], "sharded": []}
    results = {}
    for _ in range(iters):
        for name, plan in (("single", single), ("sharded", sharded)):
            dt, results[name] = timed(plan)
            samples[name].append(dt)
    timings = {
        p: (float(np.median(s)),
            float(np.percentile(s, 75) - np.percentile(s, 25)))
        for p, s in samples.items()
    }
    retraces = sum(hooi.SWEEP_TRACE_COUNTS.values()) - traces_before
    res = results["sharded"]
    fit_maxdiff = float(np.abs(
        results["single"].fit_history - res.fit_history).max())
    sched = sharded.engine.shard_schedule(coo, sharded.mesh,
                                         (sharded.spec.shard.axis,))
    return {
        "label": label or f"{'x'.join(map(str, shape))}@{density:g}",
        "shape": list(shape),
        "density": density,
        "nnz": coo.nnz,
        "nnz_padded": sched.nnz_padded,
        "ranks": list(ranks),
        "method": method,
        "n_iter": n_iter,
        "devices": devices,
        "single_s": timings["single"][0],
        "single_iqr_s": timings["single"][1],
        "sharded_s": timings["sharded"][0],
        "sharded_iqr_s": timings["sharded"][1],
        "overhead": timings["sharded"][0] / max(timings["single"][0], 1e-12),
        "fit_maxdiff": fit_maxdiff,
        "dispatches_per_call": res.dispatches,
        "retraces_during_timing": int(retraces),
        "collective_bytes_per_sweep": res.collective_bytes_per_sweep,
        "shard_imbalance": res.shard_imbalance,
    }


def main(argv: Optional[list] = None) -> int:
    args = _parse_args(argv)
    if "jax" in sys.modules and "XLA_FLAGS" not in os.environ:
        print("warning: jax already imported without XLA_FLAGS; "
              "multi-device cases will fail", file=sys.stderr)
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={max(1, args.devices)}",
    )

    import jax

    from benchmarks.common import registry_snapshot
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    n_dev = len(jax.devices())
    device_counts = sorted({d for d in (1, 2, 4) if d <= n_dev})

    if args.smoke:
        grid = [
            ("synthetic-small", (30, 24, 18), 0.03, (4, 3, 2), 5, "gram"),
        ]
        warmup, iters = 1, 3
    else:
        grid = [
            ("synthetic-medium", (60, 50, 40), 0.02, (6, 5, 4), 5, "gram"),
            ("nell2-like", (200, 200, 200), 1e-3, (8, 8, 8), 5, "gram"),
        ]
        warmup, iters = 3, 10

    cases = []
    for label, shape, density, ranks, n_iter, method in grid:
        for devices in device_counts:
            t0 = time.time()
            case = bench_case(shape, density, ranks, method, n_iter, devices,
                              warmup, iters, label=label)
            cases.append(case)
            print(
                f"{label:18s} d={devices} "
                f"single={case['single_s']*1e3:8.2f}ms "
                f"sharded={case['sharded_s']*1e3:8.2f}ms "
                f"fitdiff={case['fit_maxdiff']:.1e} "
                f"imbalance={case['shard_imbalance']:.3f} "
                f"retraces={case['retraces_during_timing']} "
                f"({time.time()-t0:.1f}s)",
                flush=True,
            )

    payload = {
        "benchmark": "shard_bench",
        "smoke": bool(args.smoke),
        "created_unix": int(time.time()),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "devices": n_dev,
        "cases": cases,
        "metrics": registry_snapshot(),
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out} ({len(cases)} cases)")

    import numpy as np

    bad = [c for c in cases if not np.isfinite(c["fit_maxdiff"])
           or c["fit_maxdiff"] > 1e-5]
    if bad:
        print("SHARD PARITY REGRESSION: sharded fit diverged from "
              "single-device:")
        for c in bad:
            print(f"  {c['label']} d={c['devices']}: "
                  f"maxdiff={c['fit_maxdiff']:.2e}")
        return 1
    bad = [c for c in cases if c["retraces_during_timing"] != 0
           or c["dispatches_per_call"] != 1]
    if bad:
        print("SHARD DISPATCH REGRESSION: timed calls retraced or "
              "multi-dispatched:")
        for c in bad:
            print(f"  {c['label']} d={c['devices']}: "
                  f"retraces={c['retraces_during_timing']} "
                  f"dispatches={c['dispatches_per_call']}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
