"""Snapshot/resume pipeline benchmark (TuckerSpec.snapshot) -> BENCH_resume.json.

Measures what fault tolerance costs and proves what it buys:

  * overhead — wall-clock of the segmented snapshot pipeline (checkpoint
    write after every ``every_n_sweeps`` sweeps) over the unsegmented scan
    pipeline on the same problem. The acceptance gate: < 10%% at
    ``every_n_sweeps=5`` (snapshot cadence amortized over 5 compiled sweeps).
  * parity — the segmented run's fit history must match the unsegmented
    run's to 1e-5 (same per-sweep math, the CI gate), and a job killed at a
    segment boundary then resumed must land on the same final fit.
  * steady state — after warmup, timed snapshot runs must not retrace: one
    compiled segment program serves every segment (fresh dirs per call, so
    only the checkpoint writes repeat).

  BENCH_resume.json = {
    "benchmark": "resume_bench", "smoke": bool, "jax": .., "cases": [{
       "shape", "density", "nnz", "ranks", "method", "n_iter",
       "every_n_sweeps",
       "plain_s", "plain_iqr_s",     # unsegmented median wall-clock (s)
       "snap_s", "snap_iqr_s",       # segmented+checkpointing median (s)
       "overhead",                   # snap_s / plain_s - 1 (MUST be < 0.10)
       "fit_maxdiff",                # segmented vs unsegmented (< 1e-5)
       "resume_fit_maxdiff",         # killed+resumed vs unsegmented (< 1e-5)
       "snapshots_per_run", "segments_per_run",
       "retraces_during_timing",     # MUST be 0
    }, ...]
  }

    PYTHONPATH=src:. python benchmarks/resume_bench.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from typing import Optional

OVERHEAD_GATE = 0.10  # snapshot cost bound at every_n_sweeps=5 (ISSUE gate)
PARITY_GATE = 1e-5


def _parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / few iters (CI gate)")
    ap.add_argument("--out", default="BENCH_resume.json")
    return ap.parse_args(argv)


def bench_case(shape, density, ranks, method, n_iter, every, warmup, iters,
               label=""):
    import jax
    import numpy as np

    from repro import tucker
    from repro.core import hooi
    from repro.runtime.fault_tolerance import FailureInjector
    from repro.sparse.generators import random_sparse_tensor

    coo = random_sparse_tensor(shape, density, seed=0)
    plain = tucker.plan(tucker.TuckerSpec(
        shape=tuple(shape), ranks=tuple(ranks), method=method, engine="xla",
        n_iter=n_iter, tol=0.0))

    root = tempfile.mkdtemp(prefix="resume_bench_")

    def snap_spec(directory):
        return tucker.TuckerSpec(
            shape=tuple(shape), ranks=tuple(ranks), method=method,
            engine="xla", n_iter=n_iter, tol=0.0,
            snapshot=tucker.SnapshotSpec(every_n_sweeps=every,
                                         directory=directory))

    run_id = [0]

    def timed_snap():
        # a fresh directory per run: each timed sample pays the FULL
        # checkpoint cost (no old steps to overwrite cheaply), while the
        # compiled segment program is shared across runs (same static key).
        run_id[0] += 1
        d = f"{root}/run{run_id[0]}"
        t0 = time.perf_counter()
        out = tucker.plan(snap_spec(d))(coo)
        jax.block_until_ready(out.core)
        return time.perf_counter() - t0, out

    def timed_plain():
        t0 = time.perf_counter()
        out = plain(coo)
        jax.block_until_ready(out.core)
        return time.perf_counter() - t0, out

    for _ in range(max(1, warmup)):
        timed_plain()
        timed_snap()
    traces_before = sum(hooi.SWEEP_TRACE_COUNTS.values())
    samples = {"plain": [], "snap": []}
    results = {}
    for _ in range(iters):
        dt, results["plain"] = timed_plain()
        samples["plain"].append(dt)
        dt, results["snap"] = timed_snap()
        samples["snap"].append(dt)
    retraces = sum(hooi.SWEEP_TRACE_COUNTS.values()) - traces_before
    timings = {
        p: (float(np.median(s)),
            float(np.percentile(s, 75) - np.percentile(s, 25)))
        for p, s in samples.items()
    }
    fit_maxdiff = float(np.abs(
        np.asarray(results["plain"].fit_history)
        - np.asarray(results["snap"].fit_history)).max())

    # kill at the first segment boundary, resume, compare the final fit
    kill_dir = f"{root}/kill"
    spec = snap_spec(kill_dir)
    inj = FailureInjector(fail_at=[every])
    try:
        tucker.plan(spec)(coo, injector=inj)
        raise AssertionError("injected failure did not fire")
    except RuntimeError:
        pass
    resumed = tucker.resume(spec, coo)
    resume_fit_maxdiff = float(np.abs(
        np.asarray(results["plain"].fit_history)
        - np.asarray(resumed.fit_history)).max())
    shutil.rmtree(root, ignore_errors=True)

    return {
        "label": label or f"{'x'.join(map(str, shape))}@{density:g}",
        "shape": list(shape),
        "density": density,
        "nnz": coo.nnz,
        "ranks": list(ranks),
        "method": method,
        "n_iter": n_iter,
        "every_n_sweeps": every,
        "plain_s": timings["plain"][0],
        "plain_iqr_s": timings["plain"][1],
        "snap_s": timings["snap"][0],
        "snap_iqr_s": timings["snap"][1],
        "overhead": timings["snap"][0] / max(timings["plain"][0], 1e-12) - 1.0,
        "fit_maxdiff": fit_maxdiff,
        "resume_fit_maxdiff": resume_fit_maxdiff,
        "resumed_from_sweep": resumed.resumed_from_sweep,
        "snapshots_per_run": results["snap"].snapshots_written,
        "segments_per_run": results["snap"].dispatches,
        "retraces_during_timing": int(retraces),
    }


def main(argv: Optional[list] = None) -> int:
    args = _parse_args(argv)

    import jax

    from benchmarks.common import registry_snapshot
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    # the overhead gate divides a FIXED per-segment cost (one host sync +
    # one ~1ms checkpoint write) by five sweeps of compute, so it is only
    # meaningful on sweep-dominated problems: these shapes run ~25ms+ per
    # segment. (A toy tensor would "fail" the gate on dispatch overhead that
    # snapshotting did not add.)
    if args.smoke:
        grid = [
            ("synthetic-dense", (120, 100, 80), 0.05, (8, 8, 8), 20, "gram"),
        ]
        warmup, iters = 1, 3
    else:
        grid = [
            ("synthetic-dense", (120, 100, 80), 0.05, (8, 8, 8), 20, "gram"),
            ("nell2-like", (200, 200, 200), 5e-3, (8, 8, 8), 20, "gram"),
        ]
        warmup, iters = 3, 10

    cases = []
    for label, shape, density, ranks, n_iter, method in grid:
        t0 = time.time()
        case = bench_case(shape, density, ranks, method, n_iter, every=5,
                          warmup=warmup, iters=iters, label=label)
        cases.append(case)
        print(
            f"{label:18s} "
            f"plain={case['plain_s']*1e3:8.2f}ms "
            f"snap={case['snap_s']*1e3:8.2f}ms "
            f"overhead={case['overhead']*100:+.1f}% "
            f"fitdiff={case['fit_maxdiff']:.1e} "
            f"resumediff={case['resume_fit_maxdiff']:.1e} "
            f"retraces={case['retraces_during_timing']} "
            f"({time.time()-t0:.1f}s)",
            flush=True,
        )

    payload = {
        "benchmark": "resume_bench",
        "smoke": bool(args.smoke),
        "created_unix": int(time.time()),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "overhead_gate": OVERHEAD_GATE,
        "cases": cases,
        "metrics": registry_snapshot(),
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out} ({len(cases)} cases)")

    import numpy as np

    bad = [c for c in cases
           if not np.isfinite(c["fit_maxdiff"]) or c["fit_maxdiff"] > PARITY_GATE
           or not np.isfinite(c["resume_fit_maxdiff"])
           or c["resume_fit_maxdiff"] > PARITY_GATE]
    if bad:
        print("RESUME PARITY REGRESSION: segmented/resumed fit diverged "
              "from the uninterrupted run:")
        for c in bad:
            print(f"  {c['label']}: fit={c['fit_maxdiff']:.2e} "
                  f"resume={c['resume_fit_maxdiff']:.2e}")
        return 1
    bad = [c for c in cases if c["retraces_during_timing"] != 0]
    if bad:
        print("RESUME RETRACE REGRESSION: timed snapshot runs recompiled "
              "(one segment program must serve every segment):")
        for c in bad:
            print(f"  {c['label']}: retraces={c['retraces_during_timing']}")
        return 1
    bad = [c for c in cases if c["overhead"] > OVERHEAD_GATE]
    if bad:
        print(f"SNAPSHOT OVERHEAD REGRESSION: > {OVERHEAD_GATE:.0%} over the "
              f"unsegmented pipeline at every_n_sweeps=5:")
        for c in bad:
            print(f"  {c['label']}: overhead={c['overhead']:.1%}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
