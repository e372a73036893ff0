"""End-to-end sweep-pipeline benchmark (repro.tucker plans) -> BENCH_sweep.json.

Times the legacy per-sweep Python driver (``pipeline="python"``: one XLA
dispatch + one blocking host sync per sweep) against the compiled
scan-over-sweeps pipeline (``pipeline="scan"``: the whole multi-sweep loop is
one XLA program, fit history crosses device->host once per call), across

    engines  x  QRP methods  x  {synthetic, dataset-like} shapes,

and records the perf trajectory every future PR is measured against:

  BENCH_sweep.json = {
    "benchmark": "sweep_bench", "smoke": bool, "jax": .., "backend": ..,
    "cases": [{
       "shape", "density", "nnz", "ranks", "engine", "method", "n_iter",
       "python_s", "python_iqr_s",   # legacy driver median wall-clock (s)
       "scan_s",   "scan_iqr_s",     # compiled pipeline median wall-clock (s)
       "speedup",                    # python_s / scan_s  (>1 => scan faster)
       "dispatches_per_call": {"python": n_iter, "scan": 1},
       "retraces_during_timing",     # MUST be 0 (jit cache hit every call)
       "fit_maxdiff",                # |python fit history - scan fit history|
       "hbm_bytes_per_sweep",        # lowered-HLO traffic (repro.utils.hlo)
       "dot_flops_per_sweep",
       "arithmetic_intensity",       # achieved FLOPs per HBM byte
    }, ...],
    "core_fusion": {...},            # megakernel vs split-core HBM bytes
  }

Retrace regression gate (CI runs ``--smoke``): after warmup, every timed call
must hit the compiled-sweep jit cache. Any retrace during timing — e.g. a
schedule pytree or static argument churning per call — exits nonzero.

Roofline gates (same run): every case records achieved arithmetic intensity
and HBM bytes/sweep from the lowered scan program; with ``--baseline OLD.json``
a case whose intensity regressed >10% vs the same-labeled baseline case fails
the run. The ``core_fusion`` block measures the fused Kron→scatter→TTM
megakernel against the split (unfolding kernel → HBM Y → TTM kernel) core
path and fails unless fused moves strictly fewer bytes. ``--autotune`` also
times an autotuned Pallas plan per case and fails if it is slower than the
hand-picked default beyond noise.

    PYTHONPATH=src:. python benchmarks/sweep_bench.py [--smoke] [--out PATH]
        [--baseline OLD.json] [--autotune]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Optional

import numpy as np


def bench_case(
    shape,
    density: float,
    ranks,
    engine: str,
    method: str,
    n_iter: int,
    warmup: int,
    iters: int,
    label: str = "",
) -> dict:
    from repro import tucker
    from repro.core import hooi
    from repro.sparse.generators import random_sparse_tensor

    coo = random_sparse_tensor(shape, density, seed=0)
    # one plan per pipeline: each owns its engine, so schedules build once and
    # stay device-resident — the timed region is the sweep loop, not
    # host-side plan construction.
    plans = {
        p: tucker.TuckerPlan(
            tucker.TuckerSpec(
                shape=tuple(shape), ranks=tuple(ranks), method=method,
                engine=engine, pipeline=p, n_iter=n_iter,
            )
        )
        for p in ("python", "scan")
    }

    def run(pipeline):
        return plans[pipeline](coo)

    import jax

    def timed(pipeline):
        t0 = time.perf_counter()
        out = run(pipeline)
        jax.block_until_ready(out.core)
        return time.perf_counter() - t0, out

    for _ in range(max(1, warmup)):  # warm: build schedules + compile
        for pipeline in ("python", "scan"):
            timed(pipeline)
    traces_before = sum(hooi.SWEEP_TRACE_COUNTS.values())
    # paired reps — python and scan interleave so host load drift (shared CI
    # runners) biases both pipelines equally instead of whichever ran second.
    samples = {"python": [], "scan": []}
    results = {}
    for _ in range(iters):
        for pipeline in ("python", "scan"):
            dt, results[pipeline] = timed(pipeline)
            samples[pipeline].append(dt)
    timings = {
        p: (float(np.median(s)),
            float(np.percentile(s, 75) - np.percentile(s, 25)))
        for p, s in samples.items()
    }
    retraces = sum(hooi.SWEEP_TRACE_COUNTS.values()) - traces_before
    fit_maxdiff = float(
        np.abs(results["python"].fit_history - results["scan"].fit_history).max()
    )
    # roofline fields: parse the compiled scan program's HLO (trip-count
    # multiplied) into FLOPs + approximate HBM traffic per sweep.
    hlo = plans["scan"].analyze(coo)
    case = {
        "label": label or f"{'x'.join(map(str, shape))}@{density:g}",
        "shape": list(shape),
        "density": density,
        "nnz": coo.nnz,
        "ranks": list(ranks),
        "engine": engine,
        "method": method,
        "n_iter": n_iter,
        "python_s": timings["python"][0],
        "python_iqr_s": timings["python"][1],
        "scan_s": timings["scan"][0],
        "scan_iqr_s": timings["scan"][1],
        "speedup": timings["python"][0] / max(timings["scan"][0], 1e-12),
        "dispatches_per_call": {"python": n_iter, "scan": 1},
        "retraces_during_timing": int(retraces),
        "fit_maxdiff": fit_maxdiff,
        "hbm_bytes_per_sweep": hlo["hbm_bytes_per_sweep"],
        "dot_flops_per_sweep": hlo["dot_flops_per_sweep"],
        "arithmetic_intensity": hlo["arithmetic_intensity"],
        # program-contract lint over the same compiled program (repro.analysis)
        # — recorded so every benchmark artifact carries its finding count,
        # and gated to zero below.
        "lint_findings": len(plans["scan"].lint(coo)),
    }
    return case


def bench_autotune_case(shape, density, ranks, method, n_iter) -> dict:
    """Time the autotuned Pallas scan plan against the hand-picked default.

    The default block config is always in the autotuner's candidate set, so
    the tuned pick should never be slower beyond timing noise — the
    acceptance gate the caller enforces."""
    import jax

    from repro import tucker
    from repro.kernels import autotune as _autotune
    from repro.sparse.generators import random_sparse_tensor

    coo = random_sparse_tensor(shape, density, seed=0)
    plans = {}
    for label, auto in (("default", False), ("autotuned", True)):
        plans[label] = tucker.TuckerPlan(
            tucker.TuckerSpec(
                shape=tuple(shape), ranks=tuple(ranks), method=method,
                engine="pallas", pipeline="scan", n_iter=n_iter,
                autotune=auto,
            )
        )

    def timed(label):
        t0 = time.perf_counter()
        out = plans[label](coo)
        jax.block_until_ready(out.core)
        return time.perf_counter() - t0

    for label in plans:  # warm: search (autotuned), compile, schedules
        timed(label)
    samples = {label: [] for label in plans}
    for _ in range(3):
        for label in plans:
            samples[label].append(timed(label))
    med = {label: float(np.median(s)) for label, s in samples.items()}
    tuned = plans["autotuned"]._tuned_blocks
    return {
        "label": f"{'x'.join(map(str, shape))}@{density:g}",
        "default_scan_s": med["default"],
        "autotuned_scan_s": med["autotuned"],
        "autotune_speedup": med["default"] / max(med["autotuned"], 1e-12),
        "tuned_blocks": dict(tuned._asdict()) if tuned is not None else None,
        "counters": dict(_autotune.COUNTERS),
    }


def bench_core_fusion(shape=(24, 18, 2048), ranks=(6, 4, 8), nnz=512) -> dict:
    """HBM bytes of the core update, megakernel vs split kernels.

    Split = the unfolding kernel materializes Y_(N) to HBM, the blocked TTM
    kernel reads it back; fused = the Kron→scatter→TTM megakernel keeps each
    Y block in VMEM scratch and writes only G. Both byte counts come from the
    lowered programs (``repro.utils.hlo``); parity of the results is checked
    here too (the numbers must describe the same computation)."""
    import jax
    import jax.numpy as jnp

    from repro.core.coo import SparseCOO
    from repro.core.engine import make_engine
    from repro.kernels import ops
    from repro.utils.hlo import analyze_hlo

    rng = np.random.default_rng(0)
    idx = np.stack(
        [rng.integers(0, s, nnz) for s in shape], axis=1
    ).astype(np.int32)
    vals = rng.standard_normal(nnz).astype(np.float32)
    coo = SparseCOO(jnp.asarray(idx), jnp.asarray(vals), tuple(shape))
    factors = [
        jnp.asarray(rng.standard_normal((s, r)).astype(np.float32))
        for s, r in zip(shape, ranks)
    ]
    eng = make_engine("pallas")
    last = len(shape) - 1
    sched = eng.device_schedule(coo, last)
    interp = eng.resolved_interpret()

    @jax.jit
    def split_core(indices, values, fs):
        y = ops.sparse_ttm_chain_device(
            indices, values, fs, last, sched, shape=shape, interpret=interp
        )
        return ops.ttm(y.T, fs[last].T, interpret=interp).T

    @jax.jit
    def fused_core(indices, values, fs):
        return ops.sparse_ttm_core_device(
            indices, values, fs, last, sched, shape=shape, interpret=interp
        )

    args = (coo.indices, coo.values, tuple(factors))
    g_split = split_core(*args)
    g_fused = fused_core(*args)
    parity = float(
        jnp.abs(g_split - g_fused).max() / (jnp.abs(g_split).max() + 1e-12)
    )
    b_split = analyze_hlo(split_core.lower(*args).compile().as_text()).io_bytes
    b_fused = analyze_hlo(fused_core.lower(*args).compile().as_text()).io_bytes
    return {
        "shape": list(shape),
        "ranks": list(ranks),
        "nnz": int(nnz),
        "split_hbm_bytes": b_split,
        "fused_hbm_bytes": b_fused,
        "bytes_saving": 1.0 - b_fused / max(b_split, 1.0),
        "parity_relerr": parity,
    }


def bench_trace_overhead(
    shape=(30, 24, 18), density=0.03, ranks=(4, 3, 2), n_iter=5, reps=9
) -> dict:
    """Overhead of the ``repro.obs`` tracing plane on the compiled scan
    pipeline, measured two ways:

      * enabled: paired interleaved reps of the SAME warm plan with tracing
        on vs off — the span bookkeeping the instrumented call sites pay.
      * disabled: the no-op fast path is too cheap to resolve end-to-end
        (it vanishes in timer noise), so it is measured directly — a
        microbenchmark of the disabled ``span()`` call, multiplied by the
        spans one call emits and divided by the untraced wall-clock.

    The ``obs-smoke`` CI gate holds disabled <= 1% and enabled <= 5%.
    """
    import jax

    import repro.obs as obs
    from repro import tucker
    from repro.sparse.generators import random_sparse_tensor

    coo = random_sparse_tensor(shape, density, seed=0)
    plan = tucker.TuckerPlan(
        tucker.TuckerSpec(
            shape=tuple(shape), ranks=tuple(ranks), method="gram",
            engine="xla", pipeline="scan", n_iter=n_iter,
        )
    )

    def timed():
        t0 = time.perf_counter()
        out = plan(coo)
        jax.block_until_ready(out.core)
        return time.perf_counter() - t0

    was_enabled = obs.enabled()
    try:
        obs.configure(enabled=False)
        timed()  # warm: schedules + compile
        obs.configure(enabled=True)
        timed()
        off, on = [], []
        spans_per_call = 0
        for _ in range(reps):
            obs.configure(enabled=False)
            off.append(timed())
            obs.configure(enabled=True)
            before = len(obs.tracer.events())
            on.append(timed())
            spans_per_call = len(obs.tracer.events()) - before
        obs.configure(enabled=False)
        med_off = float(np.median(off))
        med_on = float(np.median(on))
        # disabled fast path, measured where it actually happens
        n = 200_000
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("bench.noop"):
                pass
        noop_s = (time.perf_counter() - t0) / n
    finally:
        obs.configure(enabled=was_enabled)
    return {
        "untraced_s": med_off,
        "traced_s": med_on,
        "spans_per_call": int(spans_per_call),
        "noop_span_ns": noop_s * 1e9,
        "enabled_overhead": med_on / max(med_off, 1e-12) - 1.0,
        "disabled_overhead": spans_per_call * noop_s / max(med_off, 1e-12),
    }


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / few iters (CI gate)")
    ap.add_argument("--out", default="BENCH_sweep.json")
    ap.add_argument("--trace", action="store_true",
                    help="also measure repro.obs tracing overhead on a warm "
                         "scan plan and gate it (disabled <= 1%%, enabled "
                         "<= 5%%)")
    ap.add_argument("--engine", default="both",
                    choices=("xla", "pallas", "both"))
    ap.add_argument("--baseline", default="",
                    help="prior BENCH_sweep.json: fail if any case's "
                         "arithmetic intensity regressed >10%% vs it")
    ap.add_argument("--autotune", action="store_true",
                    help="also time autotuned Pallas plans vs the "
                         "hand-picked default (fails if tuned is slower "
                         "beyond noise)")
    args = ap.parse_args(argv)

    import jax
    from repro.core.engine import available_engines
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    engines = available_engines() if args.engine == "both" else [args.engine]

    if args.smoke:
        grid = [
            # (label, shape, density, ranks, n_iter, methods)
            ("synthetic-small", (30, 24, 18), 0.03, (4, 3, 2), 5,
             ("householder", "gram")),
            ("nell2-like-small", (120, 120, 120), 2.4e-4, (4, 4, 4), 5,
             ("gram",)),
        ]
        warmup, iters = 1, 3
    else:
        grid = [
            ("synthetic-medium", (60, 50, 40), 0.02, (6, 5, 4), 5,
             ("householder", "gram")),
            ("synthetic-paper-200", (200, 200, 200), 1e-3, (8, 8, 8), 5,
             ("gram",)),
            ("nell2-like", (400, 400, 400), 2.4e-5, (8, 8, 8), 8, ("gram",)),
        ]
        # xla calls are ~ms: many reps for a stable median on shared runners.
        warmup, iters = 3, 15

    cases = []
    for label, shape, density, ranks, n_iter, methods in grid:
        for engine in engines:
            for method in methods:
                t0 = time.time()
                # the legacy pallas driver runs interpret-mode kernels eagerly
                # (seconds per call on CPU); fewer reps keep the run bounded.
                w, it = (1, 3) if engine == "pallas" else (warmup, iters)
                case = bench_case(
                    shape, density, ranks, engine, method, n_iter,
                    warmup=w, iters=it, label=label,
                )
                cases.append(case)
                print(
                    f"{label:22s} {engine:6s} {method:11s} "
                    f"python={case['python_s']*1e3:9.2f}ms "
                    f"scan={case['scan_s']*1e3:9.2f}ms "
                    f"speedup={case['speedup']:5.2f}x "
                    f"retraces={case['retraces_during_timing']} "
                    f"AI={case['arithmetic_intensity']:.3f} "
                    f"({time.time()-t0:.1f}s)",
                    flush=True,
                )

    core_fusion = bench_core_fusion()
    print(
        f"core fusion: split={core_fusion['split_hbm_bytes']:.3g}B "
        f"fused={core_fusion['fused_hbm_bytes']:.3g}B "
        f"saving={core_fusion['bytes_saving']*100:.1f}% "
        f"parity={core_fusion['parity_relerr']:.2e}",
        flush=True,
    )

    autotune_cases = []
    if args.autotune and "pallas" in engines:
        for label, shape, density, ranks, n_iter, methods in grid:
            at = bench_autotune_case(shape, density, ranks, methods[0], n_iter)
            autotune_cases.append(at)
            print(
                f"autotune {at['label']:22s} "
                f"default={at['default_scan_s']*1e3:9.2f}ms "
                f"tuned={at['autotuned_scan_s']*1e3:9.2f}ms "
                f"speedup={at['autotune_speedup']:5.2f}x "
                f"blocks={at['tuned_blocks']}",
                flush=True,
            )

    trace_overhead = None
    if args.trace:
        trace_overhead = bench_trace_overhead()
        print(
            f"trace overhead: untraced={trace_overhead['untraced_s']*1e3:.2f}ms "
            f"traced={trace_overhead['traced_s']*1e3:.2f}ms "
            f"enabled={trace_overhead['enabled_overhead']*100:+.2f}% "
            f"disabled={trace_overhead['disabled_overhead']*100:.4f}% "
            f"({trace_overhead['spans_per_call']} spans/call, "
            f"noop={trace_overhead['noop_span_ns']:.0f}ns)",
            flush=True,
        )

    import repro.obs as obs

    payload = {
        "benchmark": "sweep_bench",
        "smoke": bool(args.smoke),
        "created_unix": int(time.time()),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "cases": cases,
        "core_fusion": core_fusion,
        "autotune_cases": autotune_cases,
        "trace_overhead": trace_overhead,
        # the whole run's counter state (plan cache, schedule builds,
        # autotune, dispatch counters) rides with every benchmark artifact
        "metrics": obs.registry.snapshot(),
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out} ({len(cases)} cases)")

    dirty = [c for c in cases if c["lint_findings"]]
    if dirty:
        print("PROGRAM CONTRACT REGRESSION: the static linter found "
              "violations in a benchmarked program:")
        for c in dirty:
            print(f"  {c['label']} {c['engine']}/{c['method']}: "
                  f"{c['lint_findings']} finding(s) — run "
                  f"`python -m repro.analysis --all-configs` for details")
        return 1
    bad_retrace = [c for c in cases if c["retraces_during_timing"] != 0]
    if bad_retrace:
        print("RETRACE REGRESSION: timed calls recompiled the sweep pipeline:")
        for c in bad_retrace:
            print(f"  {c['label']} {c['engine']}/{c['method']}: "
                  f"{c['retraces_during_timing']} retraces")
        return 1
    bad_parity = [c for c in cases if not np.isfinite(c["fit_maxdiff"])
                  or c["fit_maxdiff"] > 1e-4]
    if bad_parity:
        print("FIT PARITY REGRESSION: scan and python pipelines diverged:")
        for c in bad_parity:
            print(f"  {c['label']} {c['engine']}/{c['method']}: "
                  f"maxdiff={c['fit_maxdiff']:.2e}")
        return 1
    if core_fusion["fused_hbm_bytes"] >= core_fusion["split_hbm_bytes"]:
        print("CORE FUSION REGRESSION: the megakernel moved "
              f"{core_fusion['fused_hbm_bytes']:.3g}B >= the split path's "
              f"{core_fusion['split_hbm_bytes']:.3g}B")
        return 1
    if core_fusion["parity_relerr"] > 1e-5:
        print("CORE FUSION PARITY REGRESSION: "
              f"relerr={core_fusion['parity_relerr']:.2e}")
        return 1
    if trace_overhead is not None:
        # 0.5 ms absolute slack so shared-runner timer noise on ms-scale
        # medians cannot flake the relative gate
        slack = max(0.05 * trace_overhead["untraced_s"], 5e-4)
        if trace_overhead["traced_s"] - trace_overhead["untraced_s"] > slack:
            print(
                "TRACE OVERHEAD REGRESSION: enabled tracing cost "
                f"{trace_overhead['enabled_overhead']*100:.1f}% > 5% "
                f"({trace_overhead['spans_per_call']} spans/call)"
            )
            return 1
        if trace_overhead["disabled_overhead"] > 0.01:
            print(
                "TRACE OVERHEAD REGRESSION: the DISABLED fast path costs "
                f"{trace_overhead['disabled_overhead']*100:.2f}% > 1% "
                f"(noop span = {trace_overhead['noop_span_ns']:.0f}ns)"
            )
            return 1
    slow_tuned = [a for a in autotune_cases if a["autotune_speedup"] < 0.8]
    if slow_tuned:
        print("AUTOTUNE REGRESSION: the tuned config lost to the default "
              "beyond timing noise:")
        for a in slow_tuned:
            print(f"  {a['label']}: {a['autotune_speedup']:.2f}x "
                  f"({a['tuned_blocks']})")
        return 1
    if args.baseline:
        try:
            with open(args.baseline) as f:
                base = {
                    (c["label"], c["engine"], c["method"]): c
                    for c in json.load(f).get("cases", [])
                }
        except (OSError, ValueError) as e:
            print(f"baseline unreadable ({e}); skipping intensity gate")
            base = {}
        regressed = []
        for c in cases:
            b = base.get((c["label"], c["engine"], c["method"]))
            if b and "arithmetic_intensity" in b:
                if c["arithmetic_intensity"] < 0.9 * b["arithmetic_intensity"]:
                    regressed.append((c, b))
        if regressed:
            print("INTENSITY REGRESSION vs baseline:")
            for c, b in regressed:
                print(f"  {c['label']} {c['engine']}/{c['method']}: "
                      f"{c['arithmetic_intensity']:.3f} < 0.9 * "
                      f"{b['arithmetic_intensity']:.3f}")
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
