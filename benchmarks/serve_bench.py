"""TuckerService benchmark (micro-batching vs sequential) -> BENCH_serve.json.

Times the serving plane end-to-end: N mixed-nnz decomposition requests
through a ``TuckerService`` at several ``max_batch`` settings, against the
baseline every caller wrote before the service existed — a sequential
``tucker.decompose`` loop (one warm plan call per request). Records, per
batch size, throughput, p50/p99 end-to-end latency, and the dispatch count,
i.e. the amortization trajectory every future serving PR is measured
against:

  BENCH_serve.json = {
    "benchmark": "serve_bench", "smoke": bool, "jax": .., "backend": ..,
    "workload": {"shape", "ranks", "method", "n_iter", "n_requests",
                  "nnz_values", "bucket"},
    "sequential": {"total_s", "throughput_rps", "p50_ms", "p99_ms",
                    "dispatches"},
    "cases": [{
       "max_batch", "total_s", "throughput_rps",
       "speedup_vs_sequential",        # service rps / sequential rps
       "p50_ms", "p99_ms",             # end-to-end submit->result latency
       "dispatches", "dispatch_bound", # bound = ceil(N / max_batch)
       "requests_per_dispatch", "flushes", "padding_overhead",
       "parity_max_core_diff",         # service vs sequential results
    }, ...]
  }

Acceptance gates (exit nonzero on violation; CI runs ``--smoke``):

  * parity: every service result allclose (1e-4) to its sequential twin;
  * amortization: dispatches <= ceil(N / max_batch) for every batched case;
  * throughput: >= 2x the sequential loop at max_batch >= 8 (XLA engine).

The SLO phase (``--slo`` runs it alone; a full run appends it) drives a
mixed ragged-nnz MULTI-TENANT load — several specs, several densities, so
several BatchKeys — through the same service twice: once with
``max_inflight_flushes=1`` (the sequential-flush baseline this PR replaces)
and once with a concurrent executor pool. Its gates:

  * bitwise parity: per-request results of the concurrent run are
    ``np.array_equal`` to the sequential-flush run (same plans, same batch
    composition, same compiled programs — concurrency must not change one
    bit of output);
  * amortization unchanged: both runs issue the same dispatch count;
  * overlap: the Perfetto trace of the concurrent run contains >= 2
    simultaneously-open ``serve.dispatch`` spans (the executors genuinely
    overlap device waits, even on one core);
  * throughput: concurrent >= 1.5x sequential-flush where the host has >= 2
    cores to overlap onto (CI forces a multi-device host); on a single-core
    host parallel speedup is physically impossible, so the gate degrades to
    bounded-regression (>= 0.75x) and says so;
  * p99 SLO: concurrent p99 <= slo_factor x the sequential-flush p99
    (1.0 when parallel — the pool must shrink the tail, 1.5 single-core).

Both timed runs are best-of-3: results are bitwise-deterministic, so trials
differ only by scheduler noise and the fastest trial is the cleanest
measurement.

``BENCH_serve.json`` grows a ``"slo"`` section with the concurrency
trajectory (both runs' throughput/p99, speedup, overlap depth, and the
adaptive-policy demo's adaptation counts + final per-key limits).

    PYTHONPATH=src:. python benchmarks/serve_bench.py \\
        [--smoke] [--slo] [--out PATH] [--trace-out PATH]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from typing import Optional

import numpy as np


def build_workload(smoke: bool):
    """Mixed-nnz requests that still share ONE nnz bucket: the dispatch gate
    below (ceil(N / max_batch)) assumes one queue, so the bucket base is
    chosen to cover the largest request. n_requests is a multiple of every
    benchmarked batch size, so steady-state flushes are all 'full'."""
    from repro import tucker
    from repro.sparse.generators import random_sparse_tensor

    spec = tucker.TuckerSpec(
        shape=(20, 16, 12), ranks=(3, 3, 2), method="gram", n_iter=3
    )
    n_requests = 48 if smoke else 192
    densities = [0.02, 0.03, 0.04]  # ragged nnz; one shared bucket, sized below
    coos = [
        random_sparse_tensor(spec.shape, densities[i % len(densities)],
                             seed=1000 + i)
        for i in range(n_requests)
    ]
    return spec, coos


def bench_sequential(spec, coos, plan) -> dict:
    """The baseline loop: one warm ``plan(coo)`` call per request."""
    from repro.core import hooi

    lat = []
    d0 = sum(hooi.SWEEP_DISPATCH_COUNTS.values())
    t_start = time.perf_counter()
    results = []
    for c in coos:
        t0 = time.perf_counter()
        results.append(plan(c))
        lat.append((time.perf_counter() - t0) * 1e3)
    total = time.perf_counter() - t_start
    return {
        "total_s": total,
        "throughput_rps": len(coos) / total,
        "p50_ms": float(np.percentile(lat, 50)),
        "p99_ms": float(np.percentile(lat, 99)),
        "dispatches": sum(hooi.SWEEP_DISPATCH_COUNTS.values()) - d0,
    }, results


def bench_service(spec, coos, max_batch: int, bucket_base: int) -> dict:
    from repro.serve import ServiceConfig, TuckerService

    cfg = ServiceConfig(
        max_batch=max_batch,
        # generous: the submit burst lands whole, so every steady-state
        # flush is 'full' — the tail (N % max_batch == 0) included.
        max_wait_ms=200.0,
        bucket_base=bucket_base,
    )
    with TuckerService(cfg) as svc:
        t_start = time.perf_counter()
        tickets = [svc.submit_coo(c, spec) for c in coos]
        results = [t.result(timeout=600) for t in tickets]
        total = time.perf_counter() - t_start
        snap = svc.metrics.snapshot()
    lat = [r.timing.total_ms for r in results]
    return {
        "max_batch": max_batch,
        "total_s": total,
        "throughput_rps": len(coos) / total,
        "p50_ms": float(np.percentile(lat, 50)),
        "p99_ms": float(np.percentile(lat, 99)),
        "dispatches": snap["dispatches"],
        "dispatch_bound": math.ceil(len(coos) / max_batch),
        "requests_per_dispatch": snap["requests_per_dispatch"],
        "flushes": snap["flushes"],
        "padding_overhead": snap["padding_overhead"],
    }, results


def build_slo_workload(smoke: bool):
    """Mixed ragged-nnz MULTI-TENANT load: four tenants (distinct specs ->
    distinct plans -> distinct BatchKeys) x three densities, interleaved
    round-robin. Per-tenant request counts are exact multiples of the batch
    size and ``max_wait_ms`` is generous, so every flush pops exactly FULL —
    batch composition is deterministic FIFO per key no matter how executors
    race, which is what makes the bitwise-parity gate meaningful."""
    from repro import tucker
    from repro.sparse.generators import random_sparse_tensor

    tenants = [
        tucker.TuckerSpec(shape=(20, 16, 12), ranks=r, method="gram", n_iter=3)
        for r in [(3, 3, 2), (4, 2, 2), (2, 3, 3), (3, 2, 3)]
    ]
    densities = [0.02, 0.03, 0.04]
    per_tenant = 16 if smoke else 24
    coos = {
        ti: [
            random_sparse_tensor(
                tenants[ti].shape, densities[i % len(densities)],
                seed=2000 + 97 * ti + i,
            )
            for i in range(per_tenant)
        ]
        for ti in range(len(tenants))
    }
    reqs = [
        (tenants[ti], coos[ti][i])
        for i in range(per_tenant)
        for ti in range(len(tenants))
    ]
    return tenants, reqs


def bench_slo_run(reqs, inflight: int, bucket: int, max_batch: int,
                  adaptive_target_p99_ms=None):
    """One multi-tenant pass at a given executor-pool width."""
    from repro.serve import ServiceConfig, TuckerService

    cfg = ServiceConfig(
        max_batch=max_batch,
        max_wait_ms=60_000.0,  # full-only flushes: deterministic composition
        bucket_base=bucket,
        max_inflight_flushes=inflight,
        adaptive_target_p99_ms=adaptive_target_p99_ms,
    )
    with TuckerService(cfg) as svc:
        t_start = time.perf_counter()
        tickets = [svc.submit_coo(c, s) for s, c in reqs]
        results = [t.result(timeout=600) for t in tickets]
        total = time.perf_counter() - t_start
        snap = svc.metrics.snapshot()
    lat = [r.timing.total_ms for r in results]
    return {
        "max_inflight_flushes": inflight,
        "total_s": total,
        "throughput_rps": len(reqs) / total,
        "p50_ms": float(np.percentile(lat, 50)),
        "p99_ms": float(np.percentile(lat, 99)),
        "dispatches": snap["dispatches"],
        "requests_per_dispatch": snap["requests_per_dispatch"],
    }, results, snap


def max_open_dispatch_spans(tracer) -> int:
    """Peak number of simultaneously-open serve.dispatch spans in the
    tracer ring — >= 2 proves flushes overlapped in wall-clock."""
    intervals = [
        (ev.t0, ev.t1) for ev in tracer.events() if ev.name == "serve.dispatch"
    ]
    edges = [(t0, 1) for t0, _ in intervals] + [(t1, -1) for _, t1 in intervals]
    open_now = peak = 0
    for _, delta in sorted(edges):  # close before open on exact ties
        open_now += delta
        peak = max(peak, open_now)
    return peak


def run_slo_phase(smoke: bool, trace_out: Optional[str]):
    """Concurrent-vs-sequential-flush comparison + gates; returns
    (payload_section, failures)."""
    import repro.obs as obs

    from repro.sparse.layout import bucket_nnz

    failures = []
    tenants, reqs = build_slo_workload(smoke)
    max_nnz = max(c.nnz for _, c in reqs)
    bucket = bucket_nnz(max_nnz, base=max_nnz)
    max_batch = 8
    host_parallelism = len(os.sched_getaffinity(0)) if hasattr(
        os, "sched_getaffinity") else (os.cpu_count() or 1)
    parallel_host = host_parallelism >= 2
    # a wide pool on a single core just thrashes the scheduler; two executors
    # are enough to prove wall-clock overlap without drowning in context
    # switches
    inflight = 4 if parallel_host else 2
    # single-core hosts cannot speed up compute-bound flushes by running
    # them concurrently — gate bounded-regression there (GIL/lock contention
    # costs real throughput and tail), the real bars where the host can
    # actually overlap: 1.5x throughput and a p99 no worse than the
    # sequential-flush baseline
    speedup_gate = 1.5 if parallel_host else 0.75
    slo_factor = 1.0 if parallel_host else 1.5

    # warm every tenant's plan + batched program outside the timed runs
    bench_slo_run(reqs[: max_batch * len(tenants)], inflight, bucket,
                  max_batch)

    # tracing on for BOTH timed runs: symmetric overhead, fair comparison.
    # Best-of-N on each side — results are bitwise-deterministic, so trials
    # differ only by scheduler noise, and the minimum wall-clock is the
    # least-perturbed measurement (run-to-run variance on a contended host
    # dwarfs the effect under test otherwise).
    n_trials = 3
    obs.configure(enabled=True, ring_capacity=65536)
    seq = seq_results = None
    for _ in range(n_trials):
        obs.tracer.clear()
        s, s_res, _ = bench_slo_run(reqs, 1, bucket, max_batch)
        if seq is None or s["total_s"] < seq["total_s"]:
            seq, seq_results = s, s_res
    conc = conc_results = conc_snap = None
    overlap = n_spans = 0
    for _ in range(n_trials):
        obs.tracer.clear()
        c, c_res, c_snap = bench_slo_run(reqs, inflight, bucket, max_batch)
        if conc is None or c["total_s"] < conc["total_s"]:
            conc, conc_results, conc_snap = c, c_res, c_snap
            overlap = max_open_dispatch_spans(obs.tracer)
            n_spans = (obs.tracer.export_perfetto(trace_out)
                       if trace_out else 0)
    obs.configure(enabled=False)

    speedup = conc["throughput_rps"] / seq["throughput_rps"]
    p99_slo_ms = slo_factor * seq["p99_ms"]
    bitwise = all(
        np.array_equal(np.asarray(a.core), np.asarray(b.core))
        and all(
            np.array_equal(np.asarray(fa), np.asarray(fb))
            for fa, fb in zip(a.factors, b.factors)
        )
        for a, b in zip(seq_results, conc_results)
    )
    print(
        f"slo: seq-flush {seq['throughput_rps']:8.1f} req/s "
        f"p99={seq['p99_ms']:.2f}ms d={seq['dispatches']} | "
        f"concurrent {conc['throughput_rps']:8.1f} req/s "
        f"p99={conc['p99_ms']:.2f}ms d={conc['dispatches']} | "
        f"{speedup:.2f}x (gate {speedup_gate}x, "
        f"host_parallelism={host_parallelism}) "
        f"overlap={overlap} bitwise={bitwise}",
        flush=True,
    )

    if not bitwise:
        failures.append("slo: concurrent results are not bitwise-identical "
                        "to the sequential-flush run")
    if conc["dispatches"] != seq["dispatches"]:
        failures.append(
            f"slo: dispatch count changed under concurrency "
            f"({conc['dispatches']} vs {seq['dispatches']})"
        )
    if overlap < 2:
        failures.append(
            f"slo: peak simultaneously-open serve.dispatch spans {overlap} "
            f"< 2 — flushes never overlapped"
        )
    if speedup < speedup_gate:
        failures.append(
            f"slo: concurrent throughput {speedup:.2f}x sequential-flush "
            f"< {speedup_gate}x gate (host_parallelism={host_parallelism})"
        )
    if conc["p99_ms"] > p99_slo_ms:
        failures.append(
            f"slo: concurrent p99 {conc['p99_ms']:.2f}ms > SLO "
            f"{p99_slo_ms:.2f}ms ({slo_factor}x sequential-flush p99)"
        )

    # adaptive-policy demo: an unattainable target must narrow the limits
    # (trajectory recorded, no parity gate — adaptation changes composition).
    # max_batch=2 gives each key enough flushes to reach the policy's
    # evaluation period.
    adaptive, _, adaptive_snap = bench_slo_run(
        reqs, inflight, bucket, 2, adaptive_target_p99_ms=1e-6
    )
    if not adaptive_snap["adaptations"].get("narrow"):
        failures.append("slo: adaptive policy never narrowed under an "
                        "unattainable p99 target")

    section = {
        "max_batch": max_batch,
        "n_tenants": len(tenants),
        "n_requests": len(reqs),
        "bucket": bucket,
        "host_parallelism": host_parallelism,
        "max_inflight_flushes": inflight,
        "n_trials": n_trials,
        "sequential_flush": seq,
        "concurrent": conc,
        "speedup_concurrent_vs_sequential_flush": speedup,
        "speedup_gate": speedup_gate,
        "p99_slo_ms": p99_slo_ms,
        "p99_ratio": conc["p99_ms"] / seq["p99_ms"],
        "overlap_max_open_dispatch_spans": overlap,
        "perfetto_spans_exported": n_spans,
        "bitwise_parity": bool(bitwise),
        "queue_depth_final": conc_snap["queue_depth"],
        "inflight_final": conc_snap["inflight_flushes"],
        "adaptive_demo": {
            "target_p99_ms": 1e-6,
            "throughput_rps": adaptive["throughput_rps"],
            "p99_ms": adaptive["p99_ms"],
            "adaptations": adaptive_snap["adaptations"],
        },
    }
    return section, failures


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="fewer requests / batch sizes (CI gate)")
    ap.add_argument("--slo", action="store_true",
                    help="run ONLY the concurrency SLO phase (serve-slo CI)")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--trace-out", default="serve_slo_trace.json",
                    help="Perfetto trace of the concurrent SLO run")
    args = ap.parse_args(argv)

    import jax

    from benchmarks.common import registry_snapshot
    from repro import tucker
    from repro.sparse.layout import bucket_nnz
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.slo:
        slo_section, failures = run_slo_phase(args.smoke, args.trace_out)
        payload = {
            "benchmark": "serve_bench",
            "smoke": bool(args.smoke),
            "slo_only": True,
            "created_unix": int(time.time()),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "devices": jax.device_count(),
            "slo": slo_section,
            "metrics": registry_snapshot(),
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {args.out} (slo phase only)")
        if failures:
            print("SERVE BENCH GATE FAILURES:")
            for msg in failures:
                print(f"  {msg}")
            return 1
        return 0

    spec, coos = build_workload(args.smoke)
    nnz_values = sorted({c.nnz for c in coos})
    # one bucket sized to the workload: covers every request (so the dispatch
    # bound holds) without the up-to-growth-x padded compute a mis-sized
    # bucket base costs — the tuning note the README's serving section makes.
    bucket_base = bucket_nnz(max(nnz_values), base=max(nnz_values))
    batch_sizes = (4, 8) if args.smoke else (2, 4, 8, 16)
    assert all(len(coos) % b == 0 for b in batch_sizes)

    plan = tucker.plan(spec)
    for c in coos[: len(nnz_values) * 2]:
        plan(c)  # warm the per-nnz sequential programs
    seq, seq_results = bench_sequential(spec, coos, plan)
    print(
        f"sequential: {seq['throughput_rps']:8.1f} req/s "
        f"p50={seq['p50_ms']:.2f}ms p99={seq['p99_ms']:.2f}ms "
        f"dispatches={seq['dispatches']}",
        flush=True,
    )

    cases = []
    failures = []
    for b in batch_sizes:
        # warmup pass compiles the (k=b, bucket) program outside the timing
        _case, _ = bench_service(spec, coos[: 2 * b], b, bucket_base)
        case, results = bench_service(spec, coos, b, bucket_base)
        case["speedup_vs_sequential"] = (
            case["throughput_rps"] / seq["throughput_rps"]
        )
        diffs = [
            float(np.abs(np.asarray(r.core) - np.asarray(s.core)).max())
            for r, s in zip(results, seq_results)
        ]
        case["parity_max_core_diff"] = max(diffs)
        cases.append(case)
        print(
            f"max_batch={b:3d}: {case['throughput_rps']:8.1f} req/s "
            f"({case['speedup_vs_sequential']:4.2f}x) "
            f"p50={case['p50_ms']:.2f}ms p99={case['p99_ms']:.2f}ms "
            f"dispatches={case['dispatches']}/{case['dispatch_bound']} "
            f"pad={case['padding_overhead']:.2f}x",
            flush=True,
        )
        if case["parity_max_core_diff"] > 1e-4:
            failures.append(
                f"max_batch={b}: parity violation "
                f"(max core diff {case['parity_max_core_diff']:.2e})"
            )
        if case["dispatches"] > case["dispatch_bound"]:
            failures.append(
                f"max_batch={b}: {case['dispatches']} dispatches > bound "
                f"{case['dispatch_bound']} (micro-batching regressed)"
            )
        if b >= 8 and case["speedup_vs_sequential"] < 2.0:
            failures.append(
                f"max_batch={b}: {case['speedup_vs_sequential']:.2f}x < 2x "
                f"sequential throughput (amortization regressed)"
            )

    slo_section, slo_failures = run_slo_phase(args.smoke, args.trace_out)
    failures.extend(slo_failures)

    payload = {
        "benchmark": "serve_bench",
        "smoke": bool(args.smoke),
        "created_unix": int(time.time()),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "workload": {
            "shape": list(spec.shape),
            "ranks": list(spec.ranks),
            "method": spec.method,
            "n_iter": spec.n_iter,
            "n_requests": len(coos),
            "nnz_values": nnz_values,
            "bucket": bucket_base,
        },
        "sequential": seq,
        "cases": cases,
        "slo": slo_section,
        "metrics": registry_snapshot(),
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out} ({len(cases)} cases)")

    if failures:
        print("SERVE BENCH GATE FAILURES:")
        for msg in failures:
            print(f"  {msg}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
