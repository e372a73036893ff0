"""The benchmark harness: everything is found by name, nothing by code.

``BENCHMARK.json`` names the cells. A cell names a configuration (its file
under ``bench/configs/``) and a traffic mix (``bench/traffic/<mix>.json``);
a configuration's ``entry`` picks how the system is driven
(``bench/systems.py``), a traffic file's ``loop`` how load is offered
(``bench/loads.py``), and every metric is a reader of its own,
``bench/metrics/<name>.py``, with ``read(ctx)`` returning a number or
``None`` when it finds nothing to read. A new configuration, mix or metric
is a new file and an entry in ``BENCHMARK.json``.

One run: check the chips, set up (inputs from the seed, and every program
the harness can warm compiled), measure for ``--seconds`` with the
persistent compile cache off (``--trace 1`` records a profiler trace of the
window and the program's spans), read the peak device memory, free the
program's state, compare the answers with the reference, and print the
result.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import warnings
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Refused(Exception):
    """The run cannot be measured here (no chip, too few chips, an unknown
    device); the harness exits nonzero without a result."""


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    cell: dict
    config: dict
    traffic: dict
    window: Any  # loads.Window
    setup_s: float
    peaks: dict
    trace: Any = None  # trace.Reduced, in a traced run
    spans: Optional[list] = None  # repro.obs SpanEvents, in a traced run
    compiles: List[tuple] = dataclasses.field(default_factory=list)  # CompileLog.events

    @property
    def completed(self) -> int:
        return sum(r.error is None for r in self.window.records)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell_parts(bench: dict, name: str, root: Path = ROOT):
    """``(cell, config, traffic)`` of the named cell."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no cell {name!r} in BENCHMARK.json (cells: {sorted(cells)})")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(root / entry["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


def metrics_for(bench: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics the cell reports."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def read_metrics(entries: List[dict], ctx: Context, root: Path = ROOT) -> Dict[str, dict]:
    out = {}
    for m in entries:
        value = reader(m["name"], root).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(correct: bool, window, metrics: dict, device: dict,
                rows, breakdown: Optional[dict] = None) -> dict:
    """The result object; the compared numbers come last."""
    out = {"correct": bool(correct), "attempted": len(window.records),
           "failed": window.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": v, "limit": lim} for name, v, lim in rows}
    return out


def chips_here(need: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < need:
        raise Refused(f"the cell needs {need} chips; JAX found {len(devices)}")
    return devices


class CompileLog:
    """Collects JAX's compile-duration events as ``(perf_counter time the
    event ended, event name, seconds)``."""

    def __init__(self) -> None:
        self.events: List[tuple] = []
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **_: Any) -> None:
        if "compile" in event:
            with self._lock:
                self.events.append((time.perf_counter(), event, float(duration)))


@contextlib.contextmanager
def fresh_compiles():
    """The persistent compile cache off, and on again afterwards. What the
    system compiles in the window it compiles there, and never loads from an
    entry that an earlier run in the checkout wrote, so every run of a cell
    does the same work; set-up and the reference still use the cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t0: Optional[float] = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    try:
        return run(args, t0)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2


def run(args, t0: float, root: Path = ROOT) -> int:
    bench = benchmark(root)
    cell, config, traffic = cell_parts(bench, args.workload, root)
    devices = chips_here(int(cell["chips"]))
    peaks = peaks_for(devices[0].device_kind, root)

    import jax
    import jax.monitoring

    from bench import check, loads, systems
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program is kept, however fast it compiled, so that a second run
    # of a cell finds all of its set-up in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # the service warns once per spec that cannot batch; that is the path
    # under test, not news
    warnings.filterwarnings("ignore", message="spec spec.engine=")
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        systems.configure(config)
        system = systems.SYSTEMS[config["entry"]](config, traffic, args.seed, args.seconds)
        system.setup()
        setup_end = time.perf_counter()
        setup_s = setup_end - t0

        with fresh_compiles():
            if args.trace:
                window, reduced = _traced(traffic, system, args.seconds, int(cell["chips"]))
            else:
                window = loads.run(traffic, system.request, args.seconds)
                reduced = None
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    in_window = [e for e in compiles.events
                 if e[0] > setup_end and e[1] == "/jax/core/compile/backend_compile_duration"]
    lat = window.latencies_s()
    late = max((r.sent - r.due for r in window.records), default=0.0)
    third = len(lat) // 3
    # the last third's median latency over the first third's: about 1 when
    # the system keeps up, growing with the backlog when it does not
    backlog = float(np.median(lat[-third:]) / np.median(lat[:third])) if third else 1.0
    print(f"bench: setup_s={setup_s} requests={len(window.records)} "
          f"failed={window.failed} window_compiles={len(in_window)} "
          f"latency_max_s={lat.max() if lat.size else 0.0} sent_late_max_s={late} "
          f"backlog={backlog}", file=sys.stderr)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": system.memory_peak()}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    ctx = Context(cell=cell, config=config, traffic=traffic, window=window,
                  setup_s=setup_s, peaks=peaks, trace=reduced,
                  spans=reduced.spans if reduced is not None else None,
                  compiles=compiles.events)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(metrics_for(bench, cell["name"], kind), ctx, root)
    if not args.trace:
        # the per-layer metrics that need no profiler, read here without one,
        # to show how far the traced run's readings of them are moved
        host = [m for m in metrics_for(bench, cell["name"], "per_layer")
                if m["source"] != "device_trace"]
        untraced = read_metrics(host, ctx, root)
        print("bench: untraced " + " ".join(f"{k}={v['value']!r}" for k, v in untraced.items()),
              file=sys.stderr)
    breakdown = reduced.breakdown() if reduced is not None else None

    t_check = time.perf_counter()
    readings = system.check(window)
    correct, rows = check.verdict(readings, config["limits"])
    print(f"bench: check_s={time.perf_counter() - t_check}", file=sys.stderr)
    for name, v, lim in rows:
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result_line(correct, window, metrics, device, rows, breakdown)),
          flush=True)
    return 0


def _traced(traffic, system, seconds, chips):
    """The window under the profiler, with the program's spans on; returns
    it and the reduced trace (whose ``spans`` are the program's)."""
    import jax

    import repro.obs as obs
    from bench import loads, trace

    obs.configure(enabled=True, ring_capacity=1 << 21)
    obs.tracer.clear()
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        anchor = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            window = loads.run(traffic, system.request, seconds)
        jax.profiler.stop_trace()
        spans = obs.tracer.events()
        obs.configure(enabled=False)
        reduced = trace.reduce(trace.find_xplane(tdir), chips, spans, anchor)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    reduced.spans = spans
    return window, reduced
