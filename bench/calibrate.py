"""Readings of the numbers that decide ``correct``, for the program as a
cell runs it and for its lower-precision control, over many seeds in one
process. The limits in ``bench/configs/*.json`` are set from these.

    python3 bench/calibrate.py --workload nell2.steady --seeds 1-12 --variant program
    python3 bench/calibrate.py --workload nell2.steady --seeds 101-103 --variant control

``program`` runs the configuration as stated; ``control`` first applies the
configuration's ``control`` overrides (a lower matmul precision). Each seed
goes through the cell's own set-up and timed path, with a window of
``--seconds`` at the cell's own load, then the comparison. One JSON line per
seed. The benchmark's own runs never run this; it needs the chips the cell
asks for.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def seeds(spec: str):
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 5,9,40")
    ap.add_argument("--variant", choices=("program", "control"), default="program")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    from bench import harness, loads, systems
    from repro.utils.compile_cache import enable_compile_cache

    bench = harness.benchmark()
    cell, config, traffic = harness.cell_parts(bench, args.workload)
    harness.chips_here(int(cell["chips"]))
    enable_compile_cache()
    if args.variant == "control":
        config = dict(config, **config["control"])
    systems.configure(config)
    for seed in seeds(args.seeds):
        t0 = time.perf_counter()
        system = systems.SYSTEMS[config["entry"]](config, traffic, seed, args.seconds)
        system.setup()
        setup_s = time.perf_counter() - t0
        with harness.fresh_compiles():
            window = loads.run(traffic, system.request, args.seconds)
        t1 = time.perf_counter()
        readings = system.check(window)
        print(json.dumps({"workload": cell["name"], "variant": args.variant, "seed": seed,
                          "readings": readings, "answers": len(window.records),
                          "failed": window.failed, "setup_s": setup_s,
                          "check_s": time.perf_counter() - t1}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
