"""Run one benchmark cell on the chips of this machine and print its result.

    python3 bench/run.py --workload nell2.steady --seed 7 --seconds 50 --trace 0

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (see ``bench/harness.py``). The last line of
standard output is the result as one JSON object; the numbers that decide
``correct`` are the last lines of standard error. Without a TPU, or with
fewer chips than the cell asks for, it exits nonzero and prints no result.
"""
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the repository root, not this directory, heads the path: bench's modules
# are imported as the ``bench`` package and shadow nothing of the library's
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu would log under /tmp

if __name__ == "__main__":
    from bench import harness

    sys.exit(harness.main(sys.argv[1:], t0=T0))
