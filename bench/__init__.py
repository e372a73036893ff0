"""The chip benchmark of the sparse Tucker system: ``python3 bench/run.py``
runs one cell of ``BENCHMARK.json``; see ``bench/harness.py``."""
