import contextlib
import io
import json
import os
import sys

import pytest

# the benchmark is imported as the ``bench`` package from the repository root
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if sys.path[:1] != [ROOT]:
    sys.path.insert(0, ROOT)


@pytest.fixture
def cpu_run(monkeypatch):
    """Run the harness here on the CPU: the look for a chip returns the CPU
    devices, the peaks are the v5e's, the persistent compile cache stays off,
    and the JAX settings a run changes are put back afterwards. Returns a
    function ``(root, workload, seconds) -> (rc, result, stderr)``."""
    import jax

    import repro.utils.compile_cache as cc
    from bench import harness

    def chips(need):
        if len(jax.devices()) < need:
            raise harness.Refused(f"needs {need} devices")
        return jax.devices()[:need]

    monkeypatch.setattr(harness, "chips_here", chips)
    monkeypatch.setattr(harness, "peaks_for",
                        lambda kind, root=None: {"flops_per_s": 197e12,
                                                 "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")
    saved = {k: getattr(jax.config, k) for k in (
        "jax_default_matmul_precision", "jax_persistent_cache_min_compile_time_secs")}

    def run(root, workload, seconds=0.5, seed=12345678901, trace=0):
        out, err = io.StringIO(), io.StringIO()
        args = harness.parse(["--workload", workload, "--seed", str(seed),
                              "--seconds", str(seconds), "--trace", str(trace)])
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = harness.run(args, 0.0, root)
        lines = out.getvalue().strip().splitlines()
        return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()

    yield run
    from repro import tucker

    tucker.clear_plan_cache()
    for k, v in saved.items():
        jax.config.update(k, v)
