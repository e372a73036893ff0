"""Run tiny cells on the CPU, with the look for a chip skipped and a fault
planted under the timed path; after each run print ``{"case": [fault, cell,
overrides], "rc": ..., "result": <the result line>}`` as one line.

    python run_fault.py '[["<fault>", "<cell>", {<overrides of the tiny plan config>}], ...]'

Faults: ``none``; ``unchanged`` (answers carry the starting factors, as if no
sweep had updated them); ``half`` (half of the nonzeros left out);
``altered`` (each answer's core changed by one part in a thousand where it
is produced); ``nopsum`` (the sum across chips left out).
"""
import contextlib
import dataclasses
import io
import json
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]

import jax  # noqa: E402

import repro.utils.compile_cache as cc  # noqa: E402
import tiny  # noqa: E402
from bench import harness  # noqa: E402
from repro.core.coo import SparseCOO  # noqa: E402
from repro.tucker import planning  # noqa: E402


def _half(coo):
    n = coo.nnz // 2
    return SparseCOO(coo.indices[:n], coo.values[:n], coo.shape)


def plant(fault: str):
    """Plant ``fault``; returns the function that takes it out again."""
    call, batch, psum = planning.TuckerPlan.__call__, planning.TuckerPlan.batch, jax.lax.psum

    def after(plan, res, key):
        if fault == "unchanged":
            return dataclasses.replace(res, factors=list(plan._init_factors(key, None)))
        if fault == "altered":
            return dataclasses.replace(res, core=res.core * 1.001)
        return res

    def new_call(self, x, key=None, **kw):
        x = _half(x) if fault == "half" else x
        return after(self, call(self, x, key=key, **kw), key)

    def new_batch(self, coos, keys=None, pad_nnz_to=None):
        coos = [_half(c) for c in coos] if fault == "half" else coos
        keys = list(keys) if keys is not None else [None] * len(coos)
        out = batch(self, coos, keys=keys, pad_nnz_to=pad_nnz_to)
        return [after(self, r, k) for r, k in zip(out, keys)]

    planning.TuckerPlan.__call__ = new_call
    planning.TuckerPlan.batch = new_batch
    if fault == "nopsum":
        jax.lax.psum = lambda x, axis_name, **kw: x

    def unplant():
        planning.TuckerPlan.__call__, planning.TuckerPlan.batch = call, batch
        jax.lax.psum = psum

    return unplant


def main(argv) -> int:
    harness.chips_here = lambda need: jax.devices()[:need]
    harness.peaks_for = lambda kind, root=None: {"flops_per_s": 197e12,
                                                 "hbm_bytes_per_s": 819e9}
    cc.enable_compile_cache = lambda: ""
    for fault, cell, plan in json.loads(argv[0]):
        root = tiny.make(Path(tempfile.mkdtemp()), plan=plan)
        args = harness.parse(["--workload", cell, "--seed", "2718281828", "--seconds",
                              "0.5", "--trace", "0"])
        unplant = plant(fault)
        out = io.StringIO()
        try:
            with contextlib.redirect_stdout(out):
                rc = harness.run(args, 0.0, root)
        finally:
            unplant()
        lines = out.getvalue().strip().splitlines()
        print(json.dumps({"case": [fault, cell, plan], "rc": rc,
                          "result": json.loads(lines[-1]) if lines else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
