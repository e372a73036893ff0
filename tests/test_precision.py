"""Mixed-precision spec axis (ISSUE 7): bf16-compute / f32-accumulate.

``TuckerSpec.precision="bf16_fp32acc"`` casts the Kron/TTM operands to bf16
while every accumulator (one-hot scatter, MXU dot) stays f32. The contract:

* both engines accept the axis and decompose to a fit within a DOCUMENTED
  tolerance of the fp32 run (bf16 has ~3 significant decimal digits — the
  README pins |rel_error_bf16 - rel_error_f32| < 5e-2 on these shapes);
* the engines agree with EACH OTHER far more tightly than with fp32 (same
  rounding decisions, different executors);
* fp32-only features (shard, the vmapped batch program) refuse or fall
  back rather than silently computing in the wrong precision;
* the non-auto ``dtype`` axis (bfloat16/float32 storage) keeps composing.
"""
import numpy as np
import pytest

from repro import tucker
from repro.core import engine as E
from repro.sparse.generators import random_sparse_tensor

ENGINES = E.available_engines()
BF16_FIT_TOL = 5e-2  # documented in README "Kernel autotuning & mixed precision"


def _decompose(coo, engine, precision, **kw):
    kw.setdefault("n_iter", 3)
    kw.setdefault("method", "gram")
    return tucker.decompose(coo, (3, 3, 2), engine=engine,
                            precision=precision, **kw)


def test_spec_precision_validation():
    with pytest.raises(ValueError, match="precision"):
        tucker.TuckerSpec(shape=(8, 8, 8), ranks=(2, 2, 2), precision="fp16")
    with pytest.raises(ValueError, match="precision"):
        tucker.TuckerSpec(
            shape=(8, 8, 8), ranks=(2, 2, 2), precision="bf16_fp32acc",
            shard=tucker.ShardSpec(num_devices=1),
        )
    s = tucker.TuckerSpec(shape=(8, 8, 8), ranks=(2, 2, 2),
                          precision="bf16_fp32acc")
    assert not s.supports_batched_dispatch  # batch program is fp32-only


@pytest.mark.parametrize("engine", ENGINES)
def test_bf16_fit_parity_vs_fp32(engine):
    coo = random_sparse_tensor((20, 16, 12), 0.05, seed=0)
    f32 = _decompose(coo, engine, "fp32")
    b16 = _decompose(coo, engine, "bf16_fp32acc")
    assert b16.precision == "bf16_fp32acc" and f32.precision == "fp32"
    assert np.isfinite(b16.rel_error)
    assert abs(b16.rel_error - f32.rel_error) < BF16_FIT_TOL
    # the reconstruction itself stays close, not just the scalar fit
    np.testing.assert_allclose(
        np.asarray(b16.core), np.asarray(f32.core), rtol=0.1,
        atol=0.1 * np.abs(np.asarray(f32.core)).max(),
    )


@pytest.mark.skipif(len(ENGINES) < 2, reason="needs both engines")
def test_bf16_engines_agree_with_each_other():
    """xla and pallas make the SAME bf16 rounding decisions — cross-engine
    agreement is much tighter than either engine's distance to fp32."""
    coo = random_sparse_tensor((20, 16, 12), 0.05, seed=1)
    fits = {}
    for eng in ("xla", "pallas"):
        fits[eng] = _decompose(coo, eng, "bf16_fp32acc").rel_error
    assert abs(fits["xla"] - fits["pallas"]) < 1e-3


def test_bf16_batch_falls_back_sequentially():
    """batch() on a bf16 spec must not take the fp32-only vmapped program —
    it falls back to sequential calls with per-call-identical results."""
    coos = [random_sparse_tensor((14, 10, 8), 0.06, seed=s) for s in (3, 4)]
    spec = tucker.TuckerSpec(shape=(14, 10, 8), ranks=(3, 2, 2),
                             method="gram", n_iter=2, engine="xla",
                             precision="bf16_fp32acc")
    tucker.clear_plan_cache()
    plan = tucker.plan(spec)
    batched = plan.batch(coos)
    singles = [plan(c) for c in coos]
    for b, s in zip(batched, singles):
        np.testing.assert_allclose(
            np.asarray(b.core), np.asarray(s.core), rtol=1e-5, atol=1e-5
        )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_non_auto_dtype_composes_with_precision(dtype):
    """Explicit storage dtypes keep working alongside the compute-precision
    axis (bf16 storage + fp32 compute and vice versa are both legal)."""
    coo = random_sparse_tensor((14, 10, 8), 0.06, seed=5)
    res = tucker.decompose(coo, (3, 2, 2), n_iter=2, engine="xla",
                           dtype=dtype, precision="fp32")
    assert np.isfinite(res.rel_error)
    res2 = tucker.decompose(coo, (3, 2, 2), n_iter=2, engine="xla",
                            dtype="float32", precision="bf16_fp32acc")
    assert np.isfinite(res2.rel_error)


def test_result_records_precision_field():
    coo = random_sparse_tensor((12, 10, 8), 0.05, seed=6)
    res = _decompose(coo, "xla", "bf16_fp32acc", n_iter=2)
    assert res.precision == "bf16_fp32acc"
    assert res.spec.precision == "bf16_fp32acc"
