"""AdamW + schedule + ZeRO spec + Tucker-QRP gradient compression."""
import numpy as np
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.optim import adamw
from repro.optim.compression import (
    CompressionConfig, compress_grads_for_slow_axis, compress_matrix,
    compression_ratio_matrix, decompress_matrix,
)


def test_adamw_converges_on_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=1, total_steps=200,
                            weight_decay=0.0, grad_clip=0.0)
    target = jnp.asarray(np.random.default_rng(0).standard_normal((4, 4)), jnp.float32)
    params = {"w": jnp.zeros((4, 4), jnp.float32)}
    opt = adamw.init(params)
    for _ in range(150):
        grads = {"w": params["w"] - target}
        params, opt, _ = adamw.apply(cfg, grads, opt)
    assert float(jnp.max(jnp.abs(params["w"] - target))) < 0.05


def test_schedule_warmup_and_decay():
    cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    lrs = [float(adamw.schedule(cfg, jnp.int32(s))) for s in (0, 5, 10, 100)]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(5e-4, rel=1e-3)
    assert lrs[2] == pytest.approx(1e-3, rel=1e-3)
    assert lrs[3] == pytest.approx(1e-4, rel=1e-2)


def test_grad_clip_bounds_update():
    cfg = adamw.AdamWConfig(lr=1.0, grad_clip=1.0, warmup_steps=0)
    params = {"w": jnp.zeros((8,), jnp.float32)}
    opt = adamw.init(params)
    huge = {"w": jnp.full((8,), 1e6, jnp.float32)}
    _, _, metrics = adamw.apply(cfg, huge, opt)
    assert float(metrics["grad_norm"]) > 1e6  # reported pre-clip


def test_bf16_master_fp32_roundtrip():
    cfg = adamw.AdamWConfig(lr=1e-4, warmup_steps=0)
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    opt = adamw.init(params)
    assert opt.master["w"].dtype == jnp.float32
    g = {"w": jnp.full((4,), 0.5, jnp.bfloat16)}
    params2, opt2, _ = adamw.apply(cfg, g, opt)
    assert params2["w"].dtype == jnp.bfloat16
    assert opt2.master["w"].dtype == jnp.float32


def test_zero_spec_adds_fsdp_axis():
    # 1 CPU device: a (1,1) mesh exercises the spec logic (axis size 1
    # always divides); multi-device behaviour is covered in test_distributed.
    import jax
    from jax.sharding import AxisType
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    from repro.models.sharding import ShardingRules
    rules = ShardingRules().replace(fsdp=("data",))
    s = adamw.zero_spec(P(None, "model"), (64, 32), mesh, rules)
    assert s == P("data", "model")
    # size-1 axis divides everything; real divisibility guards are covered
    # by test_distributed on a multi-device mesh
    s2 = adamw.zero_spec(P(None, None), (63, 31), mesh, rules)
    assert s2 == P("data", None)
    # won't double-shard if fsdp axis already used
    s3 = adamw.zero_spec(P("data", None), (64, 32), mesh, rules)
    assert s3 == P("data", None)


# ---- paper-technique gradient compression --------------------------------


def test_compression_exact_for_low_rank():
    rng = np.random.default_rng(0)
    g = (rng.standard_normal((64, 8)) @ rng.standard_normal((8, 48))).astype(np.float32)
    q, p = compress_matrix(jnp.asarray(g), rank=8)
    np.testing.assert_allclose(np.asarray(decompress_matrix(q, p)), g, atol=1e-3)


def test_compression_error_feedback_recovers():
    """With error feedback, the *sum* of compressed updates converges to the
    sum of true gradients (PowerSGD property)."""
    rng = np.random.default_rng(1)
    g_true = rng.standard_normal((32, 32)).astype(np.float32)
    cfg = CompressionConfig(rank=4, min_elements=1)
    err = None
    acc = np.zeros_like(g_true)
    for _ in range(40):
        grads = {"w": jnp.asarray(g_true)}
        red, err = compress_grads_for_slow_axis(grads, cfg, err, axis_present=False)
        acc += np.asarray(red["w"])
    # average delivered gradient ~ true gradient
    np.testing.assert_allclose(acc / 40, g_true, atol=0.35 * np.abs(g_true).max())


def test_compression_ratio():
    assert compression_ratio_matrix(4096, 11008, 64) > 30
