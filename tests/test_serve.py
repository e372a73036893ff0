"""Serving engine: static-shape generate, greedy determinism."""
import numpy as np
import jax
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serve.engine import Engine, ServeConfig


@pytest.fixture(scope="module")
def engine():
    from jax.sharding import AxisType
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("repro-100m", smoke=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return Engine(cfg, mesh, params, ServeConfig(max_seq_len=64, batch_size=2))


def test_generate_shapes(engine):
    prompts = np.random.default_rng(0).integers(0, 200, size=(2, 8)).astype(np.int32)
    out = engine.generate(prompts, max_new_tokens=6)
    assert out.shape == (2, 14)
    np.testing.assert_array_equal(out[:, :8], prompts)
    assert (out < engine.cfg.vocab_size).all()


def test_greedy_is_deterministic(engine):
    prompts = np.random.default_rng(1).integers(0, 200, size=(2, 8)).astype(np.int32)
    a = engine.generate(prompts, max_new_tokens=5)
    b = engine.generate(prompts, max_new_tokens=5)
    np.testing.assert_array_equal(a, b)


def test_decode_continuation_consistent_with_prefill(engine):
    """Greedy continuation via decode == re-prefilling the grown prompt."""
    rng = np.random.default_rng(2)
    prompts = rng.integers(0, 200, size=(2, 8)).astype(np.int32)
    out = engine.generate(prompts, max_new_tokens=3)
    out2 = engine.generate(out[:, :10].astype(np.int32), max_new_tokens=1)
    np.testing.assert_array_equal(out[:, :11], out2)
