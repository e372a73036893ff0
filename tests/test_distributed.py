"""Distributed paths on a multi-device host mesh (subprocess: tests keep the
main process at 1 device per the dry-run isolation rule)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

# Each test compiles a model in an 8-device subprocess: minutes of CPU time.
pytestmark = pytest.mark.slow


def _run(code: str, ndev: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_train_step_shards_on_multi_device():
    got = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import model as M
        from repro.models.sharding import RULES_TRAIN
        from repro.train.step import make_train_step, train_state_specs
        from repro.optim import adamw
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        cfg = get_config("yi-6b", smoke=True)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        pshard = M.param_shardings(cfg, RULES_TRAIN, mesh)
        params = jax.tree_util.tree_map(jax.device_put, params, pshard)
        opt = adamw.init(params)
        step = jax.jit(make_train_step(cfg, mesh, RULES_TRAIN))
        B, S = 4, 64
        batch = {"tokens": jnp.zeros((B, S), jnp.int32),
                 "labels": jnp.zeros((B, S), jnp.int32)}
        p2, o2, m = step(params, opt, batch)
        print(float(m["loss"]))
    """)
    assert float(got.strip()) > 0


def test_moe_ep_all_to_all_multi_device():
    got = _run("""
        import jax, numpy as np, jax.numpy as jnp, dataclasses
        from repro.configs import get_config
        from repro.models import model as M
        from repro.models.moe import moe_block
        from repro.models.sharding import DEFAULT_RULES
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        cfg = dataclasses.replace(get_config("granite-moe-1b-a400m", smoke=True),
                                  capacity_factor=8.0, dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        p = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)
        y, aux = jax.jit(lambda x: moe_block(cfg, mesh, DEFAULT_RULES, x,
            p["router"], p["moe_wi"], p["moe_wg"], p["moe_wo"]))(x)
        # single-device reference
        mesh1 = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        y1, _ = moe_block(cfg, mesh1, DEFAULT_RULES, x,
            p["router"], p["moe_wi"], p["moe_wg"], p["moe_wo"])
        print(float(np.abs(np.asarray(y) - np.asarray(y1)).max()))
    """)
    assert float(got.strip()) < 2e-3


def test_checkpoint_elastic_reshard_across_meshes():
    got = _run("""
        import jax, numpy as np, jax.numpy as jnp, tempfile
        from repro.checkpoint.manager import CheckpointManager
        from repro.configs import get_config
        from repro.models import model as M
        from repro.models.sharding import RULES_TRAIN
        cfg = get_config("yi-6b", smoke=True)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d)
        mgr.save(3, params)
        # restore onto a (4,2) mesh with full shardings
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        shard = M.param_shardings(cfg, RULES_TRAIN, mesh)
        restored, step, _ = mgr.restore(params, shardings=shard)
        ok = all(np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
                 for a, b in zip(jax.tree_util.tree_leaves(params),
                                  jax.tree_util.tree_leaves(restored)))
        print(step, ok)
    """)
    step, ok = got.split()
    assert step == "3" and ok == "True"
