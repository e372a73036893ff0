"""Every compiled sweep program against the plain reference.

``bench/reference.py`` is sparse Tucker HOOI written from the algorithm and
independent of the program: its own Kronecker accumulation, a float64
column-pivoted Gram-Schmidt QR and a float64 core. Each case here runs one
program variant and the reference from the same key (``hooi.init_factors``
and ``reference.init_factors`` draw the same start) and holds the program to:

* the same number of sweeps;
* the fit history within ``FIT_TOL`` of the reference's, sweep by sweep;
* ``||G - G_ref|| / ||G_ref|| <= CORE_TOL``, where ``G_ref`` is the core the
  reference computes from the program's own factors.

The variants are the single-device scan program on both engines, the XLA
engine with Kron reuse, the snapshot segment program, the vmapped batch
program and the sharded program on both engines (four forced host devices,
in one subprocess; order 4 takes the chained ``kron_contrib`` +
``scatter_rows_pallas`` path on Pallas), each at tensor orders 2, 3 and 4; then the ``gram`` factor
update and ``bf16_fp32acc`` on both engines at order 3. On these tensors the
fp32 programs agree with the reference to about 1e-7 in both numbers.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro import tucker
from repro.sparse.generators import low_rank_sparse_tensor

# the plain reference is imported as the ``bench`` package from the repo root
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import reference  # noqa: E402

N_ITER = 5
FIT_TOL = 1e-5
CORE_TOL = 1e-5
# bf16 operands carry 8 bits of mantissa (2^-8 ~ 4e-3 relative per rounding)
BF16_FIT_TOL = 1e-3
BF16_CORE_TOL = 1e-2
# order -> (shape, ranks): effective ranks, so no clamping moves them
ORDERS = {
    2: ((30, 20), (4, 4)),
    3: ((24, 20, 16), (4, 3, 3)),
    4: ((10, 9, 8, 7), (3, 3, 2, 2)),
}
VARIANTS = ("scan-xla", "scan-pallas", "kron-reuse", "segments", "batch", "sharded",
            "sharded-pallas")


def _tensor(order, seed):
    shape, ranks = ORDERS[order]
    coo, _ = low_rank_sparse_tensor(shape, ranks, 0.1, seed=seed, noise=0.05)
    return coo


def _key(seed):
    return jax.random.PRNGKey(100 + seed)


def _check(res, coo, ranks, key, fit_tol=FIT_TOL, core_tol=CORE_TOL):
    x = reference.Tensor(np.asarray(coo.indices), np.asarray(coo.values),
                         coo.shape, block=4096)
    _, _, hist = reference.hooi(x, ranks, N_ITER, key)
    assert len(res.fit_history) == len(hist) == N_ITER
    np.testing.assert_allclose(res.fit_history, hist, rtol=0, atol=fit_tol)
    g_ref = reference.core_given(x, [np.asarray(f) for f in res.factors])
    core = np.asarray(res.core, np.float64)
    assert core.shape == g_ref.shape
    gap = np.linalg.norm(core - g_ref) / np.linalg.norm(g_ref)
    assert gap <= core_tol, gap


def _spec(order, **kw):
    shape, ranks = ORDERS[order]
    kw.setdefault("method", "householder")
    kw.setdefault("engine", "xla")
    return tucker.TuckerSpec(shape=shape, ranks=ranks, n_iter=N_ITER, **kw)


_SHARDED_SCRIPT = """
    import numpy as np, jax
    from repro import tucker
    from repro.sparse.generators import low_rank_sparse_tensor

    assert jax.device_count() == 4
    for order, (shape, ranks) in %(orders)r.items():
        coo, _ = low_rank_sparse_tensor(shape, ranks, 0.1, seed=order,
                                        noise=0.05)
        for variant, engine in (("sharded", "auto"), ("sharded-pallas", "pallas")):
            spec = tucker.TuckerSpec(shape=shape, ranks=ranks, n_iter=%(n_iter)d,
                                     method="householder", engine=engine,
                                     shard=tucker.ShardSpec(num_devices=4))
            res = tucker.plan(spec)(coo, key=jax.random.PRNGKey(100 + order))
            assert res.dispatches == 1
            assert res.engine == ("xla" if engine == "auto" else engine)
            np.savez(%(out)r + "/%%s-order%%d.npz" %% (variant, order),
                     core=np.asarray(res.core), hist=res.fit_history,
                     **{"f%%d" %% i: np.asarray(f) for i, f in enumerate(res.factors)})
"""


@pytest.fixture(scope="module")
def sharded(tmp_path_factory):
    """The sharded program at every order, on the XLA engine (``auto`` on a
    CPU) and on Pallas, run once in one subprocess over four forced host
    devices (this process keeps its one device)."""
    out = str(tmp_path_factory.mktemp("sharded"))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = textwrap.dedent(_SHARDED_SCRIPT % {
        "orders": ORDERS, "n_iter": N_ITER, "out": out})
    run = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    return out


class _Saved:
    def __init__(self, path):
        z = np.load(path)
        self.core = z["core"]
        self.fit_history = z["hist"]
        self.factors = [z[f"f{i}"] for i in range(len(z.files) - 2)]


@pytest.mark.parametrize("order", sorted(ORDERS))
@pytest.mark.parametrize("variant", VARIANTS)
def test_program_matches_reference(variant, order, tmp_path, request):
    ranks = ORDERS[order][1]
    coo, key = _tensor(order, order), _key(order)
    if variant.startswith("sharded"):
        d = request.getfixturevalue("sharded")
        res = _Saved(os.path.join(d, f"{variant}-order{order}.npz"))
    elif variant == "batch":
        p = tucker.plan(_spec(order))
        assert p.supports_batched_dispatch  # the vmapped program, not a loop
        coos = [coo, _tensor(order, order + 10)]
        keys = [key, _key(order + 10)]
        results = p.batch(coos, keys=keys)
        assert results[0].dispatches == 1
        for c, k, r in zip(coos[1:], keys[1:], results[1:]):
            _check(r, c, ranks, k)
        res = results[0]
    else:
        kw = {
            "scan-xla": {},
            "scan-pallas": {"engine": "pallas"},
            "kron-reuse": {"use_kron_reuse": True},
            "segments": {"snapshot": tucker.SnapshotSpec(
                every_n_sweeps=2, directory=str(tmp_path))},
        }[variant]
        p = tucker.plan(_spec(order, **kw))
        res = p(coo, key=key)
        if variant == "kron-reuse":
            assert sorted(p.engine.kron_plans) == list(range(order))
        if variant == "segments":
            assert res.dispatches == 3  # 5 sweeps in segments of 2, 2, 1
    _check(res, coo, ranks, key)


@pytest.mark.parametrize("engine", ["xla", "pallas"])
def test_gram_matches_reference(engine):
    ranks = ORDERS[3][1]
    coo, key = _tensor(3, 3), _key(3)
    res = tucker.plan(_spec(3, engine=engine, method="gram"))(coo, key=key)
    _check(res, coo, ranks, key)


@pytest.mark.parametrize("engine", ["xla", "pallas"])
def test_bf16_matches_reference(engine):
    ranks = ORDERS[3][1]
    coo, key = _tensor(3, 3), _key(3)
    res = tucker.plan(_spec(3, engine=engine, precision="bf16_fp32acc"))(
        coo, key=key)
    assert res.precision == "bf16_fp32acc"
    _check(res, coo, ranks, key, fit_tol=BF16_FIT_TOL, core_tol=BF16_CORE_TOL)
