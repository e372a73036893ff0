"""Per-kernel allclose vs ref.py oracles: shape/dtype sweeps (deliverable c)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.coo import SparseCOO
from repro.kernels import kron_kernel, ops, ref
from repro.kernels.kron_kernel import build_scatter_plan, scatter_rows_pallas
from repro.sparse.generators import random_sparse_tensor

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "l,i3,r3", [(1024, 32, 32), (1024, 64, 32), (1024, 128, 32),
                (1024, 256, 32), (100, 300, 17), (8, 8, 8)]
)
def test_ttm_kernel_sweep(l, i3, r3, dtype):
    """Paper Table III shapes (R1R2=1024, I3 in 32..256) + odd shapes."""
    y = RNG.standard_normal((l, i3)).astype(np.float32)
    u = RNG.standard_normal((r3, i3)).astype(np.float32)
    ya = jnp.asarray(y, dtype=dtype)
    ua = jnp.asarray(u, dtype=dtype)
    got = np.asarray(ops.ttm(ya, ua))
    want = np.asarray(ref.ttm_ref(ya.astype(jnp.float32), ua.astype(jnp.float32)))
    tol = 5e-5 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * np.abs(want).max())


@pytest.mark.parametrize(
    "n,ra,rb", [(100, 32, 32), (100, 64, 64), (100, 128, 128), (50, 256, 256),
                (7, 5, 3)]
)
def test_kron_kernel_sweep(n, ra, rb):
    """Paper Table IV shapes (rank 32..256) + odd shapes."""
    a = RNG.standard_normal((n, ra)).astype(np.float32)
    b = RNG.standard_normal((n, rb)).astype(np.float32)
    v = RNG.standard_normal((n,)).astype(np.float32)
    got = np.asarray(ops.kron_contrib(jnp.asarray(a), jnp.asarray(b), jnp.asarray(v)))
    want = np.asarray(ref.kron_contrib_ref(a, b, v))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n_rows,nnz", [(64, 200), (300, 50), (128, 128), (1, 5)])
def test_scatter_kernel(n_rows, nnz):
    rows = RNG.integers(0, n_rows, size=nnz).astype(np.int32)
    contrib = RNG.standard_normal((nnz, 48)).astype(np.float32)
    plan = build_scatter_plan(rows, n_rows, bn=32, bi=32)
    contrib_perm = contrib[plan.order] * plan.valid[:, None]
    got = np.asarray(
        scatter_rows_pallas(jnp.asarray(contrib_perm), plan, n_rows, interpret=True)
    )
    want = np.asarray(ref.scatter_rows_ref(jnp.asarray(contrib), jnp.asarray(rows), n_rows))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _sparse_with_empty_row_block():
    """Nonzeros in mode 0's first and third 128-row blocks only: the second
    row block is never visited, and each group ends in padding slots."""
    rng = np.random.default_rng(5)
    shape = (300, 12, 10)
    rows = np.concatenate([np.arange(128), np.arange(256, shape[0])])
    lin = rng.choice(len(rows) * shape[1] * shape[2], size=900, replace=False)
    r, j, k = np.unravel_index(lin, (len(rows), shape[1], shape[2]))
    idx = np.stack([rows[r], j, k], axis=1)
    return SparseCOO.from_parts(idx, rng.standard_normal(900).astype(np.float32), shape)


TENSORS = {
    "uniform": lambda: random_sparse_tensor((40, 30, 20), 0.02, seed=2),
    "empty_row_block": _sparse_with_empty_row_block,
}


@pytest.mark.parametrize("tensor", sorted(TENSORS))
@pytest.mark.parametrize("precision", ["fp32", "bf16_fp32acc"])
@pytest.mark.parametrize(
    "ranks", [(6, 5, 4), (4, 8, 8), (8, 16, 16), (16, 16, 16)],
    ids=lambda r: "x".join(map(str, r)),
)
@pytest.mark.parametrize("mode", [0, 1, 2])
def test_full_sparse_chain_kernel_vs_core(mode, ranks, precision, tensor):
    """The fused kernel through ``ops.sparse_ttm_chain_kernel`` at Kron
    widths Ra*Rb of 20 to 256, on uniform nonzeros and on a mode with an
    unvisited row block; bf16 loads are compared with the reference on the
    same bf16-rounded factor rows."""
    coo = TENSORS[tensor]()
    fs = [jnp.asarray(RNG.standard_normal((s, r)).astype(np.float32))
          for s, r in zip(coo.shape, ranks)]
    got = np.asarray(ops.sparse_ttm_chain_kernel(coo, fs, mode, precision=precision))
    if precision == "bf16_fp32acc":
        fs = [f.astype(jnp.bfloat16).astype(jnp.float32) for f in fs]
    want = np.asarray(
        ref.sparse_ttm_chain_ref(coo.indices, coo.values, fs, mode, coo.shape[mode])
    )
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("ra,rb", [(4, 8), (8, 16), (16, 16)])
def test_lane_dense_kron_rows_are_exact(ra, rb):
    """The fused kernels' Kron rows, spread over the columns on the MXU in
    exact bf16 terms, equal the f32 outer product bit for bit, on values
    that use all 24 bits of the significand over a wide exponent range."""
    from jax.experimental import pallas as pl

    n = 128
    def draw(r):
        x = RNG.standard_normal((n, r)) * 2.0 ** RNG.integers(-20, 20, (n, r))
        return x.astype(np.float32)

    a, b = draw(ra), draw(rb)

    def body(a_ref, b_ref, ea_ref, eb_ref, o_ref):
        o_ref[...] = kron_kernel._kron_block(
            a_ref[...], b_ref[...], ea_ref[...], eb_ref[...])

    got = pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct((n, ra * rb), jnp.float32),
        interpret=True,
    )(jnp.asarray(a), jnp.asarray(b), *kron_kernel._expansions(ra, rb))
    np.testing.assert_array_equal(
        np.asarray(got), np.einsum("ti,tj->tij", a, b).reshape(n, -1))
    # what the MXU reads in one bf16 pass: terms exact in bf16 that sum to a
    parts = [np.asarray(p) for p in kron_kernel._bf16_parts(jnp.asarray(a))]
    for p in parts:
        np.testing.assert_array_equal(p, p.astype(jnp.bfloat16).astype(np.float32))
    np.testing.assert_array_equal(parts[0] + parts[1] + parts[2], a)


@pytest.mark.parametrize(
    "b,h,kvh,s,t,d,bq,bk",
    [
        (2, 4, 2, 128, 128, 64, 64, 64),
        (1, 8, 4, 64, 256, 32, 32, 64),   # decode-style: t > s
        (2, 2, 2, 100, 100, 64, 32, 32),  # non-multiple seq
        (1, 4, 1, 128, 128, 128, 128, 128),  # MQA
    ],
)
def test_flash_attention_sweep(b, h, kvh, s, t, d, bq, bk):
    q = RNG.standard_normal((b, h, s, d)).astype(np.float32)
    k = RNG.standard_normal((b, kvh, t, d)).astype(np.float32)
    v = RNG.standard_normal((b, kvh, t, d)).astype(np.float32)
    got = np.asarray(ops.flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), block_q=bq, block_k=bk))
    want = np.asarray(ref.flash_attention_ref(q, k, v, causal=True))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.standard_normal((1, 4, 64, 64)), dtype=jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((1, 2, 64, 64)), dtype=jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((1, 2, 64, 64)), dtype=jnp.bfloat16)
    got = np.asarray(ops.flash_attention(q, k, v, block_q=32, block_k=32)).astype(np.float32)
    want = np.asarray(ref.flash_attention_ref(q, k, v, causal=True)).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=0.05, atol=0.05)


@pytest.mark.parametrize("bh,c,l,p,n", [(2, 3, 64, 32, 16), (1, 1, 128, 64, 32)])
def test_ssd_chunk_kernel(bh, c, l, p, n):
    x = RNG.standard_normal((bh, c, l, p)).astype(np.float32)
    acs = np.cumsum(-np.abs(RNG.standard_normal((bh, c, l))) * 0.1, axis=-1).astype(np.float32)
    bm = RNG.standard_normal((bh, c, l, n)).astype(np.float32)
    cm = RNG.standard_normal((bh, c, l, n)).astype(np.float32)
    y, s = ops.ssd_chunk(jnp.asarray(x), jnp.asarray(acs), jnp.asarray(bm), jnp.asarray(cm))
    for i in range(bh):
        for j in range(c):
            yr, sr = ref.ssd_chunk_ref(x[i, j], acs[i, j], bm[i, j], cm[i, j])
            np.testing.assert_allclose(np.asarray(y[i, j]), np.asarray(yr), rtol=2e-4, atol=2e-4)
            np.testing.assert_allclose(np.asarray(s[i, j]), np.asarray(sr), rtol=2e-4, atol=2e-4)


def test_ssd_kernel_matches_model_mixer():
    """The Pallas SSD kernel and the model's jnp SSD produce the same
    within-chunk output (same math, two lowerings)."""
    from repro.models.mamba2 import ssd_mixer
    from repro.configs import get_config
    import dataclasses
    cfg = get_config("mamba2-1.3b", smoke=True)
    # single chunk so inter-chunk recurrence is identity
    b, s = 1, cfg.ssm_chunk
    d = cfg.d_model
    x = jnp.asarray(RNG.standard_normal((b, s, d)).astype(np.float32))
    from repro.models.model import init_params
    params = init_params(dataclasses.replace(cfg, dtype="float32"), jax.random.PRNGKey(0))
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    y_model, _ = ssd_mixer(cfg, p, x)
    assert not bool(jnp.any(jnp.isnan(y_model)))


# ---------------------------------------------------------------------------
# Fused Kron→scatter→TTM megakernel (ISSUE 7): the core update G = U^T Y_(n)
# without materializing Y_(n).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_fused_core_megakernel_vs_oracle(mode):
    from repro.core.engine import make_engine

    coo = random_sparse_tensor((24, 18, 16), 0.03, seed=5)
    fs = [jnp.asarray(RNG.standard_normal((s, r)).astype(np.float32))
          for s, r in zip(coo.shape, (5, 4, 3))]
    eng = make_engine("pallas", fuse_core=True, interpret=True)
    sched = eng.device_schedule(coo, mode)
    got = np.asarray(ops.sparse_ttm_core_device(
        coo.indices, coo.values, tuple(fs), mode, sched,
        shape=coo.shape, interpret=True,
    ))
    y = np.asarray(ref.sparse_ttm_chain_ref(
        coo.indices, coo.values, fs, mode, coo.shape[mode]
    ))
    want = np.asarray(fs[mode]).T @ y
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_fused_core_megakernel_empty_tensor():
    from repro.core.engine import make_engine

    coo = SparseCOO(jnp.zeros((0, 3), jnp.int32), jnp.zeros((0,)), (8, 6, 4))
    fs = [jnp.asarray(RNG.standard_normal((s, 2)).astype(np.float32))
          for s in coo.shape]
    eng = make_engine("pallas", fuse_core=True, interpret=True)
    sched = eng.device_schedule(coo, 2)
    got = np.asarray(ops.sparse_ttm_core_device(
        coo.indices, coo.values, tuple(fs), 2, sched,
        shape=coo.shape, interpret=True,
    ))
    assert got.shape == (2, 4) and not got.any()


def test_fused_core_megakernel_bf16_close_to_fp32():
    from repro.core.engine import make_engine

    coo = random_sparse_tensor((20, 16, 32), 0.04, seed=6)
    fs = [jnp.asarray(RNG.standard_normal((s, r)).astype(np.float32))
          for s, r in zip(coo.shape, (4, 3, 5))]
    eng = make_engine("pallas", fuse_core=True, interpret=True)
    sched = eng.device_schedule(coo, 2)
    kw = dict(shape=coo.shape, interpret=True)
    f32 = np.asarray(ops.sparse_ttm_core_device(
        coo.indices, coo.values, tuple(fs), 2, sched, **kw))
    b16 = np.asarray(ops.sparse_ttm_core_device(
        coo.indices, coo.values, tuple(fs), 2, sched,
        precision="bf16_fp32acc", **kw))
    assert b16.dtype == np.float32  # f32 accumulators all the way out
    np.testing.assert_allclose(b16, f32, rtol=3e-2, atol=3e-2 * np.abs(f32).max())


def test_hooi_fuse_core_on_off_parity():
    """Full HOOI with the fused core update matches the split path — the
    megakernel only changes WHERE the contraction happens, not the math."""
    from repro import tucker
    from repro.core.engine import make_engine

    coo = random_sparse_tensor((16, 12, 10), 0.05, seed=7)
    spec = tucker.TuckerSpec(shape=coo.shape, ranks=(3, 3, 2),
                             method="gram", n_iter=3, engine="pallas")
    split = tucker.plan(spec, engine=make_engine("pallas", fuse_core=False))(coo)
    fused = tucker.plan(spec, engine=make_engine("pallas", fuse_core=True))(coo)
    np.testing.assert_allclose(np.asarray(fused.core), np.asarray(split.core),
                               rtol=1e-5, atol=1e-5)
    assert abs(fused.rel_error - split.rel_error) < 1e-6


def test_fused_core_moves_fewer_bytes_than_split():
    """The megakernel keeps each Y_(N) block in VMEM and writes only G: its
    compiled program moves fewer HBM bytes than the split path, which writes
    Y_(N) out and reads it back into the blocked TTM kernel, and both compute
    the same core."""
    from repro.core.engine import make_engine
    from repro.utils.hlo import analyze_hlo

    shape, ranks, nnz = (24, 18, 2048), (6, 4, 8), 512
    rng = np.random.default_rng(0)
    idx = np.stack([rng.integers(0, s, nnz) for s in shape], axis=1).astype(np.int32)
    coo = SparseCOO(jnp.asarray(idx),
                    jnp.asarray(rng.standard_normal(nnz).astype(np.float32)), shape)
    fs = tuple(jnp.asarray(rng.standard_normal((s, r)).astype(np.float32))
               for s, r in zip(shape, ranks))
    eng = make_engine("pallas", interpret=True)
    last = len(shape) - 1
    sched = eng.device_schedule(coo, last)

    @jax.jit
    def split_core(indices, values, fs):
        y = ops.sparse_ttm_chain_device(indices, values, fs, last, sched,
                                        shape=shape, interpret=True)
        return ops.ttm(y.T, fs[last].T, interpret=True).T

    @jax.jit
    def fused_core(indices, values, fs):
        return ops.sparse_ttm_core_device(indices, values, fs, last, sched,
                                          shape=shape, interpret=True)

    args = (coo.indices, coo.values, fs)
    g_split, g_fused = np.asarray(split_core(*args)), np.asarray(fused_core(*args))
    assert np.abs(g_split - g_fused).max() <= 1e-5 * np.abs(g_split).max()
    b_split = analyze_hlo(split_core.lower(*args).compile().as_text()).io_bytes
    b_fused = analyze_hlo(fused_core.lower(*args).compile().as_text()).io_bytes
    assert 0 < b_fused < b_split
