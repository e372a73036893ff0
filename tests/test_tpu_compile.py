"""Compile the main path's Pallas kernels for a TPU v5e, without the chip.

The TPU compiler is installed next to JAX, and it compiles for a chip that is
described rather than attached (``jax.experimental.topologies``). Interpret
mode, which every other kernel test runs in, cannot see what Mosaic refuses:
unsupported vector shape casts, misaligned blocks, too much VMEM, a program
that does not fit HBM. These tests compile the kernels at real widths (R = 16,
128-row blocks, nell-2's 12,092-row mode) with ``interpret=False``, at both
precisions, plus one whole Pallas sweep program at 2^18 nonzeros of nell-2's
shape, on one chip and sharded over the four chips of a v5e 2x2 host.

The topology is described inside a module fixture, never at import: only one
process may hold libtpu, and under several test workers only the worker that
runs this file may load it.
"""
import base64
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import hooi, stages
from repro.core.coo import SparseCOO
from repro.kernels import kron_kernel, ttm_kernel
from repro.sparse.layout import (
    DeviceSchedule, build_mode_layout, shard_block_count, shard_rows,
)
from repro.utils import hlo

R, BN, BI, ROWS = 16, 128, 128, 12_092
NBLK = 1024  # nnz blocks streamed by one kernel call
NELL2 = (12_092, 9_184, 28_818)
HBM_BYTES = 16 * 2**30  # one v5e chip
PRECISIONS = ("fp32", "bf16_fp32acc")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _lower(kernel, precision, s):
    """Lower one kernel call at real widths for the described chip."""
    nnz = NBLK * BN
    blk = _sds(s, (NBLK,), jnp.int32)
    rows = _sds(s, (nnz, R))
    vals = _sds(s, (nnz,))
    rel = _sds(s, (nnz,), jnp.int32)
    if kernel == "fused":
        return kron_kernel._fused_call.lower(
            blk, blk, rows, rows, vals, rel, n_rows=ROWS, bn=BN, bi=BI,
            interpret=False, precision=precision,
        )
    if kernel == "mega":
        return kron_kernel._mega_call.lower(
            blk, blk, blk, rows, rows, vals, rel, _sds(s, (ROWS, R)),
            n_rows=ROWS, bn=BN, bi=BI, interpret=False, precision=precision,
        )
    if kernel == "scatter":
        # the unfused pair of the order >= 4 path: Kron rows at the given
        # precision (always emitted in f32), then the one-hot scatter.
        def chain(blkmap, first, a, b, v, rel_row):
            contrib = kron_kernel.kron_contrib_pallas(
                a, b, v, interpret=False, precision=precision
            )
            return kron_kernel._scatter_call(
                blkmap, first, rel_row, contrib, n_rows=ROWS, bn=BN, bi=BI,
                interpret=False,
            )

        return jax.jit(chain).lower(blk, blk, rows, rows, vals, rel)
    assert kernel == "ttm"
    return ttm_kernel.ttm_pallas.lower(
        _sds(s, (R * R, ROWS)), _sds(s, (R, ROWS)), interpret=False,
        precision=precision,
    )


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("kernel", ["fused", "mega", "scatter", "ttm"])
def test_kernel_compiles_for_v5e(one_chip, kernel, precision):
    text = _lower(kernel, precision, one_chip).compile().as_text()
    assert "tpu_custom_call" in text


def _nell2_coo(nnz, seed=0):
    rng = np.random.default_rng(seed)
    total = int(np.prod(NELL2, dtype=np.int64))
    lin = rng.permutation(np.unique(rng.integers(0, total, size=nnz + 4096)))
    idx = np.stack(np.unravel_index(lin[:nnz], NELL2), axis=1)
    return SparseCOO.from_parts(idx, np.ones((nnz,), np.float32), NELL2)


@pytest.fixture(scope="module")
def nell2_scan(one_chip):
    """The whole 5-sweep Pallas program of a nell-2 plan at 2^18 nonzeros,
    compiled once per precision for the tests that read it, with its
    schedules' padded slot counts."""
    coo = _nell2_coo(2**18)

    def spec_of(x):
        return _sds(one_chip, x.shape, x.dtype)

    layouts = [DeviceSchedule.from_layout(build_mode_layout(coo, m)) for m in range(3)]
    scheds = tuple(jax.tree.map(spec_of, sched) for sched in layouts)
    factors = tuple(_sds(one_chip, (NELL2[m], R)) for m in range(3))
    scalar = _sds(one_chip, ())
    compiled = {}

    def get(precision):
        if precision not in compiled:
            compiled[precision] = hooi._scan_sweeps.lower(
                spec_of(coo.indices), spec_of(coo.values), factors, scalar, scalar,
                scheds, shape=NELL2, ranks=(R, R, R), method="householder",
                n_iter=5, engine_name="pallas", interpret=False, use_reuse=False,
                precision=precision,
            ).compile()
        return compiled[precision], {sched.order.shape[0] for sched in layouts}

    return get


@pytest.mark.parametrize("precision", PRECISIONS)
def test_scan_program_compiles_for_v5e(nell2_scan, precision):
    """The whole 5-sweep Pallas program of a nell-2 plan at 2^18 nonzeros:
    Mosaic kernels inside, it fits one chip's HBM, and its order gathers run
    once, outside the sweep loop."""
    compiled, _ = nell2_scan(precision)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    # the nonzeros are put in each mode's schedule order once, before the
    # sweep loop: JAX traced no order gather inside it, and the compiler put
    # none there
    order_gathers = [m.group(1) for m in re.finditer(r'op_name="([^"]*)"', text)
                     if stages.ORDER_GATHER in m.group(1)]
    assert order_gathers and not [p for p in order_gathers if "/while/" in p]
    assert not [ln for ln in loop_lines(text) if stages.ORDER_GATHER in ln]


FUSED_CALL = re.compile(r"^\s*(?:ROOT )?%?_fused_call[.\d]* = .*custom_call_target=\"tpu_custom_call\"")
OPERAND_LAYOUTS = re.compile(r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}")


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("program", ["kernel", "scan"])
def test_fused_kernel_operands_are_lane_dense_for_v5e(one_chip, nell2_scan, program, precision):
    """The fused Kron-scatter kernel takes the values and row offsets as
    lane-dense rows, never as (n, 1) columns that a TPU pads to 128 lanes in
    HBM; so the sweep loop copies no (P, 1) column for it."""
    if program == "kernel":
        text = _lower("fused", precision, one_chip).compile().as_text()
    else:
        compiled, padded = nell2_scan(precision)
        text = compiled.as_text()
        columns = re.compile(r"\[(\d+),1\]\{1,0:T\(8,128\)")
        assert not [ln for ln in loop_lines(text)
                    if any(int(p) in padded for p in columns.findall(ln))]
    calls = [ln for ln in text.splitlines() if FUSED_CALL.match(ln)]
    assert len(calls) == (1 if program == "kernel" else 3)
    for ln in calls:
        operands = OPERAND_LAYOUTS.search(ln).group(1)
        assert ",1]" not in operands, operands


def loop_lines(text):
    """The instructions of every computation a ``while`` loop runs."""
    comps = hlo.split_computations(text)
    bodies = re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", text)
    assert bodies
    inside = {name for b in bodies
              for name, runs in hlo.computation_multipliers(comps, entry=b).items() if runs}
    return [ln for name in inside for ln in comps[name].lines]


def test_scan_program_names_its_stages_for_v5e(one_chip, monkeypatch):
    """On the chip's compiler too, every gather and custom call that JAX
    lowered in the Pallas program carries one ``tucker.*`` scope (the Kron
    kernels ``tucker.kron``, the TTM kernel ``tucker.core``); three custom
    calls the compiler makes itself (a buffer, two concatenations of factor
    slices) carry no metadata. The scopes change no instruction, and no
    kernel beyond the source locations its serialized body carries."""
    from test_stages import STAGE, assert_one_stage_each, without_metadata

    coo = _nell2_coo(2**14)

    def spec_of(x):
        return _sds(one_chip, x.shape, x.dtype)

    scheds = tuple(
        jax.tree.map(spec_of, DeviceSchedule.from_layout(build_mode_layout(coo, m)))
        for m in range(3)
    )
    factors = tuple(_sds(one_chip, (NELL2[m], R)) for m in range(3))
    scalar = _sds(one_chip, ())

    def compiled_text():
        jax.clear_caches()
        return hooi._scan_sweeps.lower(
            spec_of(coo.indices), spec_of(coo.values), factors, scalar, scalar,
            scheds, shape=NELL2, ranks=(R, R, R), method="householder", n_iter=5,
            engine_name="pallas", interpret=False, use_reuse=False,
        ).compile().as_text()

    scoped = compiled_text()
    assert assert_one_stage_each(scoped, compiler_made=3) == {
        stages.ORDER_GATHER, stages.ROW_GATHER, stages.KRON, stages.QRP, stages.CORE}
    kernels = [STAGE.findall(ln) for ln in scoped.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert sorted(k for (k,) in kernels) == sorted([stages.KRON] * 3 + [stages.CORE])
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = compiled_text()
    # a Mosaic kernel's serialized body carries source locations too: compare
    # the bodies as MLIR without debug info, the rest without metadata
    assert mosaic_kernels(plain) == mosaic_kernels(scoped)
    assert (KERNEL_BODY.sub("", without_metadata(plain))
            == KERNEL_BODY.sub("", without_metadata(scoped)))


KERNEL_BODY = re.compile(r'"body":"[^"]*"')


def mosaic_kernels(text):
    """Each Mosaic kernel of a compiled program as MLIR without debug info."""
    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    out = []
    with ctx:
        for body in re.findall(r'"custom_call_config":\{"body":"([^"]*)"', text):
            module = ir.Module.parse(base64.b64decode(body))
            out.append(module.operation.get_asm(enable_debug_info=False))
    assert out
    return out


SHARD_NNZ = 2**18  # nonzeros a chip of the sharded program


@pytest.mark.parametrize("engine", ["xla", "pallas"])
def test_sharded_program_compiles_for_v5e_2x2(topo, engine):
    """The whole 5-sweep sharded program of a nell-2 plan over the four
    chips of a v5e 2x2 host, at 2^18 nonzeros a chip: on Pallas each chip
    runs the Mosaic kernels on its own shard's schedule, with no
    ``(nnz, 256)`` Kron operand in HBM, where the XLA engine builds one;
    both psum each mode's unfolding once a sweep."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("nnz",))

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    scheds = None
    if engine == "pallas":
        idx = np.asarray(_nell2_coo(4 * SHARD_NNZ).indices)
        scheds = []
        for m in range(3):
            n_blocks = max(shard_block_count(shard_rows(idx, 4 * SHARD_NNZ, 4, s, m),
                                             NELL2[m], BN, BI) for s in range(4))

            def leaf(n, dtype=jnp.int32):
                return sds((4, n), dtype, P("nnz"))

            scheds.append(DeviceSchedule(
                order=leaf(n_blocks * BN), valid=leaf(n_blocks * BN, jnp.float32),
                rel_row=leaf(n_blocks * BN), blkmap=leaf(n_blocks),
                first=leaf(n_blocks), last=leaf(n_blocks), row_mask=None,
                kron_unique=None, kron_inverse=None, mode=m, shape=NELL2,
                n_row_blocks=-(-NELL2[m] // BI), bn=BN, bi=BI, kron_modes=None,
            ))
        scheds = tuple(scheds)
    prog = hooi.build_sharded_program(
        mesh, ("nnz",), shape=NELL2, ranks=(R, R, R), method="householder",
        n_iter=5, engine_name=engine, interpret=False,
    )
    scalar = sds((), jnp.float32, P())
    text = prog.lower(
        sds((4 * SHARD_NNZ, 3), jnp.int32, P("nnz", None)),
        sds((4 * SHARD_NNZ,), jnp.float32, P("nnz")),
        tuple(sds((NELL2[m], R), jnp.float32, P(None, None)) for m in range(3)),
        scalar, scalar, scheds,
    ).compile().as_text()
    kernels = text.count('custom_call_target="tpu_custom_call"')
    kron_operands = [int(n) for n in re.findall(r"f32\[(\d+),256\]", text)
                     if int(n) >= SHARD_NNZ]
    if engine == "pallas":
        assert kernels == 4  # a fused Kron-scatter kernel a mode, the core TTM
        assert not kron_operands
    else:
        assert kernels == 0 and kron_operands
    all_reduces = [ln for ln in loop_lines(text)
                   if re.search(r" all-reduce(-start)?\(", ln)]
    assert len(all_reduces) == 3
