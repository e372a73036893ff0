"""The decomposition's stage scopes (``repro.core.stages``) in the compiled
programs: every gather, dot, custom call, scatter and all-reduce carries
exactly one ``tucker.*`` scope in its ``op_name``, in every pipeline, and the
scopes change no compiled instruction."""
import contextlib
import os
import re
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.core import stages
from repro.sparse.generators import random_sparse_tensor
from repro.tucker import SnapshotSpec, TuckerSpec
from repro.tucker.planning import TuckerPlan
from repro.utils import hlo

SHAPE, RANKS = (12, 10, 8), (3, 3, 2)
CHECKED = ("gather", "dot", "custom-call", "scatter", "all-reduce")
STAGE = re.compile(r"tucker\.[a-z_]+")
# the loop body of a scan program runs everything but the eager preamble
PER_SWEEP = {stages.ORDER_GATHER, stages.ROW_GATHER, stages.KRON, stages.QRP, stages.CORE}


@pytest.fixture(scope="module")
def coo():
    return random_sparse_tensor(SHAPE, 0.08, seed=0)


def scoped_ops(text):
    """``(opcode, stages)`` of every checked instruction of the program;
    ``stages`` is ``None`` for one with no ``op_name`` at all, which the
    compiler made itself."""
    out = []
    for comp in hlo.split_computations(text).values():
        for op in hlo.iter_ops(comp):
            if op.opcode in CHECKED:
                m = re.search(r'op_name="([^"]*)"', op.line)
                # a scope inside a transform reads vmap(tucker.init)
                out.append((op.opcode, STAGE.findall(m.group(1)) if m else None))
    return out


def assert_one_stage_each(text, compiler_made=0):
    """Every checked instruction that JAX lowered has exactly one stage;
    ``compiler_made`` of them carry no ``op_name``. Returns the stages."""
    ops = scoped_ops(text)
    assert ops
    assert sum(found is None for _, found in ops) == compiler_made
    bad = [(opcode, found) for opcode, found in ops if found is not None and len(found) != 1]
    assert not bad, bad
    found = {f[0] for _, f in ops if f}
    assert found <= set(stages.STAGES)
    return found


def without_metadata(text):
    """The program's instructions, without ``metadata={...}`` and without
    the source-location tables (``FileNames`` ... ``StackFrames``), which
    the TPU compiler prints after the module header."""
    lines, table = [], False
    for line in text.splitlines():
        if re.match(r"^(FileNames|FunctionNames|FileLocations|StackFrames)$", line):
            table = True
        elif table and not line.strip():
            table = False
        elif not table:
            lines.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    assert sum(" = " in line for line in lines) > 50  # the instructions are all there
    return "\n".join(lines)


def _plan(engine, method="householder", **kw):
    return TuckerPlan(TuckerSpec(shape=SHAPE, ranks=RANKS, method=method, engine=engine,
                                 n_iter=2, **kw))


@pytest.mark.parametrize("engine,method", [("xla", "householder"), ("pallas", "householder"),
                                           ("xla", "gram"), ("xla", "svd")])
def test_every_sweep_operation_has_one_stage(coo, engine, method):
    found = assert_one_stage_each(_plan(engine, method).lower_hlo(coo)[0])
    want = PER_SWEEP - ({stages.ORDER_GATHER} if engine == "xla" else set())
    assert found == want


def test_kron_reuse_and_fused_core_have_one_stage(coo):
    reuse = TuckerPlan(TuckerSpec(shape=SHAPE, ranks=RANKS, engine="xla", n_iter=2,
                                  use_kron_reuse=True))
    assert stages.KRON in assert_one_stage_each(reuse.lower_hlo(coo)[0])
    fused = _plan("pallas")
    fused.engine.fuse_core = True
    assert stages.KRON in assert_one_stage_each(fused.lower_hlo(coo)[0])


def test_segment_program_has_one_stage(coo, tmp_path):
    plan = _plan("xla", snapshot=SnapshotSpec(every_n_sweeps=1, directory=str(tmp_path)))
    text, meta = plan.lower_hlo(coo)
    assert meta["kind"] == "segment"
    assert assert_one_stage_each(text) == PER_SWEEP - {stages.ORDER_GATHER}


def test_batched_program_scopes_its_preamble_too():
    coos = [random_sparse_tensor(SHAPE, 0.06 * (1 + i), seed=40 + i) for i in range(2)]
    text, _ = _plan("xla", method="gram").lower_batch_hlo(coos)
    assert_one_stage_each(text)
    # the starting factors are drawn inside the batched program
    assert any(stages.INIT in line for line in text.splitlines())


def test_sharded_program_scopes_its_psum():
    code = textwrap.dedent(
        f"""
        import sys
        sys.path.insert(0, {os.path.dirname(__file__)!r})
        from test_stages import assert_one_stage_each, scoped_ops
        from repro.core import stages
        from repro.sparse.generators import random_sparse_tensor
        from repro.tucker import ShardSpec, TuckerSpec
        from repro.tucker.planning import TuckerPlan

        coo = random_sparse_tensor({SHAPE}, 0.08, seed=0)
        plan = TuckerPlan(TuckerSpec(shape={SHAPE}, ranks={RANKS}, engine="xla", n_iter=2,
                                     shard=ShardSpec(num_devices=2)))
        text, _ = plan.lower_hlo(coo)
        found = assert_one_stage_each(text)
        assert stages.PSUM in found, found
        psums = [s for op, s in scoped_ops(text) if op == "all-reduce"]
        assert psums and all(s == [stages.PSUM] for s in psums), psums
        print("sharded scopes OK")
        """
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "sharded scopes OK" in proc.stdout


@pytest.mark.parametrize("engine", ["xla", "pallas"])
def test_scopes_change_no_instruction(coo, engine, monkeypatch):
    jax.clear_caches()
    scoped, _ = _plan(engine).lower_hlo(coo)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    jax.clear_caches()
    plain, _ = _plan(engine).lower_hlo(coo)
    assert "tucker." in scoped and "tucker." not in plain
    assert without_metadata(scoped) == without_metadata(plain)
