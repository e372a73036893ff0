"""The bridge between JAX and repro.obs: JAX's compile stages as ``jit.*``
spans and registry counters, spans as profiler annotations, and
``repro.obs`` itself free of JAX."""
import glob
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.obs import jax_bridge

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture
def tracing():
    obs.configure(enabled=True)
    obs.tracer.clear()
    yield obs.tracer
    obs.configure(enabled=False)
    obs.tracer.clear()


def _stage_seconds():
    snap = obs.registry.snapshot()
    return {stage: snap.get(f'repro_jit_stage_seconds_total{{stage="{stage}"}}', 0.0)
            for stage in jax_bridge.EVENTS.values()}


def _fresh_jit(scale):
    # a new function object is a new program: traced, lowered and compiled
    return jax.jit(lambda x: jnp.sin(x) * scale)


def test_compile_stages_are_children_of_the_open_span(tracing):
    jax_bridge.install()
    with obs.span("sweep.dispatch") as sp:
        _fresh_jit(3.0)(jnp.ones(5)).block_until_ready()
    events = tracing.events()
    jit = [e for e in events if e.name.startswith("jit.")]
    names = {e.name for e in jit}
    assert names == {"jit.trace", "jit.lower", "jit.compile"}
    dispatch = next(e for e in events if e.name == "sweep.dispatch")
    for e in jit:
        assert e.parent_id == sp.span_id
        assert e.thread_id == dispatch.thread_id
        assert dispatch.t0 <= e.t0 <= e.t1 <= dispatch.t1
    assert any(e.attrs["fun"] == "<lambda>" for e in jit if e.name == "jit.trace")
    assert any("<lambda>" in e.attrs["fun"] for e in jit if e.name == "jit.compile")


def test_with_tracing_off_the_counters_still_count():
    jax_bridge.install()
    obs.tracer.clear()
    before = _stage_seconds()
    _fresh_jit(5.0)(jnp.ones(7)).block_until_ready()
    after = _stage_seconds()
    assert obs.tracer.events() == []
    for stage in ("trace", "lower", "compile"):
        assert after[stage] > before[stage]
    prom = obs.registry.render_prometheus()
    assert "# TYPE repro_jit_stage_seconds_total counter" in prom
    assert 'repro_jit_stage_seconds_total{stage="compile"}' in prom


def test_install_listens_once():
    from jax._src import monitoring

    jax_bridge.install()
    jax_bridge.install()
    assert monitoring.get_event_duration_listeners().count(jax_bridge._on_duration) == 1
    assert obs.tracer.annotation is jax.profiler.TraceAnnotation


def test_span_appears_on_the_profilers_host_lane(tracing, tmp_path):
    from bench import xplane

    jax_bridge.install()
    jax.profiler.start_trace(str(tmp_path))
    with obs.span("stage.probe"):
        np.linalg.svd(np.random.default_rng(0).normal(size=(300, 300)))
    jax.profiler.stop_trace()
    span = next(e for e in tracing.events() if e.name == "stage.probe")
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = []
    for p in xplane.read(path):
        if not p.name.startswith("/host:"):
            continue
        ids = [k for k, v in p.names.items() if v == "stage.probe"]
        for ln in p.lines:
            hit = np.isin(ln.ids, ids)
            found += list((ln.end_ns[hit] - ln.start_ns[hit]) / 1e9)
    assert len(found) == 1
    assert abs(found[0] - (span.t1 - span.t0)) < 1e-3


def test_obs_imports_no_jax():
    code = textwrap.dedent(
        f"""
        import sys, types
        # the repro package itself imports the library; load repro.obs alone
        pkg = types.ModuleType("repro")
        pkg.__path__ = [{os.path.join(SRC, "repro")!r}]
        sys.modules["repro"] = pkg
        import repro.obs
        repro.obs.configure(enabled=True)
        with repro.obs.span("x"):
            pass
        assert "jax" not in sys.modules, sorted(m for m in sys.modules if "jax" in m)
        print("obs without jax OK")
        """
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "obs without jax OK" in proc.stdout
