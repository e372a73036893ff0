"""JAX's compile stages as :mod:`repro.obs` spans and counters, and the
tracer's spans as profiler annotations.

JAX reports how long each stage of making a program took through
``jax.monitoring``, on the thread that made it, as the stage ends:

=========================================================  ===============
event                                                      span
=========================================================  ===============
``/jax/core/compile/jaxpr_trace_duration``                 ``jit.trace``
``/jax/core/compile/jaxpr_to_mlir_module_duration``        ``jit.lower``
``/jax/core/compile/backend_compile_duration``             ``jit.compile``
=========================================================  ===============

:func:`install` listens for them. Each event adds its seconds to the
registry counter ``repro_jit_stage_seconds_total{stage=...}``, always, so
an operator sees recompiles on the Prometheus exposition with tracing off.
While tracing is on it also records the stage as a finished span ending
now, with JAX's function name as ``fun``, under whatever span is open on
that thread (``sweep.dispatch``, ``serve.dispatch``, ``plan.compile``). A
jit traced inside another's trace gives a ``jit.trace`` inside the outer
one.

:func:`install` also hands the tracer ``jax.profiler.TraceAnnotation``, so
that while tracing is on every span shows on the host's lanes of a profiler
capture (``jax.profiler.trace``), on the device ops' clock.

This module imports JAX; ``import repro.obs`` does not import it. The
library installs the bridge once per process when its front end
(``repro.tucker``) is imported.
"""
from __future__ import annotations

import threading
import time
from typing import Any

from repro.obs import Counter, registry, tracer

__all__ = ["EVENTS", "install"]

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


def _seconds(stage: str) -> Counter:
    # looked up per event (a compile is rare), so the counter comes back on
    # the exposition after a registry reset
    return registry.counter(
        "repro_jit_stage_seconds_total",
        "seconds JAX spent tracing, lowering and compiling programs",
        labels={"stage": stage},
    )


_lock = threading.Lock()
_installed = False


def _on_duration(event: str, duration_secs: float, **kwargs: Any) -> None:
    stage = EVENTS.get(event)
    if stage is None:
        return
    _seconds(stage).inc(duration_secs)
    if tracer.enabled:
        t1 = time.perf_counter()
        tracer.record("jit." + stage, t1 - duration_secs, t1,
                      fun=str(kwargs.get("fun_name", "")))


def install() -> None:
    """Listen for JAX's compile stages and annotate spans for the
    profiler; a second call does nothing."""
    global _installed
    with _lock:
        if _installed:
            return
        import jax.monitoring
        import jax.profiler

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        tracer.annotation = jax.profiler.TraceAnnotation
        _installed = True
