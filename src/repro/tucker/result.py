"""TuckerResult — the unified result type of the plan/execute API.

Subclasses ``repro.core.hooi.HooiResult`` and adds the serving
metadata the ROADMAP's scenarios need: the spec that produced it, the
compression ratio, the sweep count, and per-call dispatch/retrace/schedule
counters so a serving loop can assert its steady state is compile-free.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Optional

import numpy as np

from repro.core.hooi import HooiResult

if TYPE_CHECKING:
    from repro.tucker.spec import TuckerSpec


@dataclasses.dataclass(frozen=True)
class RequestTiming:
    """Where one served request's wall-clock went (attached to
    :class:`TuckerResult` by ``repro.serve.TuckerService``; ``None`` on
    direct plan/decompose calls).

    ``execute_ms`` is the wall-clock of the whole batched dispatch the
    request rode in — shared by all ``batch_size`` members, which is the
    point: per-request amortized cost is ``execute_ms / batch_size``.

    Attributes:
      queue_ms: submit -> dequeue (micro-batching wait).
      execute_ms: dequeue -> results ready (the batched dispatch).
      total_ms: submit -> results ready.
      batch_size: number of requests in the flush that served this one.
      nnz: this request's real stored nonzeros.
      nnz_padded: the flush's common padded nnz (its bucket boundary).
      flush_reason: why the batch flushed — 'full', 'timeout' or 'drain'.
    """

    queue_ms: float
    execute_ms: float
    total_ms: float
    batch_size: int
    nnz: int
    nnz_padded: int
    flush_reason: str

    @property
    def padding_fraction(self) -> float:
        """Fraction of this request's streamed nnz slots that were padding."""
        return 1.0 - self.nnz / max(1, self.nnz_padded)


@dataclasses.dataclass
class TuckerResult(HooiResult):
    """A :class:`~repro.core.hooi.HooiResult` plus plan/serving metadata.

    Inherited: ``core``, ``factors``, ``rel_error``, ``fit_history``,
    ``engine``. Added:

    Attributes:
      spec: the :class:`~repro.tucker.spec.TuckerSpec` this run executed.
      compression_ratio: dense storage / Tucker storage (factors included);
        the paper's core-only convention is
        ``repro.core.reconstruct.compression_ratio(..., include_factors=False)``.
      dispatches: top-level XLA dispatches this call issued (1 for a fit,
        one per segment of a snapshot job; 0 where not tracked, e.g. the
        dense eager driver).
      retraces: traces of the compiled sweep pipeline this call triggered
        (0 on every plan-cache hit — the serving steady state).
      schedule_builds: host-side schedule constructions/uploads this call
        triggered (0 when the engine's per-tensor caches were warm).
      timing: per-request queue/batch/execute wall-clock when the result was
        produced by ``repro.serve.TuckerService`` (``None`` otherwise).
      collective_bytes_per_sweep: psum payload of one ALS sweep on the
        sharded pipeline (``core.distributed.psum_bytes_per_sweep`` — N
        psums of I_n x prod R_t f32, independent of nnz). ``None`` on
        single-device runs.
      shard_imbalance: load imbalance of the nnz sharding this run executed
        with (``1 - min/max`` of per-shard real nonzeros; 0.0 = perfectly
        even). ``None`` on single-device runs.
      snapshots_written: checkpoints this call wrote (snapshot specs only;
        includes the step-0 snapshot a fresh job writes before its first
        segment).
      resumed_from_sweep: the sweep count the job restarted from when this
        call resumed a snapshot; ``None`` on fresh runs.
      retries: segment dispatches that failed transiently and were retried
        by the ``run_with_retries`` wrapper this call ran under.
      precision: the sweep compute precision this run executed at ('fp32'
        or 'bf16_fp32acc' — the engine's setting, which a prebuilt engine
        may override relative to the spec).
      tuned_blocks: the autotuned kernel block shapes
        (:class:`repro.kernels.autotune.BlockConfig`) the plan applied
        before this call, or ``None`` when no autotuning ran.
      trace_summary: per-stage milliseconds of this call — span name ->
        total ms over the call's span subtree (``repro.obs``). ``None``
        unless tracing was enabled (``repro.obs.configure(enabled=True)``)
        when the call ran. Batched dispatches attach the whole batch's
        summary to every member result.
    """

    spec: Optional["TuckerSpec"] = None
    compression_ratio: Optional[float] = None
    dispatches: int = 0
    retraces: int = 0
    schedule_builds: int = 0
    timing: Optional[RequestTiming] = None
    collective_bytes_per_sweep: Optional[int] = None
    shard_imbalance: Optional[float] = None
    snapshots_written: int = 0
    resumed_from_sweep: Optional[int] = None
    retries: int = 0
    precision: str = "fp32"
    tuned_blocks: Optional[tuple] = None
    trace_summary: Optional[dict] = None

    @property
    def n_sweeps(self) -> int:
        """ALS sweeps that actually ran (after any ``tol`` early exit)."""
        return int(np.asarray(self.fit_history).size)
