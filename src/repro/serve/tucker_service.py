"""TuckerService — the micro-batching Tucker decomposition service.

The paper's hybrid platform wins by division of labor: the CPU aggregates
and schedules, the accelerator runs saturated batched TTM/Kron pipelines.
``repro.tucker`` already has the device half (``TuckerPlan.batch``: one XLA
dispatch decomposes k nnz-padded tensors); this module is the host half that
feeds it. Callers ``submit()`` independent decomposition requests and get a
future-style :class:`TuckerTicket` back; a bounded pool of executor threads
groups compatible requests — same :class:`~repro.tucker.spec.TuckerSpec`,
same ``bucket_nnz`` boundary — into micro-batches and flushes each as ONE
batched dispatch the moment a queue holds ``max_batch`` requests or its
oldest request has waited ``max_wait_ms``.

Concurrency model (the division of labor the paper's hybrid platform is
built on — CPU aggregates, accelerator never idles):

  * ``max_inflight_flushes`` executor threads pop ready batches
    independently, so flushes of *distinct* ``BatchKey``\\ s dispatch
    concurrently — one key's device wait no longer idles every other key.
  * Flushes of the *same* plan pipeline: host-side batch assembly (COO
    padding + key stacking) runs outside the plan's dispatch lock, so one
    executor assembles flush N+1 while another is in device wait on flush N
    (see ``TuckerPlan``'s two-lock contract in ``tucker/planning.py``).
  * Admission control bounds the work in flight: with ``max_pending`` set,
    ``submit`` blocks (``backpressure='block'``) or raises
    :class:`ServiceOverloadedError` (``'reject'``) once that many requests
    are unresolved — queued *or* executing.
  * An optional adaptive batch policy (``adaptive_target_p99_ms``) closes
    the loop on the recorded latency distributions, narrowing a key's
    ``max_batch``/``max_wait_ms`` when its observed p99 overshoots the
    target and widening back when there is headroom.

Every execute path resolves every ticket it dequeued — error paths fail
them, and a belt-and-braces guard converts any would-be leak into a pointed
``RuntimeError`` rather than a silent ``result()`` hang.

Amortization contract (asserted by ``tests/test_tucker_service.py`` and the
``serve_soak`` CI gate): under load, dispatches ≈ requests / max_batch, and
every result carries a :class:`~repro.tucker.result.RequestTiming` showing
where its wall-clock went (queue wait vs. shared batched execute).

    with TuckerService(ServiceConfig(max_batch=8, max_wait_ms=2.0)) as svc:
        tickets = [svc.submit(idx, vals, spec) for idx, vals in requests]
        results = [t.result() for t in tickets]   # TuckerResult each

Synchronous API, internally queued: ``submit`` never blocks on device work;
``TuckerTicket.result()`` blocks until the request's batch has executed.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
import warnings
from typing import Any, List, Optional, Sequence, Set

from repro.core.coo import SparseCOO
from repro.obs import event as _obs_event, span as _obs_span
from repro.serve.batching import (
    AdaptiveBatchPolicy,
    BatchKey,
    Flush,
    MicroBatcher,
)
from repro.serve.metrics import ServiceMetrics
from repro.sparse.layout import bucket_nnz, shard_pad_nnz
from repro.tucker.result import RequestTiming, TuckerResult
from repro.tucker.spec import ShardSpec, TuckerSpec

__all__ = [
    "ServiceConfig",
    "ServiceOverloadedError",
    "TuckerService",
    "TuckerTicket",
]

_BACKPRESSURE_POLICIES = ("block", "reject")


class ServiceOverloadedError(RuntimeError):
    """``submit`` refused by admission control: the service already holds
    ``max_pending`` unresolved requests and ``backpressure='reject'``. The
    request was NOT enqueued — callers shed load or retry later."""


# The plan-cache capacity knob is process-global, but services come and go:
# this registry tracks which live services installed a capacity, so closing
# one never loosens the bound a still-running service relies on. The newest
# live holder's capacity rules; when the last holder closes, the capacity
# observed before ANY service touched it comes back.
_CAPACITY_LOCK = threading.Lock()
_CAPACITY_HOLDERS: List["TuckerService"] = []
_CAPACITY_BASELINE: Optional[int] = None
_CAPACITY_VERSION: Optional[int] = None  # cache version of OUR last install


def _install_capacity(svc: "TuckerService") -> None:
    from repro import tucker

    global _CAPACITY_BASELINE, _CAPACITY_VERSION
    with _CAPACITY_LOCK:
        if not _CAPACITY_HOLDERS:
            _CAPACITY_BASELINE = tucker.plan_cache_info()["capacity"]
        _CAPACITY_HOLDERS.append(svc)
        tucker.set_plan_cache_capacity(svc.config.plan_cache_capacity)
        _CAPACITY_VERSION = tucker.plan_cache_info()["capacity_version"]


def _uninstall_capacity(svc: "TuckerService") -> None:
    from repro import tucker

    global _CAPACITY_VERSION
    with _CAPACITY_LOCK:
        if svc not in _CAPACITY_HOLDERS:
            return
        _CAPACITY_HOLDERS.remove(svc)
        if tucker.plan_cache_info()["capacity_version"] != _CAPACITY_VERSION:
            # someone called set_plan_cache_capacity() manually since our
            # install (detected by version, so even re-setting the SAME
            # value counts) — their bound wins, don't clobber it
            return
        if _CAPACITY_HOLDERS:
            tucker.set_plan_cache_capacity(
                _CAPACITY_HOLDERS[-1].config.plan_cache_capacity
            )
            _CAPACITY_VERSION = tucker.plan_cache_info()["capacity_version"]
        else:
            tucker.set_plan_cache_capacity(_CAPACITY_BASELINE)


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Tuning knobs of one :class:`TuckerService`.

    Attributes:
      max_batch: flush a queue the moment it holds this many requests (the
        batched program's leading axis; also the amortization ceiling).
      max_wait_ms: flush a non-full queue once its oldest request has waited
        this long — the latency bound a trickle of traffic pays for
        batching. 0 flushes on every scheduler wakeup (minimum latency,
        batches only form within one submit burst).
      bucket_base / bucket_growth: the ``repro.sparse.layout.bucket_nnz``
        grid requests are padded to. Coarser growth => fewer compiled
        programs and bigger shared batches, but up to (growth-1)x padded
        slots of wasted stream bandwidth.
      plan_cache_capacity: if set, bound the global plan cache (LRU) so a
        long-lived service cannot pin every compiled program + device
        schedule it has ever seen (``tucker.set_plan_cache_capacity``). The
        knob is process-global: the newest live service's capacity rules,
        and the pre-service capacity returns when the last one closes.
      latency_window: samples retained per latency distribution.
      shard: a :class:`~repro.tucker.spec.ShardSpec` to construct the
        service over a device mesh: every submitted spec that does not carry
        its own ``shard`` is planned with this one, so requests execute as
        single-dispatch shard_map programs across the mesh (one dispatch per
        request — mesh parallelism replaces vmap amortization). A spec
        submitted with an explicit ``shard`` keeps it.
      max_retries: transient flush failures (RuntimeError — the
        ``runtime.fault_tolerance`` retry class) retried in place before the
        whole batch fails. 0 (default) keeps the historical fail-fast
        behavior; the terminal failure always reaches the tickets with no
        trailing backoff sleep.
      retry_backoff_ms: base of the exponential retry backoff.
      max_inflight_flushes: size of the executor pool — how many flushes may
        execute concurrently. 2 (default) overlaps one flush's device wait
        with another's host assembly; 1 restores the strictly sequential
        single-scheduler behavior.
      max_pending: admission bound — the most *unresolved* requests (queued
        or executing) the service accepts before applying backpressure.
        ``None`` (default) is unbounded.
      backpressure: what an over-``max_pending`` submit does: ``'block'``
        (default) waits for capacity; ``'reject'`` raises
        :class:`ServiceOverloadedError` immediately (counted in
        ``ServiceMetrics.rejected``).
      adaptive_target_p99_ms: if set, enable the per-key
        :class:`~repro.serve.batching.AdaptiveBatchPolicy` with this target
        end-to-end p99 (ms); ``max_batch``/``max_wait_ms`` become the
        ceilings the policy widens back toward. ``None`` disables
        adaptation (static limits).
    """

    max_batch: int = 8
    max_wait_ms: float = 2.0
    bucket_base: int = 512
    bucket_growth: float = 2.0
    plan_cache_capacity: Optional[int] = None
    latency_window: int = 8192
    shard: Optional["ShardSpec"] = None
    max_retries: int = 0
    retry_backoff_ms: float = 50.0
    max_inflight_flushes: int = 2
    max_pending: Optional[int] = None
    backpressure: str = "block"
    adaptive_target_p99_ms: Optional[float] = None

    def __post_init__(self) -> None:
        if int(self.max_inflight_flushes) < 1:
            raise ValueError(
                f"max_inflight_flushes must be >= 1, got "
                f"{self.max_inflight_flushes}"
            )
        if self.max_pending is not None and int(self.max_pending) < 1:
            raise ValueError(
                f"max_pending must be >= 1 (or None for unbounded), got "
                f"{self.max_pending}"
            )
        if self.backpressure not in _BACKPRESSURE_POLICIES:
            raise ValueError(
                f"backpressure must be one of {_BACKPRESSURE_POLICIES}, got "
                f"{self.backpressure!r}"
            )
        if (
            self.adaptive_target_p99_ms is not None
            and not float(self.adaptive_target_p99_ms) > 0.0
        ):
            raise ValueError(
                f"adaptive_target_p99_ms must be > 0 (or None to disable), "
                f"got {self.adaptive_target_p99_ms}"
            )


# process-wide monotonic ticket ids: the `ticket` span attribute that links a
# request's submit span (producer thread) to its batch's flush/dispatch/split
# spans (scheduler thread) in one exported trace.
_TICKET_IDS = itertools.count(1)


class TuckerTicket:
    """Future-style handle for one submitted request. Deliberately NOT a
    ``concurrent.futures.Future``: requests are never cancellable once
    queued (a flush takes its whole batch), so the Future cancel/running
    state machine would be dead API surface here.

    ``ticket_id`` is a process-wide monotonic id; it is also the ``ticket``
    attribute on the request's serve-plane spans, so one request's queue
    wait and its batch's execute can be correlated in a trace.
    """

    def __init__(self) -> None:
        self.ticket_id = next(_TICKET_IDS)
        self._done = threading.Event()
        self._result: Optional[TuckerResult] = None
        self._exception: Optional[BaseException] = None

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> TuckerResult:
        """Block until the request's batch executed; raise its error if the
        batch failed, ``TimeoutError`` if ``timeout`` elapsed first."""
        if not self._done.wait(timeout):
            raise TimeoutError("TuckerService request not done within timeout")
        if self._exception is not None:
            raise self._exception
        return self._result

    def exception(self, timeout: Optional[float] = None) -> Optional[BaseException]:
        if not self._done.wait(timeout):
            raise TimeoutError("TuckerService request not done within timeout")
        return self._exception

    # -- service-side completion ------------------------------------------

    def _set_result(self, result: TuckerResult) -> None:
        self._result = result
        self._done.set()

    def _set_exception(self, exc: BaseException) -> None:
        self._exception = exc
        self._done.set()


@dataclasses.dataclass
class _Pending:
    """One queued request (internal)."""

    coo: SparseCOO
    key: Optional[object]  # per-request PRNG key for factor init (or None)
    ticket: TuckerTicket
    submitted_at: float


class TuckerService:
    """Synchronous-API, internally queued micro-batching decomposition
    service. See the module docstring for the architecture and the
    concurrency model; thread-safe: any number of threads may ``submit``
    concurrently, and up to ``max_inflight_flushes`` flushes execute
    concurrently on the executor pool.
    """

    def __init__(self, config: Optional[ServiceConfig] = None) -> None:
        self.config = config or ServiceConfig()
        self.metrics = ServiceMetrics(latency_window=self.config.latency_window)
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._batcher = MicroBatcher(
            max_batch=self.config.max_batch,
            max_wait_s=self.config.max_wait_ms / 1e3,
        )
        self._policy: Optional[AdaptiveBatchPolicy] = None
        if self.config.adaptive_target_p99_ms is not None:
            self._policy = AdaptiveBatchPolicy(
                max_batch=self.config.max_batch,
                max_wait_s=self.config.max_wait_ms / 1e3,
                target_p99_ms=self.config.adaptive_target_p99_ms,
            )
        self._closing = False
        self._closed = False
        self._drain_on_close = True
        # admission-control state, guarded by self._cv: unresolved counts
        # every accepted request from enqueue until its ticket resolves;
        # inflight counts batches currently inside _execute.
        self._unresolved = 0
        self._inflight = 0
        self._warned_specs: Set[TuckerSpec] = set()
        self._remove_eviction_hook = None
        if self.config.plan_cache_capacity is not None:
            from repro import tucker

            _install_capacity(self)
            self._remove_eviction_hook = tucker.add_plan_eviction_hook(
                self._on_plan_evicted
            )
        self._executors = [
            threading.Thread(
                target=self._executor_loop,
                name=f"tucker-service-exec-{i}",
                daemon=True,
            )
            for i in range(self.config.max_inflight_flushes)
        ]
        for t in self._executors:
            t.start()

    # -- public API ---------------------------------------------------------

    def submit(
        self,
        indices: Any,
        values: Any,
        spec: TuckerSpec,
        *,
        key: Any = None,
    ) -> TuckerTicket:
        """Enqueue one decomposition of the COO tensor (``indices``,
        ``values``, shape = ``spec.shape``); returns immediately with a
        :class:`TuckerTicket`. ``key`` seeds the random factor init (default
        PRNGKey(0), matching ``tucker.decompose``)."""
        coo = SparseCOO.from_parts(indices, values, spec.shape)
        return self.submit_coo(coo, spec, key=key)

    def submit_coo(
        self, coo: SparseCOO, spec: TuckerSpec, *, key: Any = None
    ) -> TuckerTicket:
        """`submit` for callers who already hold a ``SparseCOO``."""
        if spec.algorithm != "sparse":
            raise ValueError(
                f"TuckerService serves algorithm='sparse' specs, got "
                f"{spec.algorithm!r} (dense inputs have no nnz axis to batch)"
            )
        if spec.snapshot is not None:
            raise ValueError(
                "TuckerService does not serve snapshot specs: batch members "
                "would interleave step sequences in one checkpoint directory "
                "— run snapshot jobs directly via tucker.plan(spec)(coo)"
            )
        if self.config.shard is not None and spec.shard is None:
            # the service's mesh: plans built here execute sharded; a spec
            # that already carries its own ShardSpec wins
            spec = dataclasses.replace(spec, shard=self.config.shard)
        if tuple(coo.shape) != spec.shape:
            raise ValueError(
                f"input shape {tuple(coo.shape)} does not match the spec "
                f"shape {spec.shape}"
            )
        if coo.nnz == 0:
            raise ValueError(
                "cannot serve a tensor with zero stored nonzeros: an "
                "all-zero tensor has no defined Tucker fit (relative error "
                "is 0/0)"
            )
        # check-and-claim under the lock: concurrent first-submits of one
        # new spec used to race the bare set read/mutation below and both
        # run the synchronous plan() (duplicated compile) and both warn.
        # Exactly one submitter wins the claim; the plan() itself runs
        # OUTSIDE the lock (it can compile — holding the service lock across
        # it would stall every submit and executor).
        with self._lock:
            first_submit = spec not in self._warned_specs
            if first_submit:
                self._warned_specs.add(spec)
        if first_submit:
            from repro import tucker

            # plan once per new spec, synchronously: a misconfigured spec
            # (e.g. a ShardSpec wanting more devices than are attached) must
            # raise HERE at the submit call site, like every other
            # validation error — not asynchronously as a whole-batch flush
            # failure in an executor thread. (A concurrent submit of the
            # same spec that lost the claim proceeds without waiting; if the
            # spec is truly broken its ticket fails at flush.)
            try:
                spec_plan = tucker.plan(spec)
            except BaseException:
                # release the claim so the next submit re-validates instead
                # of silently treating a never-planned spec as known-good
                with self._lock:
                    self._warned_specs.discard(spec)
                raise
            # plan-level check: the spec property alone misses engine
            # resolution (e.g. 'auto' -> pallas) and prebuilt-engine
            # overrides. Sharded specs intentionally flush sequentially —
            # each member is already ONE dispatch spanning the whole mesh,
            # so the no-amortization warning would be misleading.
            if spec.shard is None and not spec_plan.supports_batched_dispatch:
                warnings.warn(
                    f"spec {spec.engine=} {spec.precision=} "
                    f"{spec.use_kron_reuse=} cannot share one batched "
                    f"dispatch; its flushes fall back to sequential "
                    f"execution (correct results, no amortization)",
                    RuntimeWarning,
                    stacklevel=3,
                )
        ticket = TuckerTicket()
        now = time.perf_counter()
        item = _Pending(coo=coo, key=key, ticket=ticket, submitted_at=now)
        dt = spec.resolved_dtype()
        bkey = BatchKey(
            spec=spec,
            bucket=bucket_nnz(
                coo.nnz,
                base=self.config.bucket_base,
                growth=self.config.bucket_growth,
            ),
            dtype=str(dt) if dt is not None else str(coo.values.dtype),
        )
        with _obs_span(
            "serve.submit", ticket=ticket.ticket_id, nnz=int(coo.nnz),
            bucket=int(bkey.bucket),
        ):
            with self._cv:
                if self._closing:
                    raise RuntimeError("TuckerService is closed")
                if (
                    self.config.max_pending is not None
                    and self._unresolved >= self.config.max_pending
                ):
                    if self.config.backpressure == "reject":
                        self.metrics.on_reject()
                        _obs_event(
                            "serve.reject", ticket=ticket.ticket_id,
                            unresolved=self._unresolved,
                        )
                        raise ServiceOverloadedError(
                            f"TuckerService holds "
                            f"{self._unresolved} unresolved requests "
                            f"(max_pending={self.config.max_pending}, "
                            f"backpressure='reject')"
                        )
                    # block: wait for executors to resolve work (they
                    # notify_all on every batch completion) — or for close.
                    while self._unresolved >= self.config.max_pending:
                        self._cv.wait()
                        if self._closing:
                            raise RuntimeError("TuckerService is closed")
                self._unresolved += 1
                self._batcher.add(bkey, item, now)
                self.metrics.set_queue_depth(len(self._batcher))
                _obs_event(
                    "serve.enqueue", ticket=ticket.ticket_id,
                    bucket=int(bkey.bucket),
                )
                # counted before the notify can race a flush: 'submitted'
                # never trails 'completed' in a concurrent snapshot
                self.metrics.on_submit()
                # notify_all: executors AND admission-blocked submitters
                # share this condition; a single notify could wake only a
                # blocked submitter and leave the new work waiting out a
                # timeout before any executor re-checks.
                self._cv.notify_all()
        return ticket

    def decompose_batch(
        self,
        coos: Sequence[SparseCOO],
        spec: TuckerSpec,
        *,
        keys: Any = None,
        timeout: Optional[float] = None,
    ) -> List[TuckerResult]:
        """Convenience: submit many tensors, block for all results (in
        submission order). The scheduler still micro-batches them by bucket.
        ``timeout`` bounds the WHOLE call, not each ticket."""
        keys = list(keys) if keys is not None else [None] * len(coos)
        if len(keys) != len(coos):
            raise ValueError(f"got {len(keys)} keys for {len(coos)} tensors")
        tickets = [
            self.submit_coo(c, spec, key=k) for c, k in zip(coos, keys)
        ]
        deadline = None if timeout is None else time.monotonic() + timeout
        results = []
        for t in tickets:
            left = (
                None if deadline is None
                else max(0.0, deadline - time.monotonic())
            )
            results.append(t.result(timeout=left))
        return results

    def flush(self) -> int:
        """Execute every queued request NOW, on the calling thread (drain
        semantics — partial batches allowed). Returns the number of requests
        flushed. Deterministic tests and latency-sensitive callers use this
        instead of waiting out ``max_wait_ms``. Raises ``RuntimeError`` on a
        closed (or closing) service: post-close the plan-cache capacity and
        eviction hooks are already uninstalled, so silently executing work
        there would run outside every bound the service promised."""
        flushed = 0
        while True:
            with self._cv:
                if self._closing:
                    raise RuntimeError("TuckerService is closed")
                batch = self._batcher.pop_any()
                if batch is not None:
                    self.metrics.set_queue_depth(len(self._batcher))
            if batch is None:
                return flushed
            flushed += len(batch.items)
            self._execute(batch)

    def pending(self) -> int:
        with self._cv:
            return len(self._batcher)

    def inflight(self) -> int:
        """Batches currently executing across the executor pool."""
        with self._cv:
            return self._inflight

    def close(self, drain: bool = True) -> None:
        """Stop the service. ``drain=True`` (default) executes everything
        still queued first; ``drain=False`` fails pending tickets with
        ``RuntimeError``. Idempotent. Joins the whole executor pool, so any
        in-flight flush finishes (and resolves its tickets) before close
        returns."""
        with self._cv:
            if self._closed:
                return
            self._closing = True
            self._drain_on_close = bool(drain)
            self._cv.notify_all()
        for t in self._executors:
            t.join()
        with self._cv:
            self._closed = True
        if self._remove_eviction_hook is not None:
            self._remove_eviction_hook()
        if self.config.plan_cache_capacity is not None:
            _uninstall_capacity(self)

    def __enter__(self) -> "TuckerService":
        return self

    def __exit__(self, *exc) -> None:
        self.close(drain=exc == (None, None, None))

    # -- executor pool -------------------------------------------------------

    def _executor_loop(self) -> None:
        """One executor thread: wait for a ready batch, execute it, repeat.
        ``max_inflight_flushes`` of these run concurrently — each pops under
        the shared condition variable, then executes OUTSIDE it, so distinct
        keys' flushes overlap and same-plan flushes pipeline on the plan's
        own dispatch lock."""
        while True:
            with self._cv:
                batch = None
                while True:
                    if self._closing and not self._drain_on_close:
                        break  # don't pop ready work just to throw it away
                    now = time.perf_counter()
                    batch = self._batcher.pop_ready(now)
                    if batch is not None or self._closing:
                        break
                    deadline = self._batcher.next_deadline()
                    # tiny epsilon past the deadline so the re-check after a
                    # timed wait sees it strictly expired.
                    self._cv.wait(
                        timeout=None
                        if deadline is None
                        else max(deadline - now, 0.0) + 1e-4
                    )
                if batch is None and self._closing:
                    if self._drain_on_close:
                        batch = self._batcher.pop_any()
                    else:
                        while True:
                            dropped = self._batcher.pop_any()
                            if dropped is None:
                                break
                            for item in dropped.items:
                                item.ticket._set_exception(
                                    RuntimeError(
                                        "TuckerService closed before execution"
                                    )
                                )
                            self.metrics.on_failure(len(dropped.items))
                            self._unresolved -= len(dropped.items)
                        self._cv.notify_all()
                    if batch is None:
                        self.metrics.set_queue_depth(len(self._batcher))
                        return
                self.metrics.set_queue_depth(len(self._batcher))
            self._execute(batch)

    # -- execution ----------------------------------------------------------

    def _execute(self, batch: Flush) -> None:
        # safe from any thread (an executor or a flush() caller): device
        # executions of one plan serialize on the plan's own dispatch lock,
        # where the engine schedule-cache hazard actually lives; host
        # assembly pipelines outside it.
        items = batch.items
        with self._cv:
            self._inflight += 1
            self.metrics.set_inflight(self._inflight)
        internal: Optional[BaseException] = None
        try:
            self._execute_inner(batch)
        except Exception as exc:
            # _execute_inner fails its batch internally on dispatch errors;
            # anything escaping it is a serve-plane bug (timing/metrics/
            # adaptation bookkeeping). The guard below turns it into ticket
            # failures — the executor itself must survive to keep the pool
            # at its configured width.
            internal = exc
            _obs_event(
                "serve.internal_error", error=type(exc).__name__,
                detail=str(exc),
            )
        finally:
            # NO execute path may leave a ticket permanently unresolved —
            # a leaked ticket is a silent result() hang. Anything not
            # resolved by the happy path or the batch-failure path (e.g. an
            # exception out of the timing/metrics code) fails loudly here.
            leaked = [it for it in items if not it.ticket.done()]
            if leaked:
                cause = (
                    f"({internal!r})" if internal is not None
                    else "(please report)"
                )
                for it in leaked:
                    it.ticket._set_exception(
                        RuntimeError(
                            "TuckerService internal error: flush finished "
                            f"without resolving this ticket {cause}"
                        )
                    )
                self.metrics.on_failure(len(leaked))
            with self._cv:
                self._unresolved -= len(items)
                self._inflight -= 1
                self.metrics.set_inflight(self._inflight)
                # capacity freed: wake admission-blocked submitters (and
                # close()-waiters)
                self._cv.notify_all()

    def _execute_inner(self, batch: Flush) -> None:
        from repro import tucker

        items = batch.items
        tickets = [it.ticket.ticket_id for it in items]
        dequeued_at = time.perf_counter()
        with _obs_span(
            "serve.flush", reason=batch.reason, batch_size=len(items),
            bucket=int(batch.key.bucket), tickets=tickets,
            executor=threading.current_thread().name,
        ) as fsp:
            try:
                plan = tucker.plan(batch.key.spec)
                # the same predicate batch() decides with — including per-key
                # fallbacks (e.g. non-threefry impls), so the padding metrics
                # below describe what actually executed
                vmappable = plan.batch_is_vmappable([it.key for it in items])
                # sequential fallback: no shared program to pad for — except
                # the sharded path, whose per-member shard_map program is also
                # shape-keyed on the padded nnz: bucket-pad it too, so
                # mixed-nnz flushes reuse one compiled program per
                # (spec, bucket)
                shard = plan.spec.shard
                pad_to = (
                    batch.key.bucket
                    if (vmappable or shard is not None) else None
                )
                fsp.set_attr("vmappable", bool(vmappable))

                def dispatch() -> Any:
                    with _obs_span(
                        "serve.dispatch", tickets=tickets,
                        batch_size=len(items),
                        pad_nnz_to=int(pad_to) if pad_to is not None else None,
                    ):
                        return plan.batch(
                            [it.coo for it in items],
                            keys=[it.key for it in items],
                            pad_nnz_to=pad_to,
                        )

                if self.config.max_retries > 0:
                    from repro.runtime.fault_tolerance import (
                        FtConfig,
                        run_with_retries,
                    )

                    results = run_with_retries(
                        dispatch,
                        FtConfig(
                            max_retries=self.config.max_retries,
                            retry_backoff_s=(
                                self.config.retry_backoff_ms / 1e3
                            ),
                        ),
                        on_retry=lambda attempt, exc: self.metrics.on_retry(),
                    )
                else:
                    results = dispatch()
                if len(results) != len(items):
                    # a short (or long) result list would silently drop
                    # tickets in the zips below — result() would then hang
                    # forever. Fail the WHOLE batch with a pointed error.
                    raise RuntimeError(
                        f"plan.batch returned {len(results)} results for "
                        f"{len(items)} requests (spec={batch.key.spec!r}) — "
                        f"failing the whole batch instead of leaving "
                        f"{abs(len(items) - len(results))} tickets unresolved"
                    )
            except Exception as exc:  # fail the batch, keep the executor alive
                for it in items:
                    it.ticket._set_exception(exc)
                self.metrics.on_failure(len(items))
                fsp.set_attr("error", type(exc).__name__)
                return
            # plan.batch is synchronous through its device->host history
            # fetch, so `done` is an honest end-to-end execute timestamp.
            done = time.perf_counter()
            execute_ms = (done - dequeued_at) * 1e3
            queue_ms, total_ms = [], []
            for it, res in zip(items, results):
                q_ms = (dequeued_at - it.submitted_at) * 1e3
                t_ms = (done - it.submitted_at) * 1e3
                res.timing = RequestTiming(
                    queue_ms=q_ms,
                    execute_ms=execute_ms,
                    total_ms=t_ms,
                    batch_size=len(items),
                    nnz=it.coo.nnz,
                    # the fallback path runs each tensor at its real nnz:
                    # honest padding metrics, not the bucket it would have
                    # padded to. The sharded path pads to the bucket and then
                    # to the even shard multiple — report what actually
                    # streamed.
                    nnz_padded=(
                        shard_pad_nnz(batch.key.bucket, shard.num_devices)
                        if shard is not None
                        else (batch.key.bucket if vmappable else it.coo.nnz)
                    ),
                    flush_reason=batch.reason,
                )
                queue_ms.append(q_ms)
                total_ms.append(t_ms)
            self.metrics.on_flush(
                reason=batch.reason,
                batch_size=len(items),
                dispatches=sum(r.dispatches for r in results),
                nnz_real=sum(it.coo.nnz for it in items),
                nnz_padded=sum(r.timing.nnz_padded for r in results),
                execute_ms=execute_ms,
                queue_ms=queue_ms,
                total_ms=total_ms,
            )
            if self._policy is not None:
                with self._cv:
                    # policy state and batcher limits mutate under the
                    # service lock: concurrent flushes of one key must not
                    # interleave observe/apply
                    update = self._policy.observe(batch.key, total_ms)
                    if update is not None:
                        self._batcher.set_limits(
                            batch.key, update.max_batch, update.max_wait_s
                        )
                        # limits may have tightened: waiting executors must
                        # recompute deadlines/fullness
                        self._cv.notify_all()
                if update is not None:
                    self.metrics.on_adaptation(update.direction)
                    _obs_event(
                        "serve.adapt", bucket=int(batch.key.bucket),
                        direction=update.direction,
                        max_batch=update.max_batch,
                        max_wait_ms=update.max_wait_s * 1e3,
                        p99_ms=update.p99_ms,
                    )
            for it, res in zip(items, results):
                with _obs_span(
                    "serve.split", ticket=it.ticket.ticket_id,
                    queue_ms=res.timing.queue_ms,
                    total_ms=res.timing.total_ms,
                    nnz=int(it.coo.nnz),
                ):
                    it.ticket._set_result(res)

    # -- plan-cache eviction observation ------------------------------------

    def _on_plan_evicted(self, key: Any, plan: Any) -> None:
        self.metrics.on_plan_eviction()
