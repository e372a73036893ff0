"""The plan/execute front-end: ``plan(spec) -> TuckerPlan``.

One API instead of four entrypoints. A :class:`~repro.tucker.spec.TuckerSpec`
is validated once; :func:`plan` returns a reusable :class:`TuckerPlan` that
owns its :class:`~repro.core.engine.SweepEngine` (host + device-resident
schedule caches) and dispatches into the compiled scan-over-sweeps program
(``repro.core.hooi._scan_sweeps``) keyed by the spec — so repeated calls on
same-shape tensors hit the jit compile cache with zero retraces, and a
serving loop can assert that via the per-call counters on
:class:`~repro.tucker.result.TuckerResult`.

``TuckerPlan.batch`` is the new serving scenario: pad nnz across a batch of
same-shape sparse tensors and ``vmap`` the whole multi-sweep program over the
leading batch axis — one XLA dispatch for k decompositions.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import warnings
from collections import OrderedDict
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hooi as _hooi
from repro.core.coo import SparseCOO
from repro.core.engine import SweepEngine, resolve_engine
from repro.obs import event as _obs_event
from repro.obs import jax_bridge as _obs_jax_bridge
from repro.obs import registry as _obs_registry
from repro.obs import span as _obs_span
from repro.obs import tracer as _obs_tracer
from repro.sparse.layout import pad_coo_batch
from repro.tucker.result import TuckerResult
from repro.tucker.spec import TuckerSpec, spec_for

# JAX's trace/lower/compile stages as jit.* spans and a counter, once per
# process
_obs_jax_bridge.install()

__all__ = [
    "PlanCache",
    "TuckerPlan",
    "add_plan_eviction_hook",
    "clear_plan_cache",
    "decompose",
    "engine_for_spec",
    "mesh_fingerprint",
    "mesh_for_shard",
    "plan",
    "plan_cache_info",
    "resume",
    "set_plan_cache_capacity",
]


def mesh_for_shard(shard: Any) -> "jax.sharding.Mesh":
    """The 1-axis nnz mesh a :class:`~repro.tucker.spec.ShardSpec` executes
    on: ``shard.num_devices`` devices named ``shard.axis``. Deterministic
    (same spec on the same host -> the same mesh), so the plan cache can key
    on its fingerprint. On a 1-device host, force more CPU devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the first
    jax import."""
    n_avail = len(jax.devices())
    if shard.num_devices > n_avail:
        raise ValueError(
            f"ShardSpec wants {shard.num_devices} devices but only {n_avail} "
            f"are attached — on a CPU host, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{shard.num_devices} before the first jax import"
        )
    return jax.make_mesh(
        (shard.num_devices,), (shard.axis,),
        axis_types=(jax.sharding.AxisType.Auto,),
    )


def mesh_fingerprint(mesh: Any) -> str:
    """Stable identity of a mesh for the plan-cache key: platform + device
    ids (in mesh order) + axis layout. Two plans over identical meshes share
    one compiled program; a changed device set or axis layout is a new key,
    never a silent reuse of the wrong mesh's executable."""
    devices = list(np.asarray(mesh.devices).flat)
    plat = devices[0].platform if devices else "none"
    ids = ",".join(str(d.id) for d in devices)
    axes = "x".join(f"{a}={s}" for a, s in zip(mesh.axis_names, mesh.devices.shape))
    return f"{plat}:{ids}/{axes}"


def _total_traces() -> int:
    return sum(_hooi.SWEEP_TRACE_COUNTS.values())


# plan-cache counters, registered at their source (every PlanCache instance
# reports into the same family — in practice the process-global _PLAN_CACHE).
_MX_PLAN_HITS = _obs_registry.counter(
    "repro_plan_cache_hits_total", "plan cache hits"
)
_MX_PLAN_MISSES = _obs_registry.counter(
    "repro_plan_cache_misses_total", "plan cache misses (plan builds)"
)
_MX_PLAN_EVICTIONS = _obs_registry.counter(
    "repro_plan_cache_evictions_total", "plan cache LRU evictions"
)
_MX_SNAPSHOTS = _obs_registry.counter(
    "repro_snapshots_written_total", "sweep-carry snapshots spilled to disk"
)
_MX_COLLECTIVE_BYTES = _obs_registry.counter(
    "repro_collective_bytes_total",
    "bytes all-reduced across chips by sharded sweeps (psum payload per "
    "sweep times sweeps run)",
)


def _attach_trace_summary(results: Any, root_span: Any) -> None:
    """Per-stage milliseconds for everything under this call's root span —
    only when tracing is live (the disabled path must stay free)."""
    if root_span.span_id < 0:  # the shared no-op span: tracing disabled
        return
    summary = _obs_tracer.subtree_summary(root_span.span_id)
    for res in results if isinstance(results, list) else [results]:
        res.trace_summary = dict(summary)


_DEFAULT_NP_KEY: Optional[np.ndarray] = None


def _default_np_key() -> np.ndarray:
    """Host copy of PRNGKey(0), built once — creating the default key per
    batch member costs one eager dispatch each, which adds up on a hot
    serving flush path."""
    global _DEFAULT_NP_KEY
    if _DEFAULT_NP_KEY is None:
        _DEFAULT_NP_KEY = np.asarray(jax.random.PRNGKey(0))
    return _DEFAULT_NP_KEY


def _is_typed_key(k: Any) -> bool:
    """New-style typed PRNG key (``jax.random.key``), whose dtype carries the
    impl — unlike raw uint32 keys, it cannot round-trip through numpy."""
    return (
        k is not None
        and hasattr(k, "dtype")
        and jnp.issubdtype(k.dtype, jax.dtypes.prng_key)
    )


def _np_key(k: Any) -> np.ndarray:
    """Host view of one raw (uint32) PRNG key; ``None`` is the default key."""
    return _default_np_key() if k is None else np.asarray(k)


def _key_vmappable(k: Any) -> bool:
    """Whether this PRNG key reproduces the per-tensor init inside the
    vmapped batched program. Raw/None keys and typed threefry keys do;
    other impls (e.g. rbg) generate DIFFERENT streams under vmap than
    unvmapped — batching them would silently break same-key
    reproducibility, so those batches fall back to sequential calls."""
    return not _is_typed_key(k) or str(k.dtype) == "key<fry>"


def _stack_keys(keys: Any) -> jax.Array:
    """One key array for the batched program. All-raw/None keys assemble
    host-side (zero eager dispatches — the hot serving path); typed
    threefry keys are unwrapped to their raw uint32 data, which IS a legacy
    threefry key with the identical stream."""
    return jnp.asarray(
        np.stack(
            [
                np.asarray(jax.random.key_data(k)) if _is_typed_key(k)
                else _np_key(k)
                for k in keys
            ]
        )
    )


def engine_for_spec(
    spec: TuckerSpec,
    prebuilt: Optional[SweepEngine] = None,
    resolved: Optional[str] = None,
) -> SweepEngine:
    """The ONE place a plan's sweep engine comes from, so ``use_kron_reuse``
    follows a single rule: honored on the XLA engine, ignored on Pallas
    (whose schedule has its own reuse layout), and warned about when a
    prebuilt engine disagrees with the spec."""
    if prebuilt is not None:
        if spec.use_kron_reuse and not prebuilt.use_kron_reuse:
            warnings.warn(
                "use_kron_reuse=True is ignored: the prebuilt SweepEngine was "
                "made with use_kron_reuse=False (pass make_engine(..., "
                "use_kron_reuse=True) instead).",
                RuntimeWarning,
                stacklevel=3,
            )
        elif prebuilt.use_kron_reuse and not spec.use_kron_reuse:
            warnings.warn(
                "the prebuilt SweepEngine overrides use_kron_reuse=False: it "
                "was made with use_kron_reuse=True, so the Kron-reuse path "
                "will run (the engine's setting wins).",
                RuntimeWarning,
                stacklevel=3,
            )
        return prebuilt
    from repro.core.engine import make_engine

    name = resolved if resolved is not None else resolve_engine(spec.engine)
    # name is already resolved, so make_engine's own resolve is a no-op
    # (no double fallback warning) — but any future construction-time logic
    # it grows applies to plan engines too.
    return make_engine(
        name, use_kron_reuse=spec.use_kron_reuse, precision=spec.precision
    )


@dataclasses.dataclass
class PlanStats:
    """Cumulative counters over a plan's lifetime (per-call numbers live on
    each :class:`TuckerResult`)."""

    calls: int = 0
    dispatches: int = 0
    retraces: int = 0
    schedule_builds: int = 0


class TuckerPlan:
    """A reusable, compile-once/run-many executable for one TuckerSpec.

    Call it on a tensor of the spec's shape (``plan(coo)``), or on a batch
    of same-shape sparse tensors (``plan.batch(coos)``). The plan owns its
    sweep engine — per-tensor schedules are cached on the engine and rebuilt
    only when a different tensor is handed in — and its compiled program is
    keyed by the spec's static fields, so the steady state is zero retraces
    and zero schedule rebuilds (asserted by ``tests/test_sweep_pipeline.py``).
    """

    def __init__(
        self,
        spec: TuckerSpec,
        engine: Optional[SweepEngine] = None,
        _resolved: Optional[str] = None,
        _mesh: Any = None,
    ) -> None:
        self.spec = spec
        if spec.shard is not None:
            # the sharded pipeline is plain XLA inside shard_map: force the
            # resolution (spec validation already rejected engine='pallas';
            # 'auto' must not pick pallas on a TPU host either).
            _resolved = "xla"
        self.mesh = (
            _mesh if _mesh is not None
            else (mesh_for_shard(spec.shard) if spec.shard is not None else None)
        )
        if self.mesh is not None:
            n_mesh = int(np.prod(list(self.mesh.devices.shape) or [1]))
            if spec.shard is None or n_mesh != spec.shard.num_devices:
                raise ValueError(
                    f"plan mesh has {n_mesh} devices but the spec "
                    f"{'has no shard' if spec.shard is None else f'wants {spec.shard.num_devices}'}"
                )
        # nonzeros shard over every axis of the plan's mesh (a caller-supplied
        # mesh keeps its own axis names; the default 1-axis mesh uses
        # shard.axis).
        self._nnz_axes = (
            tuple(self.mesh.axis_names) if self.mesh is not None else None
        )
        # the compiled shard_map program, built lazily on first sharded call.
        # Owned by the plan (not a module registry) so a plan-cache eviction
        # releases the compiled executable along with the schedules.
        self._sharded_program = None
        # its resumable sibling (snapshot specs): one segment program per
        # plan, reused for every segment of every job at any resume offset.
        self._sharded_segment_program = None
        if spec.algorithm == "sparse":
            self.engine: Optional[SweepEngine] = engine_for_spec(
                spec, prebuilt=engine, resolved=_resolved
            )
            if spec.shard is not None and self.engine.name != "xla":
                raise ValueError(
                    f"a sharded plan requires the XLA engine, but the "
                    f"prebuilt SweepEngine is {self.engine.name!r}"
                )
        else:
            if engine is not None:
                raise ValueError(
                    f"a SweepEngine only applies to algorithm='sparse' plans, "
                    f"not {spec.algorithm!r} (the dense path is plain XLA)"
                )
            self.engine = None
        # the autotuned kernel block shapes, applied once per plan on the
        # first sparse execution (spec.autotune on the Pallas engine only).
        self._tuned_blocks = None
        self.stats = PlanStats()
        # The plan's thread-safety contract, in two locks:
        #
        # * ``_exec_lock`` serializes per-tensor executions: the engine's
        #   schedule caches are bound to ONE tensor at a time
        #   (``SweepEngine._bind``), so concurrent ``__call__``s could
        #   contract tensor A against tensor B's schedule. Plans are shared
        #   process-wide through the plan cache — the lock lives here, not
        #   on any one caller. (A prebuilt engine handed to several plans
        #   still must not execute concurrently across them.)
        # * ``_dispatch_lock`` serializes only the DEVICE half of the
        #   vmapped :meth:`batch` path, which never touches the engine's
        #   schedule caches (``_batched_scan_sweeps`` consumes raw padded
        #   COO arrays): concurrent flushes of one plan overlap their
        #   host-side assembly (padding + key stacking) against another
        #   flush's device execution, and only the dispatch itself queues.
        #   This is what lets the serving plane pipeline same-plan flushes.
        self._exec_lock = threading.RLock()
        self._dispatch_lock = threading.Lock()
        # informational counters are bumped from concurrent flushes; a
        # dedicated lock keeps them exact without re-serializing execution.
        self._stats_lock = threading.Lock()

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        eng = self.engine.name if self.engine is not None else "xla"
        return (
            f"TuckerPlan({self.spec.algorithm}, shape={self.spec.shape}, "
            f"ranks={self.spec.ranks}, engine={eng}, "
            f"calls={self.stats.calls})"
        )

    @property
    def supports_batched_dispatch(self) -> bool:
        """Whether :meth:`batch` runs its members as ONE vmapped dispatch:
        the spec-level property AND an engine that actually resolved to
        plain XLA ('auto' may have picked Pallas; a prebuilt reuse engine
        overrides the spec). The single source of truth — the serving plane
        keys its padding decisions and metrics off this."""
        return (
            self.spec.supports_batched_dispatch
            and self.engine is not None
            and self.engine.name == "xla"
            and not self.engine.use_kron_reuse
        )

    def batch_is_vmappable(self, keys: Any = None) -> bool:
        """Whether :meth:`batch` with these keys runs as ONE vmapped
        dispatch — the plan-level property AND every key reproducible under
        vmap. The serving plane keys its padding decisions and metrics off
        this; batch() itself decides with the same call."""
        return self.supports_batched_dispatch and (
            keys is None or all(_key_vmappable(k) for k in keys)
        )

    # -- public execution surface -----------------------------------------

    def __call__(self, x: Any, key: Any = None, factors_init: Any = None,
                 pad_nnz_to: Optional[int] = None,
                 resume_from: Any = None, injector: Any = None) -> TuckerResult:
        """Run the planned decomposition on one tensor of the spec's shape.
        Thread-safe: concurrent calls on one plan serialize.

        ``pad_nnz_to`` (sparse algorithm only) pads the stored nonzeros with
        explicit zeros up to a target before execution, so mixed-nnz calls
        share one nnz-shape-keyed compiled program (the serving plane passes
        its bucket boundary). Sharded plans fold it into the shard padding
        while keeping the imbalance counters on the REAL nonzeros.

        ``resume_from`` (snapshot specs only) restarts the job from a saved
        snapshot: a checkpoint directory, or an already-loaded
        :class:`~repro.tucker.snapshot.SnapshotState` (as :func:`resume`
        passes). ``key``/``factors_init`` are ignored on a resume — the
        factors come from the snapshot. ``injector`` (tests) is a
        :class:`~repro.runtime.fault_tolerance.FailureInjector` consulted at
        every segment boundary, inside the retry wrapper.
        """
        with self._exec_lock, _obs_span(
            "plan.call", algorithm=self.spec.algorithm,
            shape=list(self.spec.shape), ranks=list(self.spec.ranks),
        ) as sp:
            with self._stats_lock:
                self.stats.calls += 1
            if self.spec.algorithm != "sparse" and (
                resume_from is not None or injector is not None
            ):
                raise ValueError(
                    "resume_from/injector require algorithm='sparse' with "
                    "snapshot=SnapshotSpec(...)"
                )
            if self.spec.algorithm == "dense":
                res = self._run_dense(x, key, factors_init)
            else:
                coo = self._check_sparse_input(x)
                if self.spec.algorithm == "complete":
                    res = self._run_complete(coo, key, factors_init)
                else:
                    res = self._run_sparse(coo, key, factors_init, pad_nnz_to,
                                           resume_from, injector)
            _attach_trace_summary(res, sp)
            return res

    def batch(
        self,
        coos: Sequence[SparseCOO],
        keys: Any = None,
        pad_nnz_to: Optional[int] = None,
    ) -> List[TuckerResult]:
        """Decompose k same-shape sparse tensors as ONE batched dispatch.

        Nonzeros are padded to the batch max — or to ``pad_nnz_to``, e.g. a
        ``repro.sparse.layout.bucket_nnz`` boundary so repeated flushes share
        one compiled program — with explicit zeros, which contribute nothing
        to any contraction; then the whole compiled multi-sweep program is
        ``vmap``-ed over the leading batch axis. Falls back to k sequential
        calls — same results, k dispatches — for configurations whose
        per-tensor schedules cannot share one program (the Pallas engine,
        Kron-reuse dedup plans, bf16 mixed precision); ``pad_nnz_to``
        is irrelevant there (no shared program to stabilize) and ignored —
        EXCEPT on sharded plans, whose per-member shard_map program is also
        shape-keyed on the padded nnz: there each member is padded to
        ``pad_nnz_to`` first, so mixed-nnz flushes of one bucket reuse one
        compiled program instead of recompiling per distinct nnz.

        An empty ``coos`` is a defined no-op (``[]``); a member tensor with
        zero stored nonzeros is rejected with a clear error — its relative
        error is 0/0, and the all-padding member would otherwise surface as
        an opaque NaN (or XLA shape error) deep in the compiled program.

        Per-call counters on the returned results describe the whole batched
        dispatch, not one element.
        """
        if self.spec.algorithm != "sparse":
            raise ValueError(
                f"batch() requires algorithm='sparse', got {self.spec.algorithm!r}"
            )
        if self.spec.snapshot is not None:
            raise ValueError(
                "batch() does not compose with snapshot=SnapshotSpec(...): "
                "the members would interleave step sequences in one "
                "checkpoint directory — run snapshot jobs as single calls"
            )
        coos = [self._check_sparse_input(c) for c in coos]
        if keys is None:
            keys = [None] * len(coos)
        keys = list(keys)
        if len(keys) != len(coos):
            raise ValueError(
                f"got {len(keys)} keys for {len(coos)} tensors"
            )
        if not coos:
            return []
        empty = [i for i, c in enumerate(coos) if int(c.indices.shape[0]) == 0]
        if empty:
            raise ValueError(
                f"batch() members {empty} have zero stored nonzeros: an "
                f"all-zero tensor has no defined Tucker fit (relative error "
                f"is 0/0) — filter empties out before submitting"
            )
        vmapped = self.batch_is_vmappable(keys)
        with _obs_span("plan.batch", size=len(coos), vmapped=vmapped) as sp:
            if not vmapped:
                # sequential fallback: each member re-enters __call__, which
                # serializes on _exec_lock (the engine schedule-cache
                # hazard). Stabilize the shard_map program's nnz shape
                # across the flush: explicit-zero padding changes no
                # contraction, and passing the target (instead of
                # pre-padding the tensor) keeps the shard-imbalance
                # counters on the real nonzeros.
                pad = pad_nnz_to if self.spec.shard is not None else None
                return [self(c, key=k, pad_nnz_to=pad)
                        for c, k in zip(coos, keys)]
            with self._stats_lock:
                self.stats.calls += len(coos)  # same meaning as the fallback
            results = self._run_sparse_vmapped(coos, keys, pad_nnz_to)
            _attach_trace_summary(results, sp)
            return results

    # -- input validation ---------------------------------------------------

    def _check_sparse_input(self, coo: Any) -> SparseCOO:
        if not isinstance(coo, SparseCOO):
            raise TypeError(
                f"algorithm={self.spec.algorithm!r} expects a SparseCOO input, "
                f"got {type(coo).__name__}"
            )
        if tuple(coo.shape) != self.spec.shape:
            raise ValueError(
                f"input shape {tuple(coo.shape)} does not match the planned "
                f"spec shape {self.spec.shape}"
            )
        dt = self.spec.resolved_dtype()
        if dt is not None and coo.values.dtype != dt:
            coo = SparseCOO(coo.indices, coo.values.astype(dt), coo.shape)
        return coo

    def _init_factors(self, key: Any, factors_init: Any) -> Any:
        if factors_init is not None:
            # copy: the compiled scan pipeline donates its factor buffers, and
            # donating the caller's arrays would delete them out from under a
            # warm-start loop that reuses its seed factors.
            return [jnp.array(f, copy=True) for f in factors_init]
        key = key if key is not None else jax.random.PRNGKey(0)
        return _hooi.init_factors(
            self.spec.shape, self.spec.ranks, key, dtype=self.spec.resolved_dtype()
        )

    def _compression(self) -> float:
        from repro.core.reconstruct import compression_ratio

        return compression_ratio(self.spec.shape, self.spec.ranks)

    def _result(self, core: Any, factors: Any, hist: Any, engine: Any,
                dispatches: int, retraces: int,
                schedule_builds: int) -> TuckerResult:
        with self._stats_lock:
            self.stats.dispatches += dispatches
            self.stats.retraces += retraces
            self.stats.schedule_builds += schedule_builds
        return TuckerResult.from_history(
            core, factors, hist,
            engine=engine,
            spec=self.spec,
            compression_ratio=self._compression(),
            dispatches=dispatches,
            retraces=retraces,
            schedule_builds=schedule_builds,
            precision=(
                self.engine.precision if self.engine is not None else "fp32"
            ),
            tuned_blocks=self._tuned_blocks,
        )

    def _maybe_autotune(self, coo: SparseCOO) -> None:
        """Apply the tuned kernel block shapes once per plan (spec.autotune
        on the Pallas engine): consult the persistent tuning table keyed by
        the problem fingerprint — a warm entry costs zero search trials —
        and rebind the engine's block sizes/layout. Runs under the exec
        lock (callers hold it)."""
        if (
            not self.spec.autotune
            or self.engine is None
            or self.engine.name != "pallas"
            or self._tuned_blocks is not None
        ):
            return
        from repro.kernels import autotune as _autotune

        cfg = _autotune.autotune(
            self.spec.shape, self.spec.ranks, coo.nnz,
            dtype=str(coo.values.dtype),
            precision=self.engine.precision,
            interpret=self.engine.resolved_interpret(),
        )
        self.engine.apply_blocks(cfg)
        self._tuned_blocks = cfg

    def lower_hlo(self, x: Any) -> Tuple[str, dict]:
        """Lower (without executing) this plan's compiled program on ``x``
        and return ``(optimized HLO text, program metadata)``.

        Covers every compiled sparse pipeline — the single-device scan, the
        snapshot segment program, and the sharded (plain and resumable)
        shard_map programs — so :meth:`analyze` and :meth:`lint` see the
        SAME executable the execution paths dispatch. The metadata names the
        program kind, how many sweeps one dispatch traces, which flat input
        parameters were donated, and the working precision — everything the
        ``repro.analysis`` contract linters key on.
        """
        spec, eng = self.spec, self.engine
        if spec.algorithm != "sparse":
            raise ValueError("lower_hlo() supports sparse plans only")
        coo = self._check_sparse_input(x)
        ndim = coo.ndim
        work_dtype = jnp.promote_types(coo.values.dtype, jnp.float32)
        with self._exec_lock, _obs_span(
            "plan.lower", engine=eng.name, sharded=spec.shard is not None
        ):
            self._maybe_autotune(coo)
            factors = self._init_factors(None, None)
            xnorm2 = jnp.square(coo.norm())
            tol = jnp.float32(spec.tol)
            if spec.shard is not None:
                sched = eng.shard_schedule(coo, self.mesh, self._nnz_axes)
                if spec.snapshot is not None:
                    seg = spec.snapshot.segment_len
                    prog = _hooi.build_sharded_program(
                        self.mesh, self._nnz_axes,
                        shape=spec.shape, ranks=spec.ranks,
                        method=spec.method, n_iter=seg, resumable=True,
                    )
                    core = jnp.zeros(tuple(spec.ranks), dtype=work_dtype)
                    lowered = prog.lower(
                        sched.indices, sched.values, tuple(factors), core,
                        xnorm2, tol, jnp.float32(jnp.inf),
                        jnp.asarray(False), jnp.int32(0),
                        jnp.int32(spec.n_iter),
                    )
                    # factors NOT donated: the host spills the carry to a
                    # checkpoint right after each segment dispatch.
                    kind, n_sweeps, donated = "sharded-segment", seg, ()
                else:
                    prog = _hooi.build_sharded_program(
                        self.mesh, self._nnz_axes,
                        shape=spec.shape, ranks=spec.ranks,
                        method=spec.method, n_iter=spec.n_iter,
                    )
                    lowered = prog.lower(
                        sched.indices, sched.values, tuple(factors),
                        xnorm2, tol,
                    )
                    kind, n_sweeps = "sharded", spec.n_iter
                    # donate_argnums=(2,): the factors tuple flattens to
                    # parameters 2 .. 2+ndim-1 of the entry computation.
                    donated = tuple(range(2, 2 + ndim))
            else:
                scheds = tuple(
                    eng.device_schedule(coo, m) for m in range(ndim)
                )
                common = dict(
                    shape=spec.shape, ranks=spec.ranks, method=spec.method,
                    engine_name=eng.name,
                    interpret=(
                        eng.resolved_interpret() if eng.name == "pallas"
                        else False
                    ),
                    use_reuse=eng.use_kron_reuse and eng.name == "xla",
                    precision=eng.precision, bl=eng.bl, bk=eng.bk,
                    fuse_core=eng.fuse_core and eng.name == "pallas",
                )
                if spec.snapshot is not None:
                    seg = spec.snapshot.segment_len
                    core = jnp.zeros(tuple(spec.ranks), dtype=work_dtype)
                    lowered = _hooi._segment_scan_sweeps.lower(
                        coo.indices, coo.values, tuple(factors), core,
                        xnorm2, tol, jnp.float32(jnp.inf),
                        jnp.asarray(False), jnp.int32(0),
                        jnp.int32(spec.n_iter), scheds,
                        segment_len=seg, **common,
                    )
                    kind, n_sweeps, donated = "segment", seg, ()
                else:
                    lowered = _hooi._scan_sweeps.lower(
                        coo.indices, coo.values, tuple(factors), xnorm2,
                        tol, scheds, n_iter=spec.n_iter, **common,
                    )
                    kind, n_sweeps = "scan", spec.n_iter
                    # donate_argnames=("factors",): parameters 2..2+ndim-1.
                    donated = tuple(range(2, 2 + ndim))
            with _obs_span("plan.compile", kind=kind):
                compiled = lowered.compile()
                text = compiled.as_text()
        mem = compiled.memory_analysis()
        meta = {
            "kind": kind,
            "ndim": ndim,
            "n_sweeps": n_sweeps,
            "donated_params": donated,
            "precision": eng.precision,
            "sharded": spec.shard is not None,
            "engine": eng.name,
            "working_dtype": str(jnp.dtype(work_dtype)),
            # the compiler's own per-device byte counts (None where the
            # backend reports none)
            "temp_bytes": None if mem is None else int(mem.temp_size_in_bytes),
            "argument_bytes": (
                None if mem is None else int(mem.argument_size_in_bytes)
            ),
        }
        return text, meta

    def analyze(self, x: Any) -> dict:
        """Lower (without executing) this plan's compiled program on ``x``
        and parse the optimized HLO into roofline terms: matmul FLOPs,
        approximate HBM bytes (both whole-program and per-sweep — while
        trip counts are multiplied in by ``repro.utils.hlo``) and the
        achieved arithmetic intensity; sharded programs additionally report
        collective bytes. The bench suite records these next to its
        timings, and CI gates on the per-sweep byte count — the megakernel's
        acceptance criterion (fused < split) is measured exactly here."""
        from repro.utils.hlo import analyze_hlo

        eng = self.engine
        text, meta = self.lower_hlo(x)
        s = analyze_hlo(text)
        n = max(1, meta["n_sweeps"])
        out = {
            "dot_flops": s.dot_flops,
            "dot_flops_per_sweep": s.dot_flops / n,
            "hbm_bytes": s.io_bytes,
            "hbm_bytes_per_sweep": s.io_bytes / n,
            "arithmetic_intensity": s.dot_flops / max(1.0, s.io_bytes),
            "engine": eng.name,
            "precision": eng.precision,
            "fuse_core": bool(eng.fuse_core and eng.name == "pallas"),
            "program": meta["kind"],
            "n_sweeps_traced": meta["n_sweeps"],
            "tuned_blocks": (
                dict(self._tuned_blocks._asdict())
                if self._tuned_blocks is not None else None
            ),
        }
        if meta["sharded"]:
            out["collective_bytes"] = s.total_coll_bytes
            out["collective_bytes_per_sweep"] = s.total_coll_bytes / n
        return out

    def lint(self, x: Any, baseline: Any = None) -> list:
        """Run the ``repro.analysis`` program-contract linters on this
        plan's compiled program (transfer/donation/precision/collective on
        the optimized HLO, scatter-race on the Pallas schedules, retrace
        hazards on the spec) and return the list of structured
        :class:`repro.analysis.Finding` — empty when every contract holds.
        ``baseline`` (a :class:`repro.analysis.Baseline`) filters findings
        through the committed suppression file."""
        from repro import analysis

        return analysis.lint_plan(self, x, baseline=baseline)

    def lower_batch_hlo(
        self,
        coos: Sequence[SparseCOO],
        keys: Any = None,
        pad_nnz_to: Optional[int] = None,
    ) -> Tuple[str, dict]:
        """Lower (without executing) the vmapped batched program
        :meth:`batch` dispatches on these members — the serving plane's ONE
        flush dispatch — and return ``(optimized HLO text, metadata)``.

        The batched program has its own contract surface, distinct from
        :meth:`lower_hlo`'s per-tensor pipelines: it donates NOTHING (the
        member tensors and PRNG keys are caller-owned buffers a flush must
        not consume — ``donated_params=()`` is the contract, not an
        omission), and its init/norm preamble is fused into the dispatch.
        Raises on plans whose ``batch()`` runs the sequential fallback:
        there is no shared program to lower — lint the per-member program
        with :meth:`lower_hlo`/:meth:`lint` instead.
        """
        spec = self.spec
        if spec.algorithm != "sparse":
            raise ValueError("lower_batch_hlo() supports sparse plans only")
        coos = [self._check_sparse_input(c) for c in coos]
        if not coos:
            raise ValueError(
                "lower_batch_hlo() needs at least one member tensor"
            )
        if keys is None:
            keys = [None] * len(coos)
        keys = list(keys)
        if len(keys) != len(coos):
            raise ValueError(f"got {len(keys)} keys for {len(coos)} tensors")
        if not self.batch_is_vmappable(keys):
            eng = self.engine.name if self.engine is not None else None
            raise ValueError(
                f"this plan's batch() runs the sequential fallback "
                f"(engine={eng!r}, precision={spec.precision!r}, "
                f"use_kron_reuse={spec.use_kron_reuse}, "
                f"shard={spec.shard is not None}, or non-vmappable keys) — "
                "there is no shared batched program to lower; lint the "
                "per-member program with lower_hlo()/lint() instead"
            )
        with self._exec_lock, _obs_span(
            "plan.lower", engine="xla", sharded=False, batch=len(coos)
        ):
            idx, val = pad_coo_batch(coos, target_nnz=pad_nnz_to)
            jkeys = _stack_keys(keys)
            lowered = _hooi._batched_scan_sweeps.lower(
                idx, val, jkeys, jnp.float32(spec.tol),
                shape=spec.shape, ranks=spec.ranks, method=spec.method,
                n_iter=spec.n_iter, dtype=spec.resolved_dtype(),
            )
            with _obs_span("plan.compile", kind="batched"):
                text = lowered.compile().as_text()
        work_dtype = jnp.promote_types(coos[0].values.dtype, jnp.float32)
        meta = {
            "kind": "batched",
            "ndim": coos[0].ndim,
            "batch": len(coos),
            "padded_nnz": int(idx.shape[1]),
            "n_sweeps": spec.n_iter,
            "donated_params": (),
            "precision": "fp32",  # spec.supports_batched_dispatch enforces it
            "sharded": False,
            "engine": "xla",
            "working_dtype": str(jnp.dtype(work_dtype)),
        }
        return text, meta

    def lint_batch(
        self, coos: Sequence[SparseCOO], keys: Any = None,
        baseline: Any = None,
    ) -> list:
        """:meth:`lint` for the vmapped batched program: transfer (HLO and
        jaxpr), donation (nothing may alias — the flush must not consume
        caller buffers), and precision contracts on the exact program
        ``batch()`` would dispatch for these members."""
        from repro import analysis

        return analysis.lint_batch_plan(self, coos, keys=keys,
                                        baseline=baseline)

    # -- sparse (paper Alg. 2) ---------------------------------------------

    def _run_sparse(self, coo: SparseCOO, key: Any, factors_init: Any,
                    pad_nnz_to: Optional[int] = None,
                    resume_from: Any = None, injector: Any = None) -> TuckerResult:
        if self.spec.snapshot is not None:
            return self._run_sparse_snapshot(
                coo, key, factors_init, pad_nnz_to, resume_from, injector
            )
        if resume_from is not None or injector is not None:
            raise ValueError(
                "resume_from/injector require a spec with "
                "snapshot=SnapshotSpec(...)"
            )
        self._maybe_autotune(coo)
        factors = self._init_factors(key, factors_init)
        xnorm2 = jnp.square(coo.norm())
        if self.spec.shard is not None:
            return self._run_sparse_sharded(coo, factors, xnorm2, pad_nnz_to)
        if pad_nnz_to is not None and int(pad_nnz_to) > coo.nnz:
            coo = coo.pad_to(int(pad_nnz_to))  # explicit zeros: shape-stable
        return self._run_sparse_scan(coo, factors, xnorm2)

    def _run_sparse_snapshot(self, coo: Any, key: Any, factors_init: Any,
                             pad_nnz_to: Any, resume_from: Any,
                             injector: Any) -> TuckerResult:
        """The fault-tolerant segment loop: the job's ``n_iter`` sweeps run
        as segments of ``snapshot.every_n_sweeps`` through the SAME scan
        skeleton as the uninterrupted pipelines (bit-identical per-sweep
        math), spilling the carry — factors, core, convergence state — to an
        atomic checkpoint after every segment. A dynamic ``total_sweeps``
        masks sweeps past the budget, so ONE compiled segment program serves
        every segment and every resume offset (the no-retrace contract).
        Each segment dispatch runs under ``run_with_retries``; a step-0
        snapshot before the first segment makes a kill at ANY boundary
        resumable."""
        from repro.checkpoint.manager import CheckpointManager
        from repro.core.distributed import psum_bytes_per_sweep
        from repro.runtime.fault_tolerance import FtConfig, run_with_retries
        from repro.tucker import snapshot as _snap

        spec, eng, snap = self.spec, self.engine, self.spec.snapshot
        state = None
        if resume_from is not None:
            if isinstance(resume_from, _snap.SnapshotState):
                state = resume_from
            else:
                with _obs_span("resume.restore",
                               directory=str(resume_from)) as rsp:
                    state = _snap.load_snapshot(str(resume_from))
                    rsp.set_attr("sweeps_done", int(state.sweeps_done))
            _snap.check_compatible(spec, state)

        # the relative error always normalizes by the REAL tensor norm,
        # computed before any explicit-zero padding (parity with _run_sparse).
        xnorm2 = jnp.square(coo.norm())
        core_dtype = jnp.promote_types(coo.values.dtype, jnp.float32)
        mesh_fp = mesh_fingerprint(self.mesh) if self.mesh is not None else None
        if state is not None:
            factors = [jnp.asarray(f) for f in state.factors]
            core = jnp.asarray(state.core, dtype=core_dtype)
            prev_err = float(state.prev_err)
            done = bool(state.done)
            n_done = int(state.sweeps_done)
            hist: List[float] = list(state.fit_history)
            resumed_from = n_done
        else:
            factors = self._init_factors(key, factors_init)
            core = jnp.zeros(tuple(spec.ranks), dtype=core_dtype)
            prev_err, done, n_done = float("inf"), False, 0
            hist = []
            resumed_from = None

        mgr = CheckpointManager(snap.directory, keep=snap.keep)
        ft = FtConfig(max_retries=snap.max_retries,
                      retry_backoff_s=snap.retry_backoff_s)
        retries = 0

        def on_retry(attempt: int, exc: BaseException) -> None:
            nonlocal retries
            retries += 1

        dispatches = 0
        snapshots_written = 0
        builds0 = eng.schedule_builds
        traces0 = _total_traces()
        segment_len = snap.segment_len
        total_sweeps = jnp.int32(spec.n_iter)
        tol = jnp.float32(spec.tol)

        # device-side twins of the host carry scalars: each dispatch feeds
        # the PREVIOUS dispatch's output arrays straight back in (no eager
        # host->device conversions on the hot segment loop).
        prev_err_d = jnp.float32(prev_err)
        done_d = jnp.asarray(done)
        n_done_d = jnp.int32(n_done)

        if self.spec.shard is not None:
            sched = eng.shard_schedule(
                coo, self.mesh, self._nnz_axes, pad_nnz_to=pad_nnz_to
            )
            coll_bytes = psum_bytes_per_sweep(
                spec.shape, spec.ranks,
                dtype=jnp.promote_types(coo.values.dtype, jnp.float32),
            )
            if self._sharded_segment_program is None:  # once per plan
                self._sharded_segment_program = _hooi.build_sharded_program(
                    self.mesh, self._nnz_axes,
                    shape=spec.shape, ranks=spec.ranks, method=spec.method,
                    n_iter=segment_len, resumable=True,
                )
            # the segment outputs come back replicated over the mesh; commit
            # the first segment's carry the same way, so every segment (the
            # first after a resume included) hits one compiled program.
            replicated = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec()
            )
            factors, core, prev_err_d, done_d, n_done_d = jax.device_put(
                (factors, core, prev_err_d, done_d, n_done_d), replicated
            )

            def dispatch() -> Any:
                out = self._sharded_segment_program(
                    sched.indices, sched.values, tuple(factors), core,
                    xnorm2, tol, prev_err_d, done_d, n_done_d, total_sweeps,
                )
                _hooi.SWEEP_DISPATCH_COUNTS.tick(("sharded", "scan"))
                return out
        else:
            if pad_nnz_to is not None and int(pad_nnz_to) > coo.nnz:
                coo = coo.pad_to(int(pad_nnz_to))
            use_reuse = eng.use_kron_reuse and eng.name == "xla"
            scheds = tuple(
                eng.device_schedule(coo, m) for m in range(coo.ndim)
            )
            interpret = (
                eng.resolved_interpret() if eng.name == "pallas" else False
            )

            def dispatch() -> Any:
                out = _hooi._segment_scan_sweeps(
                    coo.indices, coo.values, tuple(factors), core,
                    xnorm2, tol, prev_err_d, done_d, n_done_d, total_sweeps,
                    scheds,
                    shape=spec.shape, ranks=spec.ranks, method=spec.method,
                    segment_len=segment_len, engine_name=eng.name,
                    interpret=interpret, use_reuse=use_reuse,
                    precision=eng.precision, bl=eng.bl, bk=eng.bk,
                    fuse_core=eng.fuse_core and eng.name == "pallas",
                )
                _hooi.SWEEP_DISPATCH_COUNTS.tick((eng.name, "scan"))
                return out

        last_spill = time.monotonic()

        def save(step: Any, decision: str) -> None:
            # ``decision`` names why this boundary spilled — "initial",
            # "interval" (sweep-count cadence), "wall-clock"
            # (every_seconds elapsed), or "final" — and rides on the span
            # so heterogeneous-fleet cadence is visible in traces.
            nonlocal snapshots_written, last_spill
            with _obs_span("snapshot.spill", step=int(step),
                           decision=decision):
                _snap.save_snapshot(
                    mgr, spec, factors=factors, core=core, prev_err=prev_err,
                    done=done, sweeps_done=step, fit_history=hist,
                    mesh_fp=mesh_fp,
                )
            _MX_SNAPSHOTS.inc()
            snapshots_written += 1
            last_spill = time.monotonic()

        if state is None:
            # a kill at ANY later boundary finds a resumable job
            save(0, "initial")

        while n_done < spec.n_iter and not done:

            def step() -> Any:
                if injector is not None:
                    # consulted inside the retry wrapper: a transient
                    # injected failure retries in place (the injector is
                    # one-shot); with max_retries=0 it propagates AFTER the
                    # last snapshot, which is the kill the resume tests take.
                    injector.maybe_fail(n_done)
                return dispatch()

            with _obs_span(
                "sweep.dispatch", program="segment",
                engine="sharded" if spec.shard is not None else eng.name,
                segment_len=segment_len, sweeps_done=n_done,
            ) as dsp:
                fs, core_d, hist_dev, carry = run_with_retries(
                    step, ft, on_retry=on_retry
                )
                dispatches += 1
                factors, core = list(fs), core_d
                prev_err_d, done_d, n_done_d = carry
                seg_hist = np.asarray(_hooi._fetch_history(hist_dev))
                hist.extend(
                    float(h) for h in seg_hist[seg_hist != _hooi._SKIPPED]
                )
                # the one host sync per segment (the snapshot layer's
                # overhead): the carry scalars decide loop exit and ride
                # into the manifest.
                sweeps0 = n_done
                prev_err, done, n_done = (
                    float(np.asarray(prev_err_d)),
                    bool(np.asarray(done_d)),
                    int(np.asarray(n_done_d)),
                )
                dsp.set_attr("sweeps_run", n_done)
            if self.spec.shard is not None:
                _MX_COLLECTIVE_BYTES.inc(coll_bytes * (n_done - sweeps0))
            if done or n_done >= spec.n_iter:
                save(n_done, "final")
            elif snap.every_seconds is None:
                save(n_done, "interval")
            elif time.monotonic() - last_spill >= snap.every_seconds:
                save(n_done, "wall-clock")
            else:
                # boundary reached but the wall-clock interval has not
                # elapsed: skip the write (the final boundary always spills)
                _obs_event(
                    "snapshot.skip", step=n_done, decision="wall-clock",
                    elapsed_s=time.monotonic() - last_spill,
                )

        res = self._result(
            core, list(factors), np.asarray(hist, dtype=np.float32),
            engine=eng.name,
            dispatches=dispatches,
            retraces=_total_traces() - traces0,
            schedule_builds=eng.schedule_builds - builds0,
        )
        res.snapshots_written = snapshots_written
        res.resumed_from_sweep = resumed_from
        res.retries = retries
        if self.spec.shard is not None:
            res.collective_bytes_per_sweep = coll_bytes
            res.shard_imbalance = sched.imbalance
        return res

    def _run_sparse_sharded(self, coo: Any, factors: Any, xnorm2: Any,
                            pad_nnz_to: Optional[int] = None) -> TuckerResult:
        """One shard_map-wrapped scan dispatch over the plan's mesh: nonzeros
        sharded (device_put once, via the engine's ShardSchedule cache),
        factors replicated, one psum per mode per sweep."""
        from repro.core.distributed import psum_bytes_per_sweep

        spec, eng = self.spec, self.engine
        builds0 = eng.schedule_builds
        sched = eng.shard_schedule(
            coo, self.mesh, self._nnz_axes, pad_nnz_to=pad_nnz_to
        )
        if self._sharded_program is None:  # once per plan (under _exec_lock)
            self._sharded_program = _hooi.build_sharded_program(
                self.mesh, self._nnz_axes,
                shape=spec.shape, ranks=spec.ranks, method=spec.method,
                n_iter=spec.n_iter,
            )
        traces0 = _total_traces()
        coll_bytes = psum_bytes_per_sweep(
            spec.shape, spec.ranks,
            # the psum payload runs at the program's working precision
            dtype=jnp.promote_types(coo.values.dtype, jnp.float32),
        )
        with _obs_span("sweep.dispatch", program="sharded", engine=eng.name,
                       collective_bytes_per_sweep=int(coll_bytes)) as dsp:
            fs, core, hist_dev = self._sharded_program(
                sched.indices, sched.values, tuple(factors), xnorm2,
                jnp.float32(spec.tol),
            )
            _hooi.SWEEP_DISPATCH_COUNTS.tick(("sharded", "scan"))
            hist = np.asarray(_hooi._fetch_history(hist_dev))  # the one d2h transfer
            n_done = int(np.sum(hist != _hooi._SKIPPED))
            dsp.set_attr("sweeps_run", n_done)
            dsp.set_attr("retraces", _total_traces() - traces0)
        _MX_COLLECTIVE_BYTES.inc(coll_bytes * n_done)
        res = self._result(
            core, list(fs), hist[:n_done],
            engine=eng.name,
            dispatches=1,
            retraces=_total_traces() - traces0,
            schedule_builds=eng.schedule_builds - builds0,
        )
        res.collective_bytes_per_sweep = coll_bytes
        res.shard_imbalance = sched.imbalance
        return res

    def _run_sparse_scan(self, coo: Any, factors: Any, xnorm2: Any) -> TuckerResult:
        spec, eng = self.spec, self.engine
        use_reuse = eng.use_kron_reuse and eng.name == "xla"
        builds0 = eng.schedule_builds
        scheds = tuple(eng.device_schedule(coo, m) for m in range(coo.ndim))
        traces0 = _total_traces()
        with _obs_span("sweep.dispatch", program="scan",
                       engine=eng.name, nnz=int(coo.nnz)) as dsp:
            fs, core, hist_dev = _hooi._scan_sweeps(
                coo.indices,
                coo.values,
                tuple(factors),
                xnorm2,
                jnp.float32(spec.tol),
                scheds,
                shape=spec.shape,
                ranks=spec.ranks,
                method=spec.method,
                n_iter=spec.n_iter,
                engine_name=eng.name,
                interpret=eng.resolved_interpret() if eng.name == "pallas" else False,
                use_reuse=use_reuse,
                precision=eng.precision,
                bl=eng.bl,
                bk=eng.bk,
                fuse_core=eng.fuse_core and eng.name == "pallas",
            )
            _hooi.SWEEP_DISPATCH_COUNTS.tick((eng.name, "scan"))
            hist = np.asarray(_hooi._fetch_history(hist_dev))  # the one d2h transfer
            n_done = int(np.sum(hist != _hooi._SKIPPED))
            dsp.set_attr("sweeps_run", n_done)
            dsp.set_attr("retraces", _total_traces() - traces0)
        return self._result(
            core, list(fs), hist[:n_done],
            engine=eng.name,
            dispatches=1,
            retraces=_total_traces() - traces0,
            schedule_builds=eng.schedule_builds - builds0,
        )

    def _run_sparse_vmapped(self, coos: Any, keys: Any,
                            pad_nnz_to: Any = None) -> List[TuckerResult]:
        spec = self.spec
        # host-side assembly runs OUTSIDE the dispatch lock: another flush
        # of this plan may be in device execution while this one pads and
        # stacks — the assembly touches no shared plan state (pure numpy
        # over the caller's tensors).
        with _obs_span("plan.assemble", batch=len(coos)):
            idx, val = pad_coo_batch(coos, target_nnz=pad_nnz_to)
            jkeys = _stack_keys(keys)
        with self._dispatch_lock, _obs_span(
            "sweep.dispatch", program="batched", engine="xla",
            batch=len(coos), padded_nnz=int(idx.shape[1]),
        ) as dsp:
            traces0 = _total_traces()
            # init + norm + all sweeps for all k tensors: ONE fused dispatch
            cores, factors, hist_dev = _hooi._batched_scan_sweeps(
                idx, val, jkeys, jnp.float32(spec.tol),
                shape=spec.shape,
                ranks=spec.ranks,
                method=spec.method,
                n_iter=spec.n_iter,
                dtype=spec.resolved_dtype(),
            )
            _hooi.SWEEP_DISPATCH_COUNTS.tick(("xla", "scan"))
            hists = np.asarray(_hooi._fetch_history(hist_dev))  # (k, n_iter)
            retraces = _total_traces() - traces0
            dsp.set_attr("retraces", retraces)
        results = []
        for i in range(len(coos)):
            hist = hists[i]
            n_done = int(np.sum(hist != _hooi._SKIPPED))
            results.append(
                self._result(
                    cores[i], list(factors[i]), hist[:n_done],
                    engine="xla",
                    dispatches=1 if i == 0 else 0,
                    retraces=retraces if i == 0 else 0,
                    schedule_builds=0,
                )
            )
        return results

    # -- dense (paper Alg. 1) ----------------------------------------------

    def _run_dense(self, x: Any, key: Any, factors_init: Any) -> TuckerResult:
        from repro.core.coo import fold_dense, unfold_dense
        from repro.core.qrp import factor_update
        from repro.core.ttm import ttm_chain

        spec = self.spec
        x = jnp.asarray(x)
        if tuple(x.shape) != spec.shape:
            raise ValueError(
                f"input shape {tuple(x.shape)} does not match the planned "
                f"spec shape {spec.shape}"
            )
        dt = spec.resolved_dtype()
        if dt is not None and x.dtype != dt:
            x = x.astype(dt)
        n = x.ndim
        ranks = spec.ranks
        factors = self._init_factors(key, factors_init)
        xnorm2 = jnp.sum(
            jnp.square(x.astype(jnp.promote_types(x.dtype, jnp.float32)))
        )
        hist: List[float] = []
        core = None
        for _ in range(spec.n_iter):
            for mode in range(n):
                y = ttm_chain(x, factors, skip=mode, transpose=True)
                y_n = unfold_dense(y, mode)
                factors[mode] = factor_update(y_n, ranks[mode], spec.method)
            # core from the last power iterate: G = Y x_N U_N^T (Eq. 10).
            g_n = factors[n - 1].T @ unfold_dense(y, n - 1)
            core = fold_dense(g_n, n - 1, list(ranks))
            err = jnp.sqrt(
                jnp.maximum(xnorm2 - jnp.sum(jnp.square(core)), 0.0)
            ) / jnp.sqrt(xnorm2)
            hist.append(float(err))
            if spec.tol and len(hist) > 1 and abs(hist[-2] - hist[-1]) < spec.tol:
                break
        return self._result(
            core, factors, np.asarray(hist),
            engine="xla",
            dispatches=0,  # eager dense loop: dispatches not tracked
            retraces=0,
            schedule_builds=0,
        )

    # -- completion (EM over the dense runner) -------------------------------

    def _run_complete(self, coo: SparseCOO, key: Any,
                      factors_init: Any = None) -> TuckerResult:
        """EM-style Tucker completion (paper use cases: MRI reconstruction
        [27], process-variation prediction [15]): alternate dense HOOI with
        imputation of the missing entries from the current reconstruction.
        ``factors_init`` seeds the first EM round."""
        from repro.core.reconstruct import reconstruct_dense

        x_obs = coo.to_dense()
        mask = SparseCOO(
            coo.indices, jnp.ones_like(coo.values), coo.shape
        ).to_dense() > 0
        x = x_obs
        res = None
        factors = factors_init
        for _ in range(self.spec.n_rounds):
            res = self._run_dense(x, key, factors_init=factors)
            factors = res.factors  # warm start: EM converges in a few rounds
            xhat = reconstruct_dense(res.core, res.factors)
            x = jnp.where(mask, x_obs, xhat)
        return res


# ---------------------------------------------------------------------------
# The plan cache: one TuckerPlan (and therefore one engine + one compiled
# program family) per (spec, resolved engine). LRU with optional capacity —
# a long-lived service must not pin every compiled program + device-resident
# schedule it has ever seen — and thread-safe: concurrent ``submit`` callers
# share one plan instead of racing a double construction of the same spec.
# ---------------------------------------------------------------------------

# (spec, resolved engine) — plus the mesh fingerprint for sharded specs, so
# re-planning on an identical mesh is a cache hit while a changed device set
# can never silently reuse the wrong mesh's compiled program.
PlanCacheKey = Tuple
EvictionHook = Callable[[PlanCacheKey, TuckerPlan], None]


class PlanCache:
    """Thread-safe LRU cache of :class:`TuckerPlan` keyed by
    (spec, resolved engine name).

    ``capacity=None`` means unbounded (the historical behavior; right for
    scripts and benchmarks). A serving process sets a capacity so dropping a
    spec from rotation eventually frees its engine's device-resident
    schedules; eviction hooks let it observe (and e.g. count) those drops.
    Hooks fire outside the lock — an eviction hook may safely re-enter the
    cache.
    """

    def __init__(self, capacity: Optional[int] = None) -> None:
        self._lock = threading.RLock()
        self._entries: "OrderedDict[PlanCacheKey, TuckerPlan]" = OrderedDict()
        self._capacity = capacity
        self._hooks: List[EvictionHook] = []
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # bumps on every set_capacity call: lets a scoped capacity holder
        # (repro.serve) detect a manual override even to the same value
        self.capacity_version = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def capacity(self) -> Optional[int]:
        return self._capacity

    def get_or_create(
        self, key: PlanCacheKey, factory: Callable[[], TuckerPlan]
    ) -> TuckerPlan:
        """Return the cached plan for ``key``. Concurrent callers always end
        up sharing ONE plan object (one engine, one schedule cache, one
        compiled-program family): the build runs OUTSIDE the lock — a cold
        spec's construction must not stall cache hits for hot specs on a
        serving flush path — and a racing builder discards its plan in favor
        of the first one inserted, so no second copy is ever used (or
        compiled against)."""
        with self._lock:
            cached = self._entries.get(key)
            if cached is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                _MX_PLAN_HITS.inc()
                _obs_event("plan.cache.lookup", hit=True)
                return cached
        with _obs_span("plan.cache.build"):
            built = factory()
        evicted = []
        with self._lock:
            cached = self._entries.get(key)
            if cached is not None:  # lost the build race: share the winner
                self._entries.move_to_end(key)
                self.hits += 1
                _MX_PLAN_HITS.inc()
                _obs_event("plan.cache.lookup", hit=True, lost_race=True)
                return cached
            self.misses += 1
            _MX_PLAN_MISSES.inc()
            _obs_event("plan.cache.lookup", hit=False)
            self._entries[key] = built
            while self._capacity is not None and len(self._entries) > self._capacity:
                evicted.append(self._entries.popitem(last=False))
                self.evictions += 1
                _MX_PLAN_EVICTIONS.inc()
        for k, p in evicted:
            _obs_event("plan.cache.evict")
            self._fire_hooks(k, p)
        return built

    def set_capacity(self, capacity: Optional[int]) -> None:
        """Set (or lift, with ``None``) the LRU capacity, evicting the
        least-recently-used plans immediately if over the new bound."""
        if capacity is not None and int(capacity) < 1:
            raise ValueError(f"plan cache capacity must be >= 1, got {capacity}")
        evicted = []
        with self._lock:
            self._capacity = None if capacity is None else int(capacity)
            self.capacity_version += 1
            while self._capacity is not None and len(self._entries) > self._capacity:
                evicted.append(self._entries.popitem(last=False))
                self.evictions += 1
                _MX_PLAN_EVICTIONS.inc()
        for k, p in evicted:
            self._fire_hooks(k, p)

    def add_eviction_hook(self, hook: EvictionHook) -> Callable[[], None]:
        """Register ``hook(key, plan)`` to run on every eviction (capacity
        or ``clear``). Returns a zero-argument deregistration callable."""
        with self._lock:
            self._hooks.append(hook)

        def remove() -> None:
            with self._lock:
                if hook in self._hooks:
                    self._hooks.remove(hook)

        return remove

    def clear(self) -> None:
        """Drop all cached plans (test isolation / freeing device
        schedules). Eviction hooks observe every dropped plan."""
        with self._lock:
            dropped = list(self._entries.items())
            self._entries.clear()
        for k, p in dropped:
            self._fire_hooks(k, p)

    def info(self) -> dict:
        """Counters snapshot: size/capacity/hits/misses/evictions."""
        with self._lock:
            return {
                "size": len(self._entries),
                "capacity": self._capacity,
                "capacity_version": self.capacity_version,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
            }

    def _fire_hooks(self, key: PlanCacheKey, plan: TuckerPlan) -> None:
        with self._lock:
            hooks = list(self._hooks)
        for hook in hooks:
            hook(key, plan)


_PLAN_CACHE = PlanCache()


def plan(spec: TuckerSpec, *, engine: Optional[SweepEngine] = None,
         mesh: Any = None) -> TuckerPlan:
    """Build (or fetch the cached) :class:`TuckerPlan` for ``spec``.

    Plans are cached per (spec, resolved engine), so every caller asking for
    the same problem shares one engine — and its schedule caches — and one
    compiled program. The cache is thread-safe (concurrent ``submit`` callers
    of ``repro.serve.TuckerService`` never double-build a spec) and LRU-bounded
    when :func:`set_plan_cache_capacity` set a capacity. Passing a prebuilt
    ``engine`` bypasses the cache and wraps that engine directly (its cached
    device schedules are reused across calls).

    ``mesh`` (sharded specs only) pins execution to an explicit device mesh
    — its total device count must equal ``spec.shard.num_devices``, and the
    nonzeros shard over ALL its axes. Default: a fresh 1-axis mesh over the
    first ``num_devices`` attached devices (:func:`mesh_for_shard`). Either
    way the plan cache keys on the mesh fingerprint, so an identical mesh is
    a cache hit and a changed device set never reuses the wrong executable.
    """
    if engine is not None:
        return TuckerPlan(spec, engine=engine, _mesh=mesh)
    if mesh is not None and spec.shard is None:
        raise ValueError("mesh= only applies to specs with a ShardSpec")
    if spec.algorithm != "sparse":
        key = (spec, "xla")
    elif spec.shard is not None:
        # the key carries the mesh fingerprint: identical mesh -> cache hit
        # (one compiled shard_map program per mesh), changed device set ->
        # a fresh plan, never the wrong mesh's executable.
        mesh = mesh if mesh is not None else mesh_for_shard(spec.shard)
        key = (spec, "xla", mesh_fingerprint(mesh))
        return _PLAN_CACHE.get_or_create(
            key, lambda: TuckerPlan(spec, _resolved="xla", _mesh=mesh)
        )
    else:
        # resolve on every lookup: 'auto'/'pallas' may map differently (and
        # warn) as backend availability changes.
        key = (spec, resolve_engine(spec.engine))
    return _PLAN_CACHE.get_or_create(key, lambda: TuckerPlan(spec, _resolved=key[1]))


def clear_plan_cache() -> None:
    """Drop all cached plans (test isolation / freeing device schedules)."""
    _PLAN_CACHE.clear()


def set_plan_cache_capacity(capacity: Optional[int]) -> None:
    """Bound the global plan cache to ``capacity`` plans (LRU eviction), or
    lift the bound with ``None``. Takes effect immediately."""
    _PLAN_CACHE.set_capacity(capacity)


def plan_cache_info() -> dict:
    """Size/capacity/hit/miss/eviction counters of the global plan cache."""
    return _PLAN_CACHE.info()


def add_plan_eviction_hook(hook: EvictionHook) -> Callable[[], None]:
    """Observe global plan-cache evictions; returns a deregistration
    callable. See :meth:`PlanCache.add_eviction_hook`."""
    return _PLAN_CACHE.add_eviction_hook(hook)


def resume(spec: TuckerSpec, x: Any, directory: Optional[str] = None, *,
           key: Any = None, mesh: Any = None,
           injector: Any = None) -> TuckerResult:
    """Restart a snapshotted decomposition from its latest checkpoint.

    Loads the newest snapshot in ``directory`` (default: the spec's own
    ``snapshot.directory``), verifies it describes the same problem
    (shape/ranks/method/algorithm), and runs the remaining sweeps through the
    planned pipeline — continuing the convergence state bit-for-bit, so the
    final factors/core match an uninterrupted run of the same spec.

    Elastic: a sharded spec whose ``num_devices`` exceeds the devices now
    attached is clamped (with a warning) instead of dying — the snapshot
    carry is replicated, so only the plan re-shards: the mesh-fingerprint
    plan cache builds a fresh plan for the new mesh and the ShardSchedule is
    redistributed over it. A snapshot written by a 4-device job resumes on 2
    (or 1) unchanged.

    ``key`` is accepted for API symmetry but ignored — the factors come from
    the snapshot, not a fresh init.
    """
    from repro.tucker import snapshot as _snap

    if spec.snapshot is None:
        raise ValueError(
            "resume() requires a spec with snapshot=SnapshotSpec(...)"
        )
    directory = directory if directory is not None else spec.snapshot.directory
    with _obs_span("resume.restore", directory=str(directory)) as rsp:
        state = _snap.load_snapshot(directory)
        rsp.set_attr("sweeps_done", int(state.sweeps_done))
    _snap.check_compatible(spec, state)
    if spec.shard is not None and mesh is None:
        n_avail = len(jax.devices())
        if spec.shard.num_devices > n_avail:
            warnings.warn(
                f"resuming a {spec.shard.num_devices}-device job on "
                f"{n_avail} attached device(s): clamping "
                f"ShardSpec.num_devices — the replicated snapshot carry "
                f"restores unchanged and the nonzeros re-shard over the "
                f"smaller mesh",
                RuntimeWarning,
                stacklevel=2,
            )
            spec = dataclasses.replace(
                spec,
                shard=dataclasses.replace(spec.shard, num_devices=n_avail),
            )
    p = plan(spec, mesh=mesh)
    return p(x, key=key, resume_from=state, injector=injector)


def decompose(x: Any, ranks: Sequence[int], *, key: Any = None,
              factors_init: Any = None, **spec_kwargs: Any) -> TuckerResult:
    """One-shot convenience: infer the spec from ``x``, plan (cached), run.

    ``spec_kwargs`` are :class:`TuckerSpec` fields (method, engine, n_iter,
    tol, dtype, precision, use_kron_reuse, algorithm, n_rounds, shard,
    snapshot).
    """
    spec = spec_for(x, ranks, **spec_kwargs)
    return plan(spec)(x, key=key, factors_init=factors_init)
