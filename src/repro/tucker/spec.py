"""TuckerSpec — the frozen problem description behind the plan/execute API.

A spec captures *everything* that determines the compiled decomposition
program: tensor shape, multilinear ranks, factor-update method, sweep engine,
sweep budget, tolerance, working dtype, and the Kron-reuse flag.
Validation happens exactly once, at construction; the spec is hashable so
``repro.tucker.plan`` can key its plan cache (and therefore the jit compile
cache) on it — repeated calls on same-shape tensors hit the cache with zero
retraces.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import numpy as np

from repro.core.engine import ENGINES
from repro.core.hooi import effective_ranks

METHODS = ("svd", "householder", "gram")
ALGORITHMS = ("sparse", "dense", "complete")
FACTOR_POLICIES = ("replicated",)
# mirror of repro.kernels.kron_kernel.PRECISIONS (kept literal so building a
# spec never imports the kernel stack; parity is asserted in tests).
PRECISIONS = ("fp32", "bf16_fp32acc")


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Frozen description of the sharded-execution axis of a problem.

    The paper's hybrid split (nnz-scaling Kron/TTM work on the accelerator,
    small replicated QRP on the CPU) becomes a data-parallel mesh layout:
    COO nonzeros are sharded along ``axis`` across ``num_devices`` devices
    (padded to an even :func:`repro.sparse.layout.shard_pad_nnz` multiple),
    factor matrices follow ``factor_policy``, and one ``psum`` per mode per
    sweep completes each partial Kron-accumulation. Hashable so it can ride
    inside :class:`TuckerSpec` and key the plan cache.

    Attributes:
      num_devices: shards along the nnz axis (the mesh size). Must not
        exceed the attached device count — on a 1-CPU host, force more with
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the
        first jax import.
      axis: the mesh axis name the nonzeros shard over.
      factor_policy: how factors are laid out across the mesh. Only
        'replicated' exists today (they are small: I_n x R_n, and the QRP
        update is deterministic, so no broadcast is ever needed).
    """

    num_devices: int
    axis: str = "nnz"
    factor_policy: str = "replicated"

    def __post_init__(self) -> None:
        if int(self.num_devices) < 1:
            raise ValueError(
                f"num_devices must be >= 1, got {self.num_devices}"
            )
        if not self.axis or not isinstance(self.axis, str):
            raise ValueError(f"axis must be a non-empty string, got {self.axis!r}")
        if self.factor_policy not in FACTOR_POLICIES:
            raise ValueError(
                f"factor_policy must be one of {FACTOR_POLICIES}, got "
                f"{self.factor_policy!r}"
            )
        object.__setattr__(self, "num_devices", int(self.num_devices))


@dataclasses.dataclass(frozen=True)
class SnapshotSpec:
    """Frozen description of the fault-tolerance axis of a problem: snapshot
    the sweep carry every ``every_n_sweeps`` ALS sweeps so a preempted job
    resumes from its latest manifest instead of refitting from scratch.

    The compiled pipeline runs in chunked scan *segments* of
    ``every_n_sweeps`` sweeps; after each segment the factor/core/convergence
    carry spills to host once and is written atomically through
    :class:`repro.checkpoint.manager.CheckpointManager`. One compiled segment
    program serves the whole job — the short final segment and any resume
    offset included — so snapshotting keeps the no-retrace contract.
    Hashable so it can ride inside :class:`TuckerSpec`; two specs differing
    only in ``directory`` share the same jit cache (the program is keyed on
    shapes and statics, not paths).

    Cadence is sweep-count based (``every_n_sweeps``), wall-clock based
    (``every_seconds``), or both: with ``every_seconds`` the segment loop
    still runs sweep-granular segments (``segment_len`` sweeps each — the
    compiled program cannot be interrupted mid-sweep) but only *writes* a
    checkpoint when the interval has elapsed since the last write, so a slow
    host and a fast host on the same spec checkpoint at comparable wall-clock
    cadence instead of comparable sweep counts. The initial (step-0) and
    final snapshots are always written — a kill at any boundary stays
    resumable, and the finished state is always durable. At least one of the
    two cadences must be set.

    Attributes:
      every_n_sweeps: sweeps per segment (the sweep-count snapshot
        interval), or None for a purely wall-clock cadence.
      directory: checkpoint root, one job per directory — concurrent jobs
        snapshotting into one directory would interleave step sequences.
      every_seconds: minimum seconds between checkpoint writes, or None for
        a purely sweep-count cadence. 0.0 writes at every segment boundary.
      keep: snapshots retained (older ones are GC'd), per CheckpointManager.
      max_retries: transient-failure retries per segment dispatch
        (``runtime.fault_tolerance.run_with_retries``); 0 = fail fast and
        rely on resume.
      retry_backoff_s: base of the exponential retry backoff.
    """

    every_n_sweeps: Optional[int] = None
    directory: str = ""
    every_seconds: Optional[float] = None
    keep: int = 3
    max_retries: int = 0
    retry_backoff_s: float = 0.05

    @property
    def segment_len(self) -> int:
        """Sweeps per compiled segment dispatch: ``every_n_sweeps`` when
        set, else 1 (wall-clock cadence decides per boundary whether the
        carry actually spills)."""
        return self.every_n_sweeps if self.every_n_sweeps is not None else 1

    def __post_init__(self) -> None:
        if self.every_n_sweeps is None and self.every_seconds is None:
            raise ValueError(
                "SnapshotSpec needs a cadence: set every_n_sweeps, "
                "every_seconds, or both"
            )
        if self.every_n_sweeps is not None and int(self.every_n_sweeps) < 1:
            raise ValueError(
                f"every_n_sweeps must be >= 1, got {self.every_n_sweeps}"
            )
        if self.every_seconds is not None and not (
            float(self.every_seconds) >= 0.0  # also rejects NaN
        ):
            raise ValueError(
                f"every_seconds must be >= 0, got {self.every_seconds}"
            )
        if not self.directory or not isinstance(self.directory, str):
            raise ValueError(
                f"directory must be a non-empty string, got {self.directory!r}"
            )
        if int(self.keep) < 1:
            raise ValueError(f"keep must be >= 1, got {self.keep}")
        if int(self.max_retries) < 0:
            raise ValueError(
                f"max_retries must be >= 0, got {self.max_retries}"
            )
        if not (float(self.retry_backoff_s) >= 0.0):  # also rejects NaN
            raise ValueError(
                f"retry_backoff_s must be >= 0, got {self.retry_backoff_s}"
            )
        if self.every_n_sweeps is not None:
            object.__setattr__(
                self, "every_n_sweeps", int(self.every_n_sweeps)
            )
        if self.every_seconds is not None:
            object.__setattr__(self, "every_seconds", float(self.every_seconds))
        object.__setattr__(self, "keep", int(self.keep))
        object.__setattr__(self, "max_retries", int(self.max_retries))
        object.__setattr__(
            self, "retry_backoff_s", float(self.retry_backoff_s)
        )


def _canonical_dtype(dtype: Any) -> str:
    """Normalize a dtype spec to a canonical string ("auto" = follow the
    jax x64 flag at execution time)."""
    if dtype is None or dtype == "auto":
        return "auto"
    import jax.numpy as jnp

    return jnp.dtype(dtype).name


@dataclasses.dataclass(frozen=True)
class TuckerSpec:
    """Frozen, validated description of one Tucker decomposition problem.

    Attributes:
      shape: dense logical shape (I_1, ..., I_N) of the input tensor.
      ranks: requested multilinear rank; clamped to the representable
        fixpoint (R_n <= min(I_n, prod_{t != n} R_t)) at construction.
      method: factor update — 'householder' (paper QRP), 'gram' (TPU QRP
        variant) or 'svd'.
      engine: 'xla', 'pallas' or 'auto' — how the sweep hot loops execute
        (see ``repro.core.engine``).
      n_iter: max ALS sweeps per decomposition.
      tol: early-exit threshold on consecutive fit deltas (0 disables). A
        *dynamic* argument of the compiled pipeline — changing it never
        recompiles.
      dtype: working precision of values/factors; "auto" follows the jax
        x64 flag.
      precision: sweep compute precision — 'fp32' (full working precision)
        or 'bf16_fp32acc' (bf16 operand loads/multiplies in the Kron and
        TTM kernels with f32 VMEM accumulators; the XLA engine mirrors it
        with bf16 Kron rows + f32 scatter-add). Incompatible with shard
        (the sharded program runs fp32).
      autotune: search the Pallas kernel block shapes (bn/bi/bl/bk/layout)
        for this problem at the plan's first execution, consulting the
        persistent on-disk tuning table (``repro.kernels.autotune``) — a
        warm table entry costs zero search. No-op on the XLA engine.
      use_kron_reuse: the paper's Sec. III-C Kronecker-row dedup on the XLA
        engine (the Pallas schedule has its own reuse layout).
      algorithm: 'sparse' (paper Alg. 2, COO input), 'dense' (Alg. 1,
        dense input) or 'complete' (EM-style completion, COO input).
      n_rounds: EM rounds for algorithm='complete' (ignored otherwise).
      shard: a :class:`ShardSpec` to run the compiled sweep pipeline
        data-parallel over a device mesh (nonzeros sharded, factors
        replicated, one psum per mode per sweep), or ``None`` for
        single-device execution. Requires the sparse algorithm with the
        plain XLA engine (no Kron-reuse — its dedup plan is a per-tensor
        host artifact that cannot shard).
      snapshot: a :class:`SnapshotSpec` to run the compiled sweep pipeline
        in chunked segments with the carry checkpointed at each interval
        (resumable via ``tucker.resume``), or ``None`` for the one-dispatch
        fire-and-forget run. Requires the sparse algorithm; composes with
        ``shard`` (elastic resume onto a different device count) and with
        every engine.
    """

    shape: Tuple[int, ...]
    ranks: Tuple[int, ...]
    method: str = "householder"
    engine: str = "auto"
    n_iter: int = 5
    tol: float = 0.0
    dtype: str = "auto"
    precision: str = "fp32"
    autotune: bool = False
    use_kron_reuse: bool = False
    algorithm: str = "sparse"
    n_rounds: int = 10
    shard: Optional[ShardSpec] = None
    snapshot: Optional[SnapshotSpec] = None

    def __post_init__(self) -> None:
        shape = tuple(int(s) for s in self.shape)
        if not shape or any(s < 1 for s in shape):
            raise ValueError(f"shape must be positive, got {self.shape}")
        ranks = tuple(int(r) for r in self.ranks)
        if len(ranks) != len(shape):
            raise ValueError(
                f"ranks {ranks} and shape {shape} disagree on tensor order"
            )
        if any(r < 1 for r in ranks):
            raise ValueError(f"ranks must be positive, got {self.ranks}")
        ranks = tuple(effective_ranks(shape, ranks))
        if self.method not in METHODS:
            raise ValueError(f"method must be one of {METHODS}, got {self.method!r}")
        if self.engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, got {self.engine!r}")
        if self.algorithm not in ALGORITHMS:
            raise ValueError(
                f"algorithm must be one of {ALGORITHMS}, got {self.algorithm!r}"
            )
        if int(self.n_iter) < 1:
            raise ValueError(f"n_iter must be >= 1, got {self.n_iter}")
        if int(self.n_rounds) < 1:
            raise ValueError(f"n_rounds must be >= 1, got {self.n_rounds}")
        if not (float(self.tol) >= 0.0):  # also rejects NaN
            raise ValueError(f"tol must be >= 0, got {self.tol}")
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"precision must be one of {PRECISIONS}, got "
                f"{self.precision!r}"
            )
        if self.autotune and self.algorithm != "sparse":
            raise ValueError(
                "autotune requires algorithm='sparse' (only the sparse "
                "sweep kernels have tunable block shapes)"
            )
        if self.shard is not None:
            if not isinstance(self.shard, ShardSpec):
                raise TypeError(
                    f"shard must be a ShardSpec or None, got "
                    f"{type(self.shard).__name__}"
                )
            if self.algorithm != "sparse":
                raise ValueError(
                    f"shard requires algorithm='sparse' (only COO nonzeros "
                    f"have an nnz axis to shard), got {self.algorithm!r}"
                )
            if self.engine == "pallas":
                raise ValueError(
                    "shard requires the XLA engine: the Pallas kernels do "
                    "not run inside shard_map (use engine='xla' or 'auto')"
                )
            if self.use_kron_reuse:
                raise ValueError(
                    "shard is incompatible with use_kron_reuse: the dedup "
                    "plan is a per-tensor host artifact that cannot shard "
                    "along the nnz axis"
                )
            if self.precision != "fp32":
                raise ValueError(
                    "shard requires precision='fp32': the sharded program "
                    "runs at full working precision (mixed precision is a "
                    "kernel-engine axis)"
                )
        if self.snapshot is not None:
            if not isinstance(self.snapshot, SnapshotSpec):
                raise TypeError(
                    f"snapshot must be a SnapshotSpec or None, got "
                    f"{type(self.snapshot).__name__}"
                )
            if self.algorithm != "sparse":
                raise ValueError(
                    f"snapshot requires algorithm='sparse' (only the "
                    f"compiled sweep pipeline has a resumable carry), got "
                    f"{self.algorithm!r}"
                )
        object.__setattr__(self, "shape", shape)
        object.__setattr__(self, "ranks", ranks)
        object.__setattr__(self, "n_iter", int(self.n_iter))
        object.__setattr__(self, "n_rounds", int(self.n_rounds))
        object.__setattr__(self, "tol", float(self.tol))
        object.__setattr__(self, "dtype", _canonical_dtype(self.dtype))

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def supports_batched_dispatch(self) -> bool:
        """True when plans for this spec can vmap k tensors into ONE XLA
        dispatch (``TuckerPlan.batch``'s fast path, and the micro-batching
        contract ``repro.serve.TuckerService`` schedules around): sparse COO
        input, without the Kron-reuse dedup (whose per-tensor plan arrays
        have data-dependent sizes and cannot share one batched program).
        Sharded specs are excluded too: their one program already spans the
        mesh, so a batch runs them sequentially — still one dispatch per
        member. Snapshot specs are excluded as well: a snapshot job is one
        long-running fit bound to its own checkpoint directory, not a batch
        member. The engine must additionally *resolve*
        to 'xla' — that happens at plan level, where resolution lives."""
        return (
            self.algorithm == "sparse"
            and not self.use_kron_reuse
            and self.shard is None
            and self.snapshot is None
            and self.precision == "fp32"  # batched program is fp32-only
        )

    def resolved_dtype(self) -> Any:
        """The concrete working dtype, or ``None`` for "auto" (follow the
        jax x64 flag at execution time)."""
        if self.dtype == "auto":
            return None
        import jax.numpy as jnp

        return jnp.dtype(self.dtype)


def spec_for(
    x: Any,
    ranks: Sequence[int],
    **kwargs,
) -> TuckerSpec:
    """Build a :class:`TuckerSpec` from a tensor (``SparseCOO`` or dense
    array) — the shape and default algorithm are inferred from the input."""
    from repro.core.coo import SparseCOO

    if isinstance(x, SparseCOO):
        kwargs.setdefault("algorithm", "sparse")
        shape = x.shape
    else:
        kwargs.setdefault("algorithm", "dense")
        shape = np.asarray(x).shape if not hasattr(x, "shape") else x.shape
    return TuckerSpec(shape=tuple(shape), ranks=tuple(ranks), **kwargs)
