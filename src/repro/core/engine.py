"""Sweep engine layer: selects *how* each ALS sweep's hot loops execute.

The paper's accelerator splits Alg. 2 across a CPU (scheduling, QRP) and an
FPGA (TTM module 1, Kron-accumulation module 2). Our analogue splits each
sweep across two interchangeable execution engines:

  ``xla``     the pure-jnp path (``core.kron.sparse_ttm_chain`` + einsum TTM)
              — one fused XLA scatter-add, best on CPU and the correctness
              oracle everywhere;
  ``pallas``  the kernel path — nonzeros streamed through the fused
              kron-contrib→one-hot-scatter Pallas pipeline
              (``kernels.kron_kernel``) on a host-side ``SortedCOO`` schedule
              (``sparse.layout``), core update on the blocked TTM kernel
              (``kernels.ttm_kernel``). Mosaic on TPU; interpret mode
              elsewhere (slow but exact, which keeps CPU CI meaningful).
              Under ``shard`` each device streams its own nonzeros on a
              per-shard schedule (``SweepEngine.shard_schedule``);
  ``auto``    ``pallas`` when a TPU is attached, ``xla`` otherwise.

Engines are differentially tested against the dense ``ttm_chain`` oracle in
``tests/test_engine.py`` — any new engine must pass that harness before it
can be selected here.
"""
from __future__ import annotations

import dataclasses
import weakref
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax

from repro.core.coo import SparseCOO
from repro.obs import registry as _obs_registry, span as _obs_span
from repro.sparse.layout import (
    DeviceSchedule,
    KronReusePlan,
    ShardSchedule,
    SortedCOO,
    build_kron_reuse,
    build_mode_layout,
    build_shard_layouts,
    build_shard_schedule,
    stack_shard_layouts,
)

ENGINES = ("xla", "pallas", "auto")

# process-wide mirror of every engine's schedule_builds (labeled by what was
# built), so the registry sees rebuild storms without holding engine refs.
_SCHEDULE_BUILDS = {
    kind: _obs_registry.counter(
        "repro_schedule_builds_total",
        "host-side schedule constructions + device uploads",
        labels={"kind": kind},
    )
    for kind in ("layout", "kron", "device", "shard", "shard_layout")
}


def resolve_engine(engine: str = "auto") -> str:
    """Map a requested engine to the one that will actually run: ``auto``
    picks ``pallas`` on TPU and ``xla`` elsewhere; an explicit engine is
    honored as asked (``pallas`` off-TPU runs the kernels in interpret mode).
    """
    if engine not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
    if engine == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return engine


@dataclasses.dataclass
class SweepEngine:
    """Sweep executor: engine choice + cached per-mode layouts.

    Build via :func:`make_engine` and reuse across sweeps — the layouts are
    the expensive host-side part, exactly like the paper builds its dataflow
    schedule once per dataset. Handing it a different tensor is safe: the
    schedule cache rebinds (rebuilds) on an indices/shape change.
    """

    name: str  # resolved: "xla" or "pallas"
    bn: int = 128
    bi: int = 128
    # TTM kernel block shape; None = the kernel's own defaults (pallas only).
    bl: Optional[int] = None
    bk: Optional[int] = None
    # "fp32" or "bf16_fp32acc": bf16 operand loads/multiplies with f32
    # accumulators in the kernels (and bf16 Kron rows on the XLA engine).
    precision: str = "fp32"
    # run the core update through the fused Kron→scatter→TTM megakernel
    # (pallas only; the autotuner's "fused" layout). Off by default so the
    # split path stays the bitwise baseline.
    fuse_core: bool = False
    use_kron_reuse: bool = False
    interpret: Optional[bool] = None  # None = auto (interpret off-TPU)
    # cumulative count of host-side schedule constructions + device uploads;
    # the plan API reports per-call deltas so a serving loop can assert its
    # steady state is rebuild-free (tests/test_sweep_pipeline.py).
    schedule_builds: int = 0
    layouts: Dict[int, SortedCOO] = dataclasses.field(default_factory=dict)
    kron_plans: Dict[int, KronReusePlan] = dataclasses.field(default_factory=dict)
    dev_schedules: Dict[int, Optional[DeviceSchedule]] = dataclasses.field(
        default_factory=dict
    )
    # (mesh, nnz_axes) -> ShardSchedule: the bound tensor's nonzeros padded
    # and device_put once per mesh (the sharded pipeline's analogue of
    # dev_schedules). Invalidated by _bind like every other schedule cache.
    shard_schedules: Dict[tuple, ShardSchedule] = dataclasses.field(
        default_factory=dict
    )
    # weakref to the indices array the cached schedules were built from: a
    # live referent makes the identity check below sound (no id reuse) without
    # pinning a rebound-away tensor (and its device buffer) in memory. A dead
    # ref simply forces a rebuild.
    _bound_indices: Optional["weakref.ref"] = None
    _bound_shape: Optional[tuple] = None
    # the shard schedules additionally embed the VALUES array (the mode
    # schedules are index-derived only), so they get their own values-identity
    # guard: same indices + new values must rebuild, never silently contract
    # the old tensor's values.
    _shard_values: Optional["weakref.ref"] = None

    # -- schedule caches --------------------------------------------------
    def _bind(self, coo: SparseCOO) -> None:
        """Invalidate cached schedules when handed a different tensor —
        replaying one tensor's order/valid/rel_row against another's indices
        would be silently wrong, not an error."""
        bound = self._bound_indices() if self._bound_indices is not None else None
        if bound is not coo.indices or self._bound_shape != coo.shape:
            self.layouts.clear()
            self.kron_plans.clear()
            self.dev_schedules.clear()
            self.shard_schedules.clear()

            # when the bound tensor dies, drop its derived schedules too —
            # they are O(nnz) host+device memory of the same magnitude as the
            # tensor. The callback closes over the dicts, not the engine, so
            # it cannot extend the engine's lifetime.
            def _release(_ref, caches=(self.layouts, self.kron_plans,
                                       self.dev_schedules,
                                       self.shard_schedules)):
                for c in caches:
                    c.clear()

            self._bound_indices = weakref.ref(coo.indices, _release)
            self._bound_shape = tuple(coo.shape)

    def _note_build(self, kind: str) -> None:
        self.schedule_builds += 1
        _SCHEDULE_BUILDS[kind].inc()

    def mode_layout(self, coo: SparseCOO, mode: int) -> SortedCOO:
        self._bind(coo)
        if mode not in self.layouts:
            with _obs_span("engine.schedule.build", kind="layout", mode=mode,
                           nnz=int(coo.nnz)):
                self.layouts[mode] = build_mode_layout(
                    coo, mode, bn=self.bn, bi=self.bi
                )
            self._note_build("layout")
        return self.layouts[mode]

    def kron_plan(self, coo: SparseCOO, mode: int) -> KronReusePlan:
        self._bind(coo)
        if mode not in self.kron_plans:
            with _obs_span("engine.schedule.build", kind="kron", mode=mode,
                           nnz=int(coo.nnz)):
                self.kron_plans[mode] = build_kron_reuse(coo, mode)
            self._note_build("kron")
        return self.kron_plans[mode]

    def device_schedule(self, coo: SparseCOO, mode: int) -> Optional[DeviceSchedule]:
        """The mode's schedule with arrays committed to device exactly once —
        what the compiled scan-over-sweeps pipeline (``core.hooi``) closes
        over. ``None`` for the plain-XLA path, which needs no schedule at all
        (and must not force a host round-trip through ``coo.indices``)."""
        self._bind(coo)
        if mode not in self.dev_schedules:
            if self.name == "pallas":
                with _obs_span("engine.schedule.upload", kind="device",
                               mode=mode, engine=self.name):
                    self.dev_schedules[mode] = DeviceSchedule.from_layout(
                        self.mode_layout(coo, mode)
                    )
                self._note_build("device")
            elif self.use_kron_reuse:
                with _obs_span("engine.schedule.upload", kind="device",
                               mode=mode, engine=self.name):
                    self.dev_schedules[mode] = DeviceSchedule.from_kron_plan(
                        self.kron_plan(coo, mode), mode, tuple(coo.shape)
                    )
                self._note_build("device")
            else:
                # the plain-XLA path needs no schedule: not a build.
                self.dev_schedules[mode] = None
        return self.dev_schedules[mode]

    def shard_schedule(
        self, coo: SparseCOO, mesh, nnz_axes, pad_nnz_to: Optional[int] = None
    ) -> ShardSchedule:
        """The tensor's nonzeros padded to an even shard multiple (at least
        ``pad_nnz_to`` when given — shape-stable programs across mixed-nnz
        serving flushes) and ``device_put`` with a ``NamedSharding`` over
        ``nnz_axes`` — exactly once per (tensor, mesh, pad target): what the
        compiled shard_map pipeline (``core.hooi.build_sharded_program``)
        consumes every sweep. On the Pallas engine it also carries every
        shard's streaming schedule of every mode (``ShardSchedule.scheds``)."""
        self._bind(coo)
        bound_vals = self._shard_values() if self._shard_values is not None else None
        if bound_vals is not coo.values:
            self.shard_schedules.clear()
            self._shard_values = weakref.ref(coo.values)
        key = (mesh, tuple(nnz_axes), pad_nnz_to)
        if key not in self.shard_schedules:
            with _obs_span("engine.schedule.upload", kind="shard",
                           nnz=int(coo.nnz),
                           pad_nnz_to=pad_nnz_to and int(pad_nnz_to)):
                sched = build_shard_schedule(
                    coo, mesh, tuple(nnz_axes), target_nnz=pad_nnz_to
                )
            self._note_build("shard")
            if self.name == "pallas":
                sched = dataclasses.replace(
                    sched, scheds=self._shard_mode_schedules(coo, sched)
                )
            self.shard_schedules[key] = sched
        return self.shard_schedules[key]

    def _shard_mode_schedules(self, coo: SparseCOO, sched: ShardSchedule):
        """Every shard's streaming schedule of every mode, from the shard's
        contiguous slice of the padded nonzeros, padded to the mode's largest
        block count and stacked on the mesh."""
        layouts = build_shard_layouts(
            coo.indices, tuple(coo.shape), sched.nnz_padded, sched.n_shards,
            self.bn, self.bi,
            span=partial(_obs_span, "engine.schedule.build", kind="shard_layout"),
        )
        for _ in layouts:
            self._note_build("shard_layout")
        return tuple(
            stack_shard_layouts(
                [layouts[(s, m)] for s in range(sched.n_shards)], m,
                tuple(coo.shape), self.bn, self.bi, sched.mesh, sched.nnz_axes,
            )
            for m in range(coo.ndim)
        )

    def apply_blocks(self, cfg) -> None:
        """Adopt an autotuned block configuration
        (:class:`repro.kernels.autotune.BlockConfig`). Changing the schedule
        geometry (bn/bi) invalidates the cached per-mode layouts — replaying
        a 128-row schedule against 256-row kernel blocks would be silently
        wrong — so those rebuild on the next sweep; bl/bk/layout are pure
        kernel statics and swap freely."""
        if (int(cfg.bn) != self.bn) or (int(cfg.bi) != self.bi):
            self.layouts.clear()
            self.kron_plans.clear()
            self.dev_schedules.clear()
            self.shard_schedules.clear()
        self.bn, self.bi = int(cfg.bn), int(cfg.bi)
        self.bl, self.bk = int(cfg.bl), int(cfg.bk)
        self.fuse_core = cfg.layout == "fused"

    def resolved_interpret(self) -> bool:
        """The kernel interpret flag this engine will actually run with
        (resolved to a bool so it can be a static jit argument)."""
        from repro.kernels.ops import default_interpret

        return default_interpret() if self.interpret is None else self.interpret

    # -- Alg. 2 line 5: Y_(n) over nonzeros only --------------------------
    def mode_unfolding(
        self, coo: SparseCOO, factors: Sequence[jax.Array], mode: int
    ) -> jax.Array:
        """Mode-``mode`` unfolding of the skipped-mode TTM chain:
        Y_(n) of shape (I_n, prod_{t != n} R_t)."""
        if self.name == "pallas":
            return self._mode_unfolding_pallas(coo, factors, mode)
        from repro.core.kron import sparse_ttm_chain, sparse_ttm_chain_reuse

        if self.use_kron_reuse:
            return sparse_ttm_chain_reuse(coo, factors, mode, self.kron_plan(coo, mode))
        return sparse_ttm_chain(coo, factors, mode, precision=self.precision)

    def _mode_unfolding_pallas(
        self, coo: SparseCOO, factors: Sequence[jax.Array], mode: int
    ) -> jax.Array:
        from repro.kernels import ops

        # device-resident schedule: uploaded once per (tensor, mode), so
        # per-sweep calls hand the kernels device buffers, not numpy.
        return ops.sparse_ttm_chain_device(
            coo.indices,
            coo.values,
            factors,
            mode,
            self.device_schedule(coo, mode),
            shape=tuple(coo.shape),
            interpret=self.resolved_interpret(),
            precision=self.precision,
        )


def make_engine(
    engine: str = "auto",
    *,
    bn: int = 128,
    bi: int = 128,
    bl: Optional[int] = None,
    bk: Optional[int] = None,
    precision: str = "fp32",
    fuse_core: bool = False,
    use_kron_reuse: bool = False,
    interpret: Optional[bool] = None,
) -> SweepEngine:
    """Resolve ``engine`` and build a reusable :class:`SweepEngine`."""
    from repro.kernels.kron_kernel import PRECISIONS

    if precision not in PRECISIONS:
        raise ValueError(
            f"precision must be one of {PRECISIONS}, got {precision!r}"
        )
    return SweepEngine(
        name=resolve_engine(engine),
        bn=bn,
        bi=bi,
        bl=bl,
        bk=bk,
        precision=precision,
        fuse_core=fuse_core,
        use_kron_reuse=use_kron_reuse,
        interpret=interpret,
    )


def available_engines() -> List[str]:
    """The concrete engines (test harness helper): both run on every host."""
    return ["xla", "pallas"]
