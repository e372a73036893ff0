"""Host-side sparse layouts for the sweep engine.

The paper's FPGA streams COO nonzeros through the Kron-accumulation pipeline
in whatever order the CPU feeds them, keeping a row batch of Y_(n) resident
in BRAM (Sec. III-B/C). The TPU analogue needs that schedule made explicit:
nonzeros must arrive grouped by output row-block so the scatter kernel can
keep each Y_(n) block resident in VMEM, and every block must be padded to
the kernel's block size. This module builds that schedule — once per
(tensor, mode), on the host — as static metadata the jitted kernels index
with scalar prefetch.

``build_mode_layout`` subsumes the two older host-side precomputations:

  * ``core.kron.precompute_kron_reuse`` — the paper's Sec. III-C trick of
    computing each distinct non-mode Kronecker row once (kept here as the
    ``kron_unique``/``kron_inverse`` fields, in *original* nonzero order so
    the XLA reuse path is unchanged);
  * ``kernels.kron_kernel.build_scatter_plan`` — the row-block grouping the
    one-hot-matmul scatter kernel needs (kept as the embedded
    ``ScatterPlan``), but built from a mode-sort in O(nnz log nnz) instead
    of a per-block scan in O(nnz * n_blocks).
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import TYPE_CHECKING, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:  # runtime access is duck-typed (indices/shape/ndim) —
    # importing core.coo here would close an import cycle through
    # core/__init__ -> engine -> this module.
    from repro.core.coo import SparseCOO


class KronReusePlan(NamedTuple):
    """Host-side dedup of non-mode index tuples (paper's Kron reuse trick,
    Sec. III-C). ``modes`` is the descending non-mode order matching
    ``core.kron.kron_rows`` column ordering."""

    unique_indices: np.ndarray  # (n_unique, N-1) indices into non-mode factors
    inverse: np.ndarray  # (nnz,) map nonzero -> unique kron row
    modes: Tuple[int, ...]


def build_kron_reuse(coo: SparseCOO, skip_mode: int) -> KronReusePlan:
    """Deduplicate the (N-1)-tuples of non-mode indices so each distinct
    Kronecker row is computed once. Host-side (np.unique is data-dependent
    and not jittable); the returned plan is static metadata in original
    nonzero order."""
    idx = np.asarray(coo.indices)
    modes = tuple(t for t in range(coo.ndim - 1, -1, -1) if t != skip_mode)
    sub = idx[:, list(modes)]
    uniq, inverse = np.unique(sub, axis=0, return_inverse=True)
    return KronReusePlan(
        uniq.astype(np.int32), inverse.reshape(-1).astype(np.int32), modes
    )


class SortedCOO(NamedTuple):
    """Nonzeros of one tensor, permuted into mode-major row-block order and
    padded to block multiples — the engine's per-mode streaming schedule.

    All arrays are host-side numpy (static metadata); ``nnz_padded`` rows
    where padding entries carry ``valid == 0`` and a safe gather index of 0.
    """

    mode: int
    shape: Tuple[int, ...]
    order: np.ndarray  # (nnz_padded,) gather index into original nonzeros
    valid: np.ndarray  # (nnz_padded,) f32 1.0 real / 0.0 padding
    rel_row: np.ndarray  # (nnz_padded,) row index within the target row block
    blkmap: np.ndarray  # (n_blocks,) target row-block of each nnz block
    first: np.ndarray  # (n_blocks,) 1 iff first block of its target
    last: np.ndarray  # (n_blocks,) 1 iff last block of its target
    segments: np.ndarray  # (I_mode + 1,) row segment boundaries (sorted order)
    n_row_blocks: int
    bn: int  # nonzeros per block
    bi: int  # output rows per block
    kron: Optional[KronReusePlan]  # None unless reuse=True
    # keep-mask over output rows; None when every row block receives at least
    # one nnz block (the common case) so the scatter kernels can skip masking.
    row_mask: Optional[np.ndarray] = None

    @property
    def nnz_padded(self) -> int:
        return int(self.order.shape[0])

    @property
    def n_blocks(self) -> int:
        return int(self.blkmap.shape[0])

    def row_segment(self, i: int) -> Tuple[int, int]:
        """[start, stop) of the nonzeros with mode-coordinate ``i`` in the
        mode-sorted (pre-padding) order — the paper's (j,k)-sharing segments."""
        return int(self.segments[i]), int(self.segments[i + 1])


def build_schedule(
    rows: np.ndarray, n_rows: int, bn: int, bi: int
) -> Tuple[
    np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray,
    int, np.ndarray,
]:
    """Shared row-block grouping (the one implementation behind both
    ``build_mode_layout`` and ``kernels.kron_kernel.build_scatter_plan``):
    stable-sort ``rows``, group into BI-row output blocks, pad each group to
    a BN multiple so every nnz block targets exactly one row block.

    Returns ``(order, valid, rel_row, blkmap, first, last, n_row_blocks,
    perm)`` where ``order`` holds safe gather indices (padding slots point at
    0 with ``valid == 0``), ``first``/``last`` flag each row-block group's
    boundary blocks (the scatter kernels zero the resident block on ``first``;
    the fused-core megakernel contracts it on ``last``), and ``perm`` is the
    plain stable sort by row (pre-padding, for segment metadata). Fully
    vectorized: O(nnz log nnz) numpy with no per-row-block interpreter loop,
    so 20K-row modes schedule in milliseconds.
    """
    if bn <= 0 or bi <= 0:
        raise ValueError(f"block sizes must be positive, got bn={bn} bi={bi}")
    rows = np.asarray(rows).astype(np.int64)
    nnz = rows.shape[0]
    n_row_blocks = max(1, -(-n_rows // bi))
    perm = np.argsort(rows, kind="stable")
    sorted_rows = rows[perm]
    # row-block group boundaries within the sorted order.
    grp_bounds = np.searchsorted(sorted_rows, np.arange(0, n_row_blocks + 1) * bi)
    cnt = np.diff(grp_bounds)  # (n_row_blocks,) nonzeros per row-block group
    blocks_per_grp = -(-cnt // bn)  # ceil; 0 for empty groups
    padded_len = blocks_per_grp * bn
    total = int(padded_len.sum())
    if total == 0:  # empty tensor: one all-padding block
        order = np.full((bn,), -1, dtype=np.int64)
        blkmap = np.zeros((1,), dtype=np.int32)
        first = np.ones((1,), dtype=np.int32)
    else:
        out_start = np.concatenate([[0], np.cumsum(padded_len)[:-1]])
        order = np.full((total,), -1, dtype=np.int64)
        # destination slot of each sorted nonzero: its group's output offset
        # plus its position within the group.
        grp_of = np.repeat(np.arange(n_row_blocks), cnt)
        dest = out_start[grp_of] + (np.arange(nnz) - grp_bounds[:-1][grp_of])
        order[dest] = perm
        blkmap = np.repeat(
            np.arange(n_row_blocks, dtype=np.int32), blocks_per_grp
        )
        first = np.zeros((blkmap.shape[0],), dtype=np.int32)
        blk_start = np.concatenate([[0], np.cumsum(blocks_per_grp)[:-1]])
        first[blk_start[blocks_per_grp > 0]] = 1
    # a group's last block sits right before the next group's first (or at
    # the very end of the grid) — derivable from ``first``, kept explicit so
    # the kernels never recompute group boundaries at run time.
    last = np.empty_like(first)
    last[:-1] = first[1:]
    last[-1] = 1
    valid = (order >= 0).astype(np.float32)
    safe = np.where(order >= 0, order, 0)
    rel = rows[safe] % bi if nnz else np.zeros_like(safe)
    rel = np.where(order >= 0, rel, 0)
    return (
        safe.astype(np.int32),
        valid,
        rel.astype(np.int32),
        blkmap,
        first,
        last,
        n_row_blocks,
        perm,
    )


def visited_row_mask(
    blkmap: np.ndarray, n_row_blocks: int, bi: int, n_rows: int
) -> Optional[np.ndarray]:
    """Keep-mask over output rows for the scatter kernels: rows whose block is
    never visited by the grid stay uninitialized and must be zeroed. Computed
    once at plan-build time; ``None`` means every row block is visited."""
    visited = np.zeros((n_row_blocks,), dtype=bool)
    visited[np.asarray(blkmap)] = True
    if visited.all():
        return None
    return np.repeat(visited, bi)[:n_rows]


def build_mode_layout(
    coo: SparseCOO,
    mode: int,
    bn: int = 128,
    bi: int = 128,
    reuse: bool = False,
) -> SortedCOO:
    """Build the mode-``mode`` streaming schedule for one tensor (see
    :func:`build_schedule`), plus the per-row segment boundaries and optional
    Kron-reuse plan the engine wants alongside it."""
    idx = np.asarray(coo.indices)
    rows = idx[:, mode].astype(np.int64)
    n_rows = int(coo.shape[mode])
    order, valid, rel, blkmap, first, last, n_row_blocks, perm = build_schedule(
        rows, n_rows, bn, bi
    )
    # per-row segment boundaries (paper Sec. III-C: nonzeros sharing the mode
    # coordinate are consecutive, so their Kron rows share a Y_(n) row).
    segments = np.searchsorted(rows[perm], np.arange(n_rows + 1))
    return SortedCOO(
        mode=mode,
        shape=tuple(coo.shape),
        order=order,
        valid=valid,
        rel_row=rel,
        blkmap=blkmap,
        first=first,
        last=last,
        segments=segments.astype(np.int64),
        n_row_blocks=n_row_blocks,
        bn=bn,
        bi=bi,
        kron=build_kron_reuse(coo, mode) if reuse else None,
        row_mask=visited_row_mask(blkmap, n_row_blocks, bi, n_rows),
    )


# ---------------------------------------------------------------------------
# Device-resident schedules (the jitted sweep pipeline's view of a layout).
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DeviceSchedule:
    """One mode's schedule with every array already committed to device.

    The host-side :class:`SortedCOO` / :class:`KronReusePlan` are numpy — fine
    for plan *construction*, but handing them to a jitted callee re-uploads
    each array on every call. The paper builds its dataflow schedule once and
    streams it; this is the analogue: upload once, then every sweep of the
    compiled scan-over-sweeps pipeline (``core.hooi``) indexes device buffers.

    A pytree: array fields are leaves (any may be ``None`` — plain-XLA sweeps
    need no scatter schedule, non-reuse sweeps no Kron dedup), the block
    geometry is static aux data, so a shape/blocking change correctly
    retriggers compilation while same-schedule calls hit the jit cache.
    """

    # -- leaves (device arrays or None) -----------------------------------
    order: Optional[jax.Array]
    valid: Optional[jax.Array]
    rel_row: Optional[jax.Array]
    blkmap: Optional[jax.Array]
    first: Optional[jax.Array]
    last: Optional[jax.Array]
    row_mask: Optional[jax.Array]
    kron_unique: Optional[jax.Array]
    kron_inverse: Optional[jax.Array]
    # -- static aux --------------------------------------------------------
    mode: int
    shape: Tuple[int, ...]
    n_row_blocks: int
    bn: int
    bi: int
    kron_modes: Optional[Tuple[int, ...]]

    def tree_flatten(self):
        children = (
            self.order, self.valid, self.rel_row, self.blkmap, self.first,
            self.last, self.row_mask, self.kron_unique, self.kron_inverse,
        )
        aux = (self.mode, self.shape, self.n_row_blocks, self.bn, self.bi,
               self.kron_modes)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @classmethod
    def from_layout(cls, layout: SortedCOO) -> "DeviceSchedule":
        """Upload a host schedule's arrays to device exactly once."""
        kron = layout.kron
        return cls(
            order=jnp.asarray(layout.order),
            valid=jnp.asarray(layout.valid),
            rel_row=jnp.asarray(layout.rel_row),
            blkmap=jnp.asarray(layout.blkmap),
            first=jnp.asarray(layout.first),
            last=jnp.asarray(layout.last),
            row_mask=(
                None if layout.row_mask is None else jnp.asarray(layout.row_mask)
            ),
            kron_unique=None if kron is None else jnp.asarray(kron.unique_indices),
            kron_inverse=None if kron is None else jnp.asarray(kron.inverse),
            mode=layout.mode,
            shape=tuple(layout.shape),
            n_row_blocks=layout.n_row_blocks,
            bn=layout.bn,
            bi=layout.bi,
            kron_modes=None if kron is None else tuple(kron.modes),
        )

    @classmethod
    def from_kron_plan(
        cls, plan: KronReusePlan, mode: int, shape: Tuple[int, ...]
    ) -> "DeviceSchedule":
        """Device-resident Kron-dedup plan only (the XLA reuse path needs no
        scatter schedule)."""
        return cls(
            order=None, valid=None, rel_row=None, blkmap=None, first=None,
            last=None, row_mask=None,
            kron_unique=jnp.asarray(plan.unique_indices),
            kron_inverse=jnp.asarray(plan.inverse),
            mode=mode, shape=tuple(shape), n_row_blocks=0, bn=0, bi=0,
            kron_modes=tuple(plan.modes),
        )


def layout_padding_fraction(layout: SortedCOO) -> float:
    """Fraction of streamed nonzero slots that are padding — the price of
    block alignment (useful for picking bn on very sparse modes)."""
    return 1.0 - float(layout.valid.sum()) / max(1, layout.nnz_padded)


# ---------------------------------------------------------------------------
# Batch-dimension padding: nnz bucketing for shape-stable batched dispatch.
#
# The compiled batched sweep program (``core.hooi._batched_scan_sweeps``) is
# shape-keyed on the padded nnz, so a serving plane that padded every flush to
# its own batch max would compile one program per distinct max — unbounded.
# Rounding the pad target up to a geometric bucket boundary bounds the number
# of distinct programs to O(log nnz_max) while wasting at most (growth - 1)x
# padded slots (explicit zeros, which contribute nothing to any contraction).
# ---------------------------------------------------------------------------


def shard_pad_nnz(nnz: int, n_shards: int) -> int:
    """Padded nnz for even sharding: the minimal multiple of ``n_shards``
    that is >= ``nnz`` and >= ``n_shards`` (every shard owns at least one
    slot, even for an empty tensor). The ONE place the shard padding math
    lives — ``core.distributed.shard_nonzeros``, :func:`build_shard_schedule`
    and the batch padder all agree on it, and it composes with
    :func:`bucket_nnz` (padding a bucket boundary is a fixpoint when the
    boundary already divides evenly)."""
    if int(n_shards) < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if int(nnz) < 0:
        raise ValueError(f"nnz must be >= 0, got {nnz}")
    n_shards = int(n_shards)
    return max(((int(nnz) + n_shards - 1) // n_shards) * n_shards, n_shards)


@dataclasses.dataclass(frozen=True)
class ShardSchedule:
    """One tensor's nonzeros committed to a device mesh exactly once.

    The sharded analogue of :class:`DeviceSchedule`: COO rows are padded to a
    :func:`shard_pad_nnz` multiple (explicit zeros — they contribute nothing
    to any contraction) and ``device_put`` ONCE with a ``NamedSharding`` over
    the nnz axes, so every sweep of the compiled shard_map pipeline indexes
    the same device buffers instead of re-sharding per call. The static
    metadata (shard counts, imbalance) feeds the per-call counters on
    :class:`~repro.tucker.result.TuckerResult`.
    """

    indices: jax.Array  # (nnz_padded, N), sharded P(nnz_axes, None)
    values: jax.Array  # (nnz_padded,), sharded P(nnz_axes)
    mesh: object  # jax.sharding.Mesh
    nnz_axes: Tuple[str, ...]
    n_shards: int
    nnz: int  # real stored nonzeros (pre-padding)
    nnz_padded: int
    # per mode, every shard's streaming schedule stacked on a leading shard
    # axis (:func:`stack_shard_layouts`); None on the XLA engine.
    scheds: Optional[Tuple[DeviceSchedule, ...]] = None

    @property
    def shard_counts(self) -> np.ndarray:
        """Real (non-padding) nonzeros owned by each shard. Padding is
        appended, so shards are contiguous slices of the padded stream."""
        per = self.nnz_padded // self.n_shards
        starts = np.arange(self.n_shards) * per
        return np.clip(self.nnz - starts, 0, per)

    @property
    def imbalance(self) -> float:
        """Load imbalance across shards: ``1 - min/max`` of per-shard real
        nnz (0.0 = perfectly even; approaches 1.0 when some shard is all
        padding). Reported per call as ``TuckerResult.shard_imbalance``."""
        counts = self.shard_counts
        mx = int(counts.max())
        if mx == 0:
            return 0.0
        return 1.0 - int(counts.min()) / mx


def build_shard_schedule(
    coo, mesh, nnz_axes: Tuple[str, ...], target_nnz: Optional[int] = None
) -> ShardSchedule:
    """Pad ``coo``'s nonzeros to a :func:`shard_pad_nnz` multiple of the nnz
    mesh axes and ``device_put`` the two arrays once, sharded on their leading
    (nnz) dimension. Validates the axis names up front — a missing axis must
    be a clear error here, not an opaque ``KeyError`` deep in ``device_put``.

    ``target_nnz`` raises the pad floor (e.g. to a serving bucket boundary,
    so mixed-nnz requests share one compiled program); the schedule still
    records the REAL stored nnz, keeping ``shard_counts``/``imbalance``
    honest about where the actual nonzeros sit.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    nnz_axes = tuple(nnz_axes)
    if not nnz_axes:
        raise ValueError("nnz_axes must name at least one mesh axis")
    missing = [a for a in nnz_axes if a not in mesh.axis_names]
    if missing:
        raise ValueError(
            f"nnz axes {missing} are not mesh axes: the mesh has "
            f"{tuple(mesh.axis_names)} — every nnz_axes name must be one "
            f"of them"
        )
    n_shards = int(np.prod([mesh.shape[a] for a in nnz_axes]))
    nnz = int(coo.indices.shape[0])
    floor = max(nnz, int(target_nnz)) if target_nnz is not None else nnz
    padded = coo.pad_to(shard_pad_nnz(floor, n_shards))
    idx = jax.device_put(padded.indices, NamedSharding(mesh, P(nnz_axes, None)))
    val = jax.device_put(padded.values, NamedSharding(mesh, P(nnz_axes)))
    return ShardSchedule(
        indices=idx,
        values=val,
        mesh=mesh,
        nnz_axes=nnz_axes,
        n_shards=n_shards,
        nnz=nnz,
        nnz_padded=int(idx.shape[0]),
    )


# ---------------------------------------------------------------------------
# Per-shard streaming schedules: each chip of the sharded program streams its
# own contiguous slice of the padded nonzeros through the Kron kernels, so
# each shard gets a schedule of its own, with indices local to its slice. The
# shards' schedules are stacked into one array per field, so every shard's
# must have the same block count, and every shard visits every row block, so
# no shard's kernel leaves a row of its partial Y_(n) unwritten.
# ---------------------------------------------------------------------------


def shard_rows(indices: np.ndarray, nnz_padded: int, n_shards: int,
               shard: int, mode: int) -> np.ndarray:
    """Mode-``mode`` coordinates of shard ``shard``'s slice of the nonzeros
    padded to ``nnz_padded`` (padding slots read coordinate 0, as
    ``SparseCOO.pad_to`` pads)."""
    per = int(nnz_padded) // int(n_shards)
    part = np.asarray(indices)[shard * per:(shard + 1) * per, mode]
    rows = np.zeros((per,), dtype=np.int64)
    rows[:part.shape[0]] = part
    return rows


def shard_block_count(rows: np.ndarray, n_rows: int, bn: int, bi: int) -> int:
    """nnz blocks of one shard's schedule: each row block's nonzeros in
    ``bn``-slot blocks, and one all-padding block for a row block the shard
    never visits."""
    n_row_blocks = max(1, -(-int(n_rows) // bi))
    cnt = np.bincount(np.asarray(rows) // bi, minlength=n_row_blocks)
    return int(np.maximum(-(-cnt // bn), 1).sum())


class ShardLayout(NamedTuple):
    """One shard's mode schedule, padded to the shards' common block count:
    the :class:`SortedCOO` fields the kernels read, indices local to the
    shard's slice."""

    order: np.ndarray  # (n_blocks * bn,)
    valid: np.ndarray
    rel_row: np.ndarray
    blkmap: np.ndarray  # (n_blocks,)
    first: np.ndarray
    last: np.ndarray
    n_row_blocks: int
    pad_blocks: int  # all-padding blocks appended to reach n_blocks


def build_shard_layout(rows: np.ndarray, n_rows: int, bn: int, bi: int,
                       n_blocks: int) -> ShardLayout:
    """:func:`build_schedule` over one shard's rows, then padded to
    ``n_blocks`` (at least :func:`shard_block_count`).

    A row block the shard never visits gets one phantom coordinate, so the
    schedule gives it one block whose slots are all padding: the kernel
    zeroes that block of the shard's partial Y_(n) and no row mask is
    needed. The blocks appended to reach ``n_blocks`` are all padding and
    continue the last row-block group: the same target, ``first`` 0, and
    ``last`` moved to the new final block."""
    rows = np.asarray(rows).astype(np.int64)
    nnz = rows.shape[0]
    n_row_blocks = max(1, -(-int(n_rows) // bi))
    visited = np.zeros((n_row_blocks,), dtype=bool)
    visited[rows // bi] = True
    phantom = np.flatnonzero(~visited) * bi
    order, valid, rel, blkmap, first, last, _, _ = build_schedule(
        np.concatenate([rows, phantom]), n_rows, bn, bi
    )
    fake = order >= nnz
    order[fake], valid[fake], rel[fake] = 0, 0.0, 0
    pad_blocks = int(n_blocks) - blkmap.shape[0]
    order, valid, rel = (np.pad(a, (0, pad_blocks * bn)) for a in (order, valid, rel))
    blkmap = np.pad(blkmap, (0, pad_blocks), mode="edge")
    first, last = np.pad(first, (0, pad_blocks)), np.pad(last, (0, pad_blocks))
    last[-pad_blocks - 1], last[-1] = 0, 1
    return ShardLayout(order, valid, rel, blkmap, first, last, n_row_blocks,
                       pad_blocks)


def build_shard_layouts(indices, shape: Tuple[int, ...], nnz_padded: int,
                        n_shards: int, bn: int, bi: int, span=None):
    """Every shard's layout of every mode (:func:`build_shard_layout`), each
    mode's padded to the largest block count among its shards: a dict
    ``(shard, mode) -> ShardLayout``. ``indices`` are the tensor's
    coordinates before the shard padding.

    The shards x modes builds run on a thread pool: numpy's sort, which
    dominates them, releases the GIL. ``span``, when given, is entered
    around each build as ``span(shard=, mode=, n_blocks=, pad_blocks=)``,
    ``n_blocks`` the shard's own block count and ``pad_blocks`` the blocks
    appended to it."""
    idx = np.asarray(indices)
    ndim = len(shape)
    tasks = [(s, m) for m in range(ndim) for s in range(n_shards)]
    span = span or (lambda **_: contextlib.nullcontext())

    def rows(task):
        return shard_rows(idx, nnz_padded, n_shards, *task)

    def count(task):
        return shard_block_count(rows(task), shape[task[1]], bn, bi)

    def build(task):
        s, m = task
        own = counts[task]
        with span(shard=s, mode=m, n_blocks=own, pad_blocks=most[m] - own):
            return build_shard_layout(rows(task), shape[m], bn, bi, most[m])

    with ThreadPoolExecutor(min(len(tasks), os.cpu_count() or 1)) as pool:
        counts = dict(zip(tasks, pool.map(count, tasks)))
        most = {m: max(counts[(s, m)] for s in range(n_shards))
                for m in range(ndim)}
        return dict(zip(tasks, pool.map(build, tasks)))


def stack_shard_layouts(layouts, mode: int, shape: Tuple[int, ...], bn: int,
                        bi: int, mesh, nnz_axes: Tuple[str, ...]) -> DeviceSchedule:
    """Stack the shards' layouts of one mode on a leading shard axis and
    ``device_put`` each field once, sharded over ``nnz_axes``: each device
    holds its own shard's schedule. The block geometry is static and the
    same for every shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(tuple(nnz_axes)))

    def put(field):
        return jax.device_put(np.stack([getattr(s, field) for s in layouts]),
                              sharding)

    return DeviceSchedule(
        order=put("order"), valid=put("valid"), rel_row=put("rel_row"),
        blkmap=put("blkmap"), first=put("first"), last=put("last"),
        row_mask=None, kron_unique=None, kron_inverse=None,
        mode=mode, shape=tuple(shape), n_row_blocks=layouts[0].n_row_blocks,
        bn=bn, bi=bi, kron_modes=None,
    )


def bucket_nnz(nnz: int, base: int = 512, growth: float = 2.0) -> int:
    """Smallest bucket boundary >= ``nnz`` on the geometric grid
    ``base, ceil(base*growth), ceil(base*growth^2), ...``.

    ``nnz = 0`` maps to ``base`` (a bucket is a pad *target*, never smaller
    than one block of real capacity).
    """
    if int(base) < 1:
        raise ValueError(f"bucket base must be >= 1, got {base}")
    if not growth > 1.0:
        raise ValueError(f"bucket growth must be > 1, got {growth}")
    if int(nnz) < 0:
        raise ValueError(f"nnz must be >= 0, got {nnz}")
    b = int(base)
    while b < int(nnz):
        b = int(np.ceil(b * float(growth)))
    return b


def pad_coo_batch(coos, target_nnz: Optional[int] = None):
    """Stack k same-shape COO tensors into batched ``(k, nnz_pad, N)`` index
    and ``(k, nnz_pad)`` value arrays, padding each tensor with explicit
    zeros (the padding convention of ``SparseCOO.pad_to``: index 0, value 0).

    This is the padding step of ``TuckerPlan.batch``, extracted so the
    serving plane can pad flushes to a :func:`bucket_nnz` boundary and hit
    one compiled program per (batch size, bucket) instead of one per batch.

    ``target_nnz=None`` pads to the batch max (the plan API's default);
    anything smaller than the batch max is an error — padding never drops
    nonzeros.

    Built host-side in numpy and uploaded as two arrays: a device-op
    assembly (k ``pad_to`` concats + stacks) costs several eager dispatches
    per flush, which on CPU rivals the batched sweep program itself.
    """
    if not coos:
        raise ValueError("pad_coo_batch needs at least one tensor")
    shapes = {tuple(c.shape) for c in coos}
    if len(shapes) != 1:
        raise ValueError(f"pad_coo_batch needs same-shape tensors, got {shapes}")
    nnz_max = max(int(c.indices.shape[0]) for c in coos)
    target = nnz_max if target_nnz is None else int(target_nnz)
    if target < nnz_max:
        raise ValueError(
            f"target_nnz={target} would drop nonzeros: batch max nnz is {nnz_max}"
        )
    k, ndim = len(coos), len(coos[0].shape)
    vdtypes = {np.dtype(c.values.dtype) for c in coos}
    if len(vdtypes) != 1:
        # silent promotion would run narrow members at a wider dtype and
        # break batched-vs-sequential parity; make the caller decide
        raise ValueError(
            f"pad_coo_batch needs one common value dtype, got "
            f"{sorted(str(d) for d in vdtypes)} — cast the members, or plan "
            f"with a concrete spec dtype"
        )
    (vdtype,) = vdtypes
    idx = np.zeros((k, target, ndim), dtype=np.int32)
    val = np.zeros((k, target), dtype=vdtype)
    for b, c in enumerate(coos):
        n = int(c.indices.shape[0])
        idx[b, :n] = np.asarray(c.indices)
        val[b, :n] = np.asarray(c.values)
    return jnp.asarray(idx), jnp.asarray(val)
