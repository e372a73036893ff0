"""HOOI sweep machinery: the compiled program layer of the decomposition.

Every sparse fit (paper Alg. 2) runs one compiled scan-over-sweeps program:
``_scan_sweeps`` on one device, ``_segment_scan_sweeps`` for the snapshot
layer's resumable segments, ``_batched_scan_sweeps`` vmapped over a batch,
and ``build_sharded_program`` over a device mesh. All four share the one
sweep skeleton ``_sweep_scan`` and take their per-mode unfoldings from
``_engine_unfoldings``, so an engine runs the same code on one chip and on
each chip of a mesh. The module also owns the trace/dispatch
instrumentation the regression tests read. The front end lives in
``repro.tucker`` (plan/execute API).

Convergence metric: for orthonormal factors produced by SVD/QRP the
projection identity  ||X - G x {U}||_F^2 = ||X||_F^2 - ||G||_F^2  holds, so
the relative reconstruction error is computed without ever densifying X.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stages
from repro.core.coo import SparseCOO, fold_dense
from repro.core.kron import sparse_ttm_chain, sparse_ttm_chain_reuse_device
from repro.core.qrp import factor_update
from repro.core.ttm import ttm_unfolded
from repro.obs import registry as _obs_registry


class _MirroredCounter(collections.Counter):
    """A ``collections.Counter`` whose every increment also ticks one
    registry :class:`~repro.obs.metrics.Counter` — the keyed dicts below
    stay the fine-grained source the regression tests read, while the
    registry (and so Prometheus and the benchmark's counters) sees the
    totals."""

    def __init__(self, metric_name: str, help: str) -> None:
        super().__init__()
        self._metric = _obs_registry.counter(metric_name, help)
        self._count_lock = threading.Lock()

    def tick(self, key, n: int = 1) -> None:
        """Atomic increment. Concurrent flush executors (repro.serve) bump
        these counters from several threads; a bare ``counter[k] += 1`` is a
        read-modify-write that can lose increments under that interleaving,
        and the dispatch-count CI gates would misreport."""
        with self._count_lock:
            dict.__setitem__(self, key, self.get(key, 0) + n)
        self._metric.inc(n)

    def __setitem__(self, key, value) -> None:
        with self._count_lock:
            delta = value - self.get(key, 0)
            if delta > 0:
                self._metric.inc(delta)
            dict.__setitem__(self, key, value)

# -- instrumentation ---------------------------------------------------------
# SWEEP_TRACE_COUNTS ticks once per *trace* of the compiled sweep program
# (inside the traced body, so cache hits don't count) — the no-retrace
# regression tests read it. SWEEP_DISPATCH_COUNTS ticks once per top-level
# XLA dispatch the sparse driver issues: one per fit, per vmapped batch and
# per snapshot segment.
SWEEP_TRACE_COUNTS: collections.Counter = _MirroredCounter(
    "repro_sweep_traces_total",
    "traces of the compiled sweep pipelines (retraces when it keeps rising)",
)
SWEEP_DISPATCH_COUNTS: collections.Counter = _MirroredCounter(
    "repro_sweep_dispatches_total",
    "top-level XLA dispatches issued by the sparse drivers",
)

# the single device->host transfer of the scan pipeline (fit history); a
# module-level seam so tests can count that it really happens exactly once.
_fetch_history = jax.device_get

# scan-pipeline sentinel for "this sweep never ran" (tol early-exit). A real
# relative error is always >= 0 (or NaN on degenerate input, which must also
# count as a ran sweep), so -1 is unambiguous.
_SKIPPED = -1.0


@dataclasses.dataclass
class HooiResult:
    core: jax.Array  # (R_1, ..., R_N)
    factors: List[jax.Array]  # U_n: (I_n, R_n), orthonormal columns
    rel_error: jax.Array  # ||X - Xhat||_F / ||X||_F
    fit_history: np.ndarray  # per-sweep relative error
    engine: str = "xla"  # resolved sweep engine ("xla" for the dense driver)

    @classmethod
    def from_history(cls, core, factors, hist, engine: str = "xla", **extra):
        """Build a result from a (possibly empty) fit history.

        The single guarded construction path: when every sweep was masked
        (e.g. an all-sentinel scan history) ``hist`` is empty and the final
        relative error is NaN — never an ``IndexError`` on ``hist[-1]``.
        ``extra`` passes through to subclass fields (``TuckerResult``).
        """
        hist = np.asarray(hist).reshape(-1)
        rel = (
            jnp.asarray(hist[-1]) if hist.size else jnp.asarray(jnp.float32(jnp.nan))
        )
        return cls(core, factors, rel, hist, engine=engine, **extra)


def init_factors(
    shape: Sequence[int],
    ranks: Sequence[int],
    key: jax.Array,
    orthonormal: bool = True,
    dtype=None,
) -> List[jax.Array]:
    """Alg. 2 line 1: random init (orthonormalized for a sane first sweep).
    ``dtype=None`` follows the jax x64 flag."""
    if dtype is None:
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    factors = []
    with jax.named_scope(stages.INIT):
        keys = jax.random.split(key, len(shape))
        for k, (i, r) in zip(keys, zip(shape, ranks)):
            u = jax.random.normal(k, (i, r), dtype=dtype)
            if orthonormal:
                # lapack has no half-precision QR: orthonormalize at >= f32
                # and cast back to the working dtype.
                qdt = jnp.promote_types(dtype, jnp.float32)
                q, _ = jnp.linalg.qr(u.astype(qdt))
                u = q.astype(dtype)
            factors.append(u)
    return factors


# ---------------------------------------------------------------------------
# Sparse HOOI (paper Alg. 2) — the paper's accelerator algorithm.
# ---------------------------------------------------------------------------


def effective_ranks(shape: Sequence[int], ranks: Sequence[int]) -> List[int]:
    """Clamp the multilinear rank to what is representable:
    R_n <= min(I_n, prod_{t != n} R_t). (A matrix "rank [30,35]" — the
    paper's angiogram setting — is effectively [30,30]: Y_(n) has only
    prod_{t!=n} R_t columns, so QRP cannot produce more.) Iterated to a
    fixpoint since the bound couples the ranks."""
    r = [min(int(rr), int(s)) for rr, s in zip(ranks, shape)]
    for _ in range(len(r)):
        changed = False
        for m in range(len(r)):
            bound = int(np.prod([r[t] for t in range(len(r)) if t != m]))
            if r[m] > bound:
                r[m] = bound
                changed = True
        if not changed:
            break
    return r


# ---------------------------------------------------------------------------
# Compiled scan-over-sweeps pipeline: the entire multi-sweep HOOI loop is ONE
# XLA program per (engine, shape, ranks, method, n_iter). Schedules arrive as
# device-resident pytrees (sparse.layout.DeviceSchedule), factor/core buffers
# are donated, the ``tol`` early-exit is a cond-masked scan, and the fit
# history crosses device->host exactly once per call.
# ---------------------------------------------------------------------------


def _sweep_scan(
    mode_unfolding,
    core_unfolding,
    factors,
    xnorm2,
    tol,
    *,
    ranks,
    method,
    n_iter,
    core_dtype,
    carry_in=None,
    total_sweeps=None,
):
    """The scan-over-sweeps skeleton shared by every compiled pipeline
    (single-device, vmapped batch, shard_map mesh): ``n_iter`` cond-masked
    ALS sweeps with the dynamic-``tol`` early exit, parameterized over how
    one mode unfolding / core update executes. Keeping the skeleton single
    means the sharded program inherits tol semantics, dtype pinning and the
    skip sentinel by construction — parity is structural, not retested per
    pipeline.

    The snapshot/resume layer runs the SAME skeleton in chunks: ``carry_in``
    = ``(core, prev_err, done, n_done)`` restarts the scan mid-job (a resumed
    segment picks up the convergence state bit-for-bit), and the dynamic
    ``total_sweeps`` masks sweeps past the job's true budget so every segment
    — including a short final one, at any resume offset — reuses ONE compiled
    program. Both default to the fresh-start behavior.

    Returns ``(factors, core, hist, (prev_err, done, n_done))``; callers that
    never resume just drop the carry.
    """
    n = len(factors)
    init_dtypes = tuple(f.dtype for f in factors)

    def run_sweep(carry):
        fs, _, prev_err, done, n_done = carry
        fs = list(fs)
        y_n = None
        for mode in range(n):
            y_n = mode_unfolding(fs, mode)
            # pin each factor to its init dtype so the scan carry is a
            # fixpoint even when a kernel path emits a different precision.
            fs[mode] = factor_update(y_n, ranks[mode], method).astype(
                init_dtypes[mode]
            )
        # the core update sees the POST-update factor list (only fs[n-1]
        # changed since y_n was built) — the fused megakernel re-gathers
        # from it, the split path contracts y_n against fs[n-1] directly.
        g_n = core_unfolding(fs, y_n)
        with jax.named_scope(stages.CORE):
            core = fold_dense(g_n, n - 1, list(ranks)).astype(core_dtype)
            err = (
                jnp.sqrt(jnp.maximum(xnorm2 - jnp.sum(jnp.square(core)), 0.0))
                / jnp.sqrt(xnorm2)
            ).astype(jnp.float32)
            # stop once two consecutive sweeps agree to within tol (never
            # on the first sweep — prev_err starts at +inf).
            done = (tol > 0) & jnp.isfinite(prev_err) & (jnp.abs(prev_err - err) < tol)
        return tuple(fs), core, err, done, n_done + jnp.int32(1)

    def body(carry, _):
        fs, core, prev_err, done, n_done = carry
        already_done = done
        if total_sweeps is not None:
            # segment mode: the job's sweep budget is dynamic, so a segment
            # that crosses it masks the excess sweeps exactly like tol does.
            already_done = already_done | (n_done >= total_sweeps)
        carry = (fs, core, prev_err, already_done, n_done)
        carry = jax.lax.cond(already_done, lambda c: c, run_sweep, carry)
        # sweeps skipped by the early-exit emit the sentinel, not an error.
        emitted = jnp.where(already_done, jnp.float32(_SKIPPED), carry[2])
        return carry, emitted

    if carry_in is None:
        core0 = jnp.zeros(tuple(ranks), dtype=core_dtype)
        prev0 = jnp.float32(jnp.inf)
        done0 = jnp.asarray(False)
        n_done0 = jnp.int32(0)
    else:
        core0, prev0, done0, n_done0 = carry_in
        core0 = jnp.asarray(core0, dtype=core_dtype)
        prev0 = jnp.asarray(prev0, dtype=jnp.float32)
        done0 = jnp.asarray(done0, dtype=bool)
        n_done0 = jnp.asarray(n_done0, dtype=jnp.int32)
    carry0 = (tuple(factors), core0, prev0, done0, n_done0)
    (fs, core, prev_err, done, n_done), hist = jax.lax.scan(
        body, carry0, None, length=n_iter
    )
    return fs, core, hist, (prev_err, done, n_done)


def _engine_unfoldings(
    indices, values, scheds, *, shape, engine_name, interpret, use_reuse,
    precision="fp32", bl=None, bk=None, fuse_core=False,
):
    """The one place a compiled pipeline's per-mode unfolding / core update
    come from — shared by the full-run scan program and the snapshot segment
    program so engine routing (pallas kernels, Kron-reuse dedup, plain XLA)
    cannot drift between them. ``precision``/``bl``/``bk``/``fuse_core`` are
    the autotuner-facing statics: kernel block shapes, the mixed-precision
    axis, and the fused-megakernel core layout (pallas only).

    Called once per program, outside ``_sweep_scan``: on the pallas engine
    it orders the nonzeros into every mode's schedule order here, and every
    sweep of the call reads those operands."""
    n = len(shape)
    if engine_name == "pallas":
        from repro.kernels import ops

        ordered = [ops.order_gather(indices, values, scheds[m], m) for m in range(n)]

    def mode_unfolding(fs, mode):
        if engine_name == "pallas":
            return ops.sorted_ttm_chain(
                ordered[mode], fs, mode, scheds[mode],
                shape=shape, interpret=interpret, precision=precision,
            )
        if use_reuse:
            return sparse_ttm_chain_reuse_device(
                indices, values, fs, mode, scheds[mode], shape=shape
            )
        return sparse_ttm_chain(
            SparseCOO(indices, values, shape), fs, mode, precision=precision
        )

    def core_unfolding(fs, y_n):
        if engine_name == "pallas":
            if fuse_core:
                # megakernel: G = U^T Y with Y rebuilt in VMEM from the
                # mode-(N-1) unfolding's own sorted operands — the
                # unfolding never crosses HBM a second time.
                return ops.sorted_ttm_core(
                    ordered[n - 1], fs, n - 1, scheds[n - 1],
                    shape=shape, interpret=interpret, precision=precision,
                )
            with jax.named_scope(stages.CORE):
                return ops.ttm(
                    y_n.T, fs[n - 1].T, bl=bl, bk=bk, interpret=interpret,
                    precision=precision,
                ).T
        with jax.named_scope(stages.CORE):
            return ttm_unfolded(y_n.T, fs[n - 1].T).T

    return mode_unfolding, core_unfolding


def _scan_sweeps_impl(
    indices,
    values,
    factors,
    xnorm2,
    tol,
    scheds,
    *,
    shape,
    ranks,
    method,
    n_iter,
    engine_name,
    interpret,
    use_reuse,
    precision="fp32",
    bl=None,
    bk=None,
    fuse_core=False,
):
    # trace-time only: cache hits never reach this line.
    SWEEP_TRACE_COUNTS.tick((engine_name, shape, tuple(ranks), method, n_iter))

    mode_unfolding, core_unfolding = _engine_unfoldings(
        indices, values, scheds,
        shape=shape, engine_name=engine_name, interpret=interpret,
        use_reuse=use_reuse, precision=precision, bl=bl, bk=bk,
        fuse_core=fuse_core,
    )
    fs, core, hist, _ = _sweep_scan(
        mode_unfolding, core_unfolding, factors, xnorm2, tol,
        ranks=ranks, method=method, n_iter=n_iter,
        # working precision of the core carry: float64 inputs keep float64,
        # float32 and narrower compute in float32.
        core_dtype=jnp.promote_types(values.dtype, jnp.float32),
    )
    return fs, core, hist


# the compiled per-tensor program (tests introspect its jit cache directly).
_scan_sweeps = partial(
    jax.jit,
    static_argnames=(
        "shape", "ranks", "method", "n_iter", "engine_name", "interpret",
        "use_reuse", "precision", "bl", "bk", "fuse_core",
    ),
    donate_argnames=("factors",),
)(_scan_sweeps_impl)


def _segment_scan_sweeps_impl(
    indices,
    values,
    factors,
    core,
    xnorm2,
    tol,
    prev_err,
    done,
    n_done,
    total_sweeps,
    scheds,
    *,
    shape,
    ranks,
    method,
    segment_len,
    engine_name,
    interpret,
    use_reuse,
    precision="fp32",
    bl=None,
    bk=None,
    fuse_core=False,
):
    """One snapshot segment: ``segment_len`` sweeps of the SAME skeleton as
    ``_scan_sweeps``, continuing from an explicit carry. ``total_sweeps`` is
    dynamic, so one compiled program serves every segment of a job — the
    short final one and any resume offset included (the no-retrace contract
    the snapshot layer keeps)."""
    # trace-time only: cache hits never reach this line.
    SWEEP_TRACE_COUNTS.tick((engine_name, shape, tuple(ranks), method, "segment", segment_len))

    mode_unfolding, core_unfolding = _engine_unfoldings(
        indices, values, scheds,
        shape=shape, engine_name=engine_name, interpret=interpret,
        use_reuse=use_reuse, precision=precision, bl=bl, bk=bk,
        fuse_core=fuse_core,
    )
    return _sweep_scan(
        mode_unfolding, core_unfolding, factors, xnorm2, tol,
        ranks=ranks, method=method, n_iter=segment_len,
        core_dtype=jnp.promote_types(values.dtype, jnp.float32),
        carry_in=(core, prev_err, done, n_done),
        total_sweeps=total_sweeps,
    )


# the compiled segment program of the snapshot/resume layer. Factors are NOT
# donated: the host spills each segment's carry to a checkpoint right after
# the dispatch, and must never race a donated buffer.
_segment_scan_sweeps = partial(
    jax.jit,
    static_argnames=(
        "shape", "ranks", "method", "segment_len", "engine_name", "interpret",
        "use_reuse", "precision", "bl", "bk", "fuse_core",
    ),
)(_segment_scan_sweeps_impl)


@partial(jax.jit, static_argnames=("shape", "ranks", "method", "n_iter", "dtype"))
def _batched_scan_sweeps(
    indices, values, keys, tol, *, shape, ranks, method, n_iter, dtype=None
):
    """The whole batched decomposition — random factor init, norm, and the
    multi-sweep loop — vmapped over a leading batch of same-shape, nnz-padded
    sparse tensors: ``TuckerPlan.batch``'s (and the serving flush path's) one
    XLA dispatch for k decompositions. The init/norm preamble is fused INTO
    the program on purpose: run eagerly it costs several small dispatches per
    flush, which on CPU dwarfs the batched sweep itself and erases the
    amortization a micro-batching service exists to deliver. Plain-XLA engine
    only: Pallas / Kron-reuse schedules are per-tensor pytrees of
    data-dependent size and cannot share one batched program."""

    def one(idx, val, key):
        fs = tuple(init_factors(shape, ranks, key, dtype=dtype))
        # identical formula to the per-tensor path (square of the norm); the
        # vmapped program still reduces in its own order, so batched results
        # match sequential calls to the last bit or so, not bitwise.
        with jax.named_scope(stages.INIT):
            xn = jnp.square(jnp.sqrt(jnp.sum(jnp.square(val.astype(jnp.float32)))))
        return _scan_sweeps_impl(
            idx, val, fs, xn, tol, None,
            shape=shape, ranks=ranks, method=method, n_iter=n_iter,
            engine_name="xla", interpret=False, use_reuse=False,
        )

    fs, core, hist = jax.vmap(one)(indices, values, keys)
    # split per-member outputs INSIDE the program: k separate result buffers
    # fall out of the one dispatch, instead of 4k eager slice dispatches on
    # the host afterwards (which would out-cost the batched sweep on CPU).
    k = indices.shape[0]
    cores = tuple(core[i] for i in range(k))
    factors = tuple(tuple(f[i] for f in fs) for i in range(k))
    return cores, factors, hist


# ---------------------------------------------------------------------------
# Sharded scan pipeline: the multi-sweep loop as ONE shard_map-wrapped XLA
# program over a device mesh. Nonzeros are sharded along the mesh's nnz axes
# (see sparse.layout.ShardSchedule); inside the program each device runs the
# engine's Kron-accumulation over its local shard — on the Pallas engine the
# fused Kron-scatter kernel on the shard's own schedule — to get a *partial*
# Y_(n), a single psum over the nnz axes completes the sum (the scatter-add
# is linear in the nonzeros, so partial sums commute), and the small QRP
# factor update runs replicated on every device. Per-sweep collective
# traffic is N psums of I_n x prod_{t != n} R_t f32 — independent of nnz.
# ---------------------------------------------------------------------------

def build_sharded_program(mesh, nnz_axes, *, shape, ranks, method, n_iter,
                          resumable=False, engine_name="xla", interpret=False,
                          precision="fp32", bl=None, bk=None):
    """Build the one-dispatch sharded sweep program (uncached: each call
    returns a fresh jit-wrapped callable with its own compile cache, so the
    CALLER owns the program's lifetime — ``TuckerPlan`` holds exactly one
    and the plan cache's LRU eviction frees the compiled executable with
    the plan, instead of pinning it in a module-level registry forever).

    Returns ``program(indices, values, factors, xnorm2, tol, scheds)`` ->
    ``(factors, core, hist)`` where indices/values are committed with a
    ``NamedSharding`` over ``nnz_axes`` (``sparse.layout.build_shard_schedule``)
    and factors/xnorm2/tol are replicated. ``scheds`` is ``None`` on the XLA
    engine; on the Pallas engine it is the per-mode tuple of
    ``DeviceSchedule`` objects whose fields stack every shard's own schedule on a
    leading axis sharded over ``nnz_axes`` (``ShardSchedule.scheds``). Each
    device builds its unfoldings exactly as the single-device program does
    (``_engine_unfoldings``, so on Pallas it orders its nonzeros once a
    call) and psums each ``Y_(n)``; the core update is the split path on the
    psummed ``Y_(N)``. The whole multi-sweep loop — cond-masked ``tol``
    early exit included — is one XLA program; only the fit history crosses
    back to host.

    ``resumable=True`` builds the snapshot-segment variant instead:
    ``program(indices, values, factors, core, xnorm2, tol, prev_err, done,
    n_done, total_sweeps, scheds)`` -> ``(factors, core, hist, (prev_err,
    done, n_done))`` — ``n_iter`` sweeps continuing from an explicit
    replicated carry, with the job's true budget dynamic so one compiled
    program serves every segment at any resume offset. Factors are not
    donated there: the host spills the carry to a checkpoint right after
    each dispatch.
    """
    from jax.sharding import PartitionSpec as P

    nnz_axes = tuple(nnz_axes)
    shape, ranks = tuple(shape), tuple(ranks)
    n = len(shape)
    n_shards = int(np.prod([mesh.shape[a] for a in nnz_axes]))

    def _unfoldings(indices, values, scheds):
        # per-device view: indices (nnz_padded / n_shards, N), values
        # (nnz_padded / n_shards,), each schedule field (1, ...) — this
        # shard's own — and factors replicated.
        if scheds is not None:
            scheds = tuple(jax.tree.map(lambda a: a[0], s) for s in scheds)
        mode_unfolding, core_unfolding = _engine_unfoldings(
            indices, values, scheds, shape=shape, engine_name=engine_name,
            interpret=interpret, use_reuse=False, precision=precision,
            bl=bl, bk=bk,
        )

        def summed_unfolding(fs, mode):
            partial_y = mode_unfolding(fs, mode)
            with jax.named_scope(stages.PSUM):
                return jax.lax.psum(partial_y, nnz_axes)

        return summed_unfolding, core_unfolding

    factor_specs = tuple(P(None, None) for _ in range(n))
    core_spec = P(*([None] * n))
    # every leaf of every mode's stacked schedule: this shard's row
    sched_spec = P(nnz_axes)
    key = ("sharded", engine_name, shape, ranks, method, int(n_iter), n_shards)

    if resumable:
        def segment_body(indices, values, factors, core, xnorm2, tol,
                         prev_err, done, n_done, total_sweeps, scheds):
            mode_unfolding, core_unfolding = _unfoldings(indices, values, scheds)
            return _sweep_scan(
                mode_unfolding, core_unfolding, factors, xnorm2, tol,
                ranks=ranks, method=method, n_iter=n_iter,
                core_dtype=jnp.promote_types(values.dtype, jnp.float32),
                carry_in=(core, prev_err, done, n_done),
                total_sweeps=total_sweeps,
            )

        in_specs = (
            P(nnz_axes, None),  # indices
            P(nnz_axes),  # values
            factor_specs,  # factors (replicated)
            core_spec,  # core carry (replicated)
            P(), P(),  # xnorm2, tol
            P(), P(), P(), P(),  # prev_err, done, n_done, total_sweeps
            sched_spec,  # per-shard schedules
        )
        out_specs = (
            factor_specs,
            core_spec,
            P(None),  # fit history
            (P(), P(), P()),  # carry out: prev_err, done, n_done
        )
        inner = jax.shard_map(
            segment_body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

        def traced(indices, values, factors, core, xnorm2, tol,
                   prev_err, done, n_done, total_sweeps, scheds):
            # trace-time only (outside the shard_map body, which jax may
            # trace more than once per build): cache hits never reach here.
            SWEEP_TRACE_COUNTS.tick(key + ("segment",))
            return inner(indices, values, factors, core, xnorm2, tol,
                         prev_err, done, n_done, total_sweeps, scheds)

        return jax.jit(traced)

    def sweep_body(indices, values, factors, xnorm2, tol, scheds):
        mode_unfolding, core_unfolding = _unfoldings(indices, values, scheds)
        fs, core, hist, _ = _sweep_scan(
            mode_unfolding, core_unfolding, factors, xnorm2, tol,
            ranks=ranks, method=method, n_iter=n_iter,
            core_dtype=jnp.promote_types(values.dtype, jnp.float32),
        )
        return fs, core, hist

    in_specs = (
        P(nnz_axes, None),  # indices
        P(nnz_axes),  # values
        factor_specs,  # factors (replicated)
        P(),  # xnorm2
        P(),  # tol
        sched_spec,  # per-shard schedules
    )
    out_specs = (
        factor_specs,  # factors
        core_spec,  # core
        P(None),  # fit history
    )
    inner = jax.shard_map(
        sweep_body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )

    def traced(indices, values, factors, xnorm2, tol, scheds):
        # trace-time only (outside the shard_map body, which jax may trace
        # more than once per build): cache hits never reach this line.
        SWEEP_TRACE_COUNTS.tick(key)
        return inner(indices, values, factors, xnorm2, tol, scheds)

    # factors are donated like the single-device _scan_sweeps: the plan
    # hands in freshly-initialized (or defensively copied) buffers, so the
    # replicated inputs can be consumed by the replicated outputs in place.
    return jax.jit(traced, donate_argnums=(2,))
