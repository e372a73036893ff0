"""Kernel block-size autotuner for the Pallas sweep engine.

The paper's FPGA sizes its dataflow buffers once per (tensor, rank) problem
at synthesis time; the TPU analogue is choosing the Pallas block shapes —
``bn`` (nonzeros per Kron/scatter block), ``bi`` (unfolding rows resident in
VMEM), ``bl``/``bk`` (TTM tile), and the kernel ``layout`` ("split" = the
unfolding kernel + standalone blocked TTM, "fused" = the Kron→scatter→TTM
megakernel for the core update). This module searches that space once per
problem *fingerprint* and persists the winner in an on-disk JSON table, so a
warm ``tucker.plan`` pays zero search cost (counter-asserted in
``tests/test_autotune.py``).

Search = analytic prune + short timed trials:

1. every candidate's VMEM footprint is computed from the block shapes; ones
   that blow the per-core budget are discarded before any compilation;
2. survivors are ranked by modeled arithmetic intensity (FLOPs per HBM byte
   of one grid step — larger ``bi`` amortizes the contrib block over more
   resident rows; the fused layout skips one full Y round-trip);
3. the top ``max_trials`` (the hand-picked default always included — the
   tuned result can never lose to it) run one compiled ALS sweep each on a
   synthetic nnz-capped problem, best wall-clock wins.

The table key is a stable fingerprint: shape, ranks, the nnz bucket
(power-of-2 — so serving-plane nnz jitter maps to one entry), dtype,
precision and backend. Set ``REPRO_AUTOTUNE_TABLE`` to relocate the table
(tests point it at a tmpdir); the default lives under ``~/.cache/repro``.
"""
from __future__ import annotations

import hashlib
import json
import os
import tempfile
import time
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from repro.obs import event as _obs_event
from repro.obs import registry as _obs_registry
from repro.obs import span as _obs_span

TABLE_ENV = "REPRO_AUTOTUNE_TABLE"
TABLE_VERSION = 1
LAYOUTS = ("split", "fused")
# per-core VMEM budget the prune enforces (v5e has 128 MiB/core; stay well
# under it — the compiler needs headroom for double buffering).
VMEM_BUDGET_BYTES = 16 * 2**20

# one process-wide counter set, reset by tests: a warm plan must show zero
# searches and zero trials (the acceptance criterion of the tuning table).
COUNTERS: Dict[str, int] = {"searches": 0, "trials": 0, "table_hits": 0}

# registry twins of COUNTERS — cumulative (reset_counters does not touch
# them), so Prometheus sees lifetime totals while tests keep their
# resettable process-local dict.
_REG_COUNTERS = {
    k: _obs_registry.counter(
        f"repro_autotune_{k}_total", f"autotune {k.replace('_', ' ')}"
    )
    for k in COUNTERS
}


def _count(kind: str) -> None:
    COUNTERS[kind] += 1
    _REG_COUNTERS[kind].inc()


def reset_counters() -> None:
    for k in COUNTERS:
        COUNTERS[k] = 0


class BlockConfig(NamedTuple):
    """One point in the kernel block-shape search space."""

    bl: int = 256  # TTM: rows of Y per grid step
    bk: int = 512  # TTM: contraction slab per grid step
    bn: int = 128  # Kron/scatter: nonzeros per block
    bi: int = 128  # Kron/scatter: unfolding rows resident in VMEM
    layout: str = "split"  # "split" | "fused" (megakernel core update)


# the hand-picked kernel defaults (kernels' own DEFAULT_* constants): always
# in the candidate set, so the autotuned pick is >= the default by
# construction — the search can only improve on it.
DEFAULT_CONFIG = BlockConfig()


def nnz_bucket(nnz: int) -> int:
    """Power-of-2 bucket of a nonzero count — the fingerprint's nnz term, so
    serving-plane nnz jitter inside one bucket reuses one tuned entry."""
    n = max(1, int(nnz))
    return 1 << (n - 1).bit_length()


def fingerprint(
    shape: Sequence[int],
    ranks: Sequence[int],
    nnz: int,
    *,
    dtype: str = "float32",
    precision: str = "fp32",
    backend: Optional[str] = None,
) -> str:
    """Stable identity of one tuning problem (the table key)."""
    if backend is None:
        import jax

        backend = jax.default_backend()
    key = {
        "shape": [int(s) for s in shape],
        "ranks": [int(r) for r in ranks],
        "nnz_bucket": nnz_bucket(nnz),
        "dtype": str(dtype),
        "precision": str(precision),
        "backend": str(backend),
    }
    blob = json.dumps(key, sort_keys=True, separators=(",", ":"))
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# Cost model: VMEM footprint (hard prune) + arithmetic intensity (ranking).
# ---------------------------------------------------------------------------


def _elt_bytes(precision: str) -> int:
    return 2 if precision == "bf16_fp32acc" else 4


def vmem_bytes(
    cfg: BlockConfig, shape: Sequence[int], ranks: Sequence[int],
    precision: str = "fp32",
) -> int:
    """Modeled VMEM working set of the busiest grid step.

    The sweep's resident blocks: the Kron operand blocks a (bn, Ra) and
    b (bn, Rb) at operand precision, value/rel rows, the f32 Y scratch
    (bi, K), and — fused layout — the U block (bi, Rp) plus the resident
    core output (Rp, K). The TTM tile (bl x bk operand + bl x R output) is
    counted too; the max over the two kernels is what must fit."""
    n = len(shape)
    eb = _elt_bytes(precision)
    # worst mode for the Kron kernel: largest K = prod of non-mode ranks.
    ks = []
    for m in range(n):
        ks.append(int(np.prod([r for t, r in enumerate(ranks) if t != m])))
    k_max = max(ks)
    ra = max(ranks)
    kron = (
        cfg.bn * (ra + ra) * eb  # a, b operand blocks
        + cfg.bn * 2 * 4  # v, rel rows (f32/i32)
        + cfg.bi * k_max * 4  # Y scratch / output block (f32 accum)
    )
    if cfg.layout == "fused":
        rp = -(-max(ranks) // 8) * 8
        kron += cfg.bi * rp * eb  # resident U block
        kron += rp * k_max * 4  # resident core output
    r = max(ranks)
    ttm = (cfg.bl * cfg.bk * eb) + (cfg.bk * r * eb) + (cfg.bl * r * 4)
    return max(kron, ttm)


def arithmetic_intensity(
    cfg: BlockConfig, shape: Sequence[int], ranks: Sequence[int],
    nnz: int, precision: str = "fp32",
) -> float:
    """Modeled FLOPs per HBM byte of one sweep's Kron/scatter work — the
    ranking metric (higher = more likely compute-bound). Per block of bn
    nonzeros: the Kron build + scale is ~3*bn*K flops, the one-hot matmul
    re-association adds 2*bn*bi*K; HBM moves the operand blocks in and — on
    the split layout only — the (bi, K) Y block out per row-block group.
    The fused layout keeps Y in VMEM and adds the U-block load plus the
    2*bi*r*K contraction flops."""
    n = len(shape)
    eb = _elt_bytes(precision)
    k = int(np.prod([r for t, r in enumerate(ranks) if t != n - 1]))
    r = ranks[n - 1]
    nb = max(1, int(nnz)) / cfg.bn  # blocks per sweep mode
    flops = nb * (3 * cfg.bn * k + 2 * cfg.bn * cfg.bi * k)
    bytes_in = nb * cfg.bn * (2 * max(ranks) * eb + 8)
    # row-block groups: assume each block finishes ~one group (worst case
    # for the split layout's Y write-back traffic).
    y_bytes = nb * cfg.bi * k * 4
    if cfg.layout == "fused":
        flops += nb * 2 * cfg.bi * r * k
        bytes_io = bytes_in + nb * cfg.bi * r * eb  # U loads; Y never moves
    else:
        bytes_io = bytes_in + 2 * y_bytes  # Y write + TTM read-back
    return flops / max(1.0, bytes_io)


def candidate_configs(
    shape: Sequence[int],
    ranks: Sequence[int],
    nnz: int,
    *,
    precision: str = "fp32",
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> List[BlockConfig]:
    """The pruned, intensity-ranked candidate list. ``DEFAULT_CONFIG`` is
    always first — the tuned pick can never lose to the hand-picked
    baseline — followed by survivors in descending modeled intensity."""
    n = len(shape)
    cands = []
    for bn in (64, 128, 256):
        for bi in (64, 128, 256):
            for bl, bk in ((128, 256), (256, 512), (512, 512)):
                layouts = LAYOUTS if n == 3 else ("split",)
                for layout in layouts:
                    cands.append(BlockConfig(bl, bk, bn, bi, layout))
    kept = [
        c for c in cands
        if vmem_bytes(c, shape, ranks, precision) <= vmem_budget
    ]
    kept.sort(
        key=lambda c: arithmetic_intensity(c, shape, ranks, nnz, precision),
        reverse=True,
    )
    out = [DEFAULT_CONFIG]
    out.extend(c for c in kept if c != DEFAULT_CONFIG)
    return out


# ---------------------------------------------------------------------------
# Persistent tuning table.
# ---------------------------------------------------------------------------


def default_table_path() -> str:
    env = os.environ.get(TABLE_ENV)
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "autotune.json"
    )


class TuningTable:
    """On-disk JSON map fingerprint -> winning :class:`BlockConfig`.

    Writes are atomic (tmp file + ``os.replace``) so concurrent processes
    never observe a torn table; reads tolerate a missing or corrupt file
    (an unreadable table is an empty one, never a crash)."""

    def __init__(self, path: Optional[str] = None):
        self.path = path if path is not None else default_table_path()
        self._entries: Dict[str, dict] = {}
        self._load()

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                data = json.load(f)
            if data.get("version") == TABLE_VERSION:
                self._entries = dict(data.get("entries", {}))
        except (OSError, ValueError):
            self._entries = {}

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, fp: str) -> bool:
        return fp in self._entries

    def get(self, fp: str) -> Optional[BlockConfig]:
        e = self._entries.get(fp)
        if e is None:
            return None
        c = e["config"]
        return BlockConfig(
            int(c["bl"]), int(c["bk"]), int(c["bn"]), int(c["bi"]),
            str(c["layout"]),
        )

    def put(self, fp: str, cfg: BlockConfig, *, key: Optional[dict] = None,
            trial_ms: Optional[float] = None) -> None:
        self._entries[fp] = {
            "config": dict(cfg._asdict()),
            "key": key or {},
            "trial_ms": trial_ms,
        }

    def save(self) -> None:
        d = os.path.dirname(self.path) or "."
        os.makedirs(d, exist_ok=True)
        payload = {"version": TABLE_VERSION, "entries": self._entries}
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise


# ---------------------------------------------------------------------------
# Timed trials + the search entry point.
# ---------------------------------------------------------------------------

TRIAL_NNZ_CAP = 4096  # trials time a capped synthetic problem: search cost
#                       must stay O(seconds) even for huge inputs


def _synthetic_coo(shape: Sequence[int], nnz: int, dtype: str):
    import jax.numpy as jnp

    from repro.core.coo import SparseCOO

    rng = np.random.default_rng(0)
    idx = np.stack(
        [rng.integers(0, s, size=nnz) for s in shape], axis=1
    ).astype(np.int32)
    vals = rng.standard_normal(nnz).astype(dtype)
    return SparseCOO(jnp.asarray(idx), jnp.asarray(vals), tuple(shape))


def trial_time_ms(
    cfg: BlockConfig,
    shape: Sequence[int],
    ranks: Sequence[int],
    nnz: int,
    *,
    dtype: str = "float32",
    precision: str = "fp32",
    interpret: Optional[bool] = None,
    repeats: int = 2,
) -> float:
    """Best wall-clock of one compiled ALS sweep under ``cfg`` on a
    synthetic nnz-capped problem (compile excluded via one warmup).

    The trial times the COMPILED scan-sweep program — the exact executable a
    ``tucker.plan`` deploys — not the eager per-kernel driver: on CPU the
    eager path is interpreter-overhead-bound (every config times the same),
    while inside the compiled program the layouts genuinely differ (e.g.
    the fused megakernel trades recompute for HBM traffic, a loss on
    backends where bytes are free), so only the compiled timing ranks
    candidates the way deployment will experience them."""
    import jax
    import jax.numpy as jnp

    from repro.core import hooi as _hooi
    from repro.core.engine import make_engine

    _count("trials")
    with _obs_span("autotune.trial", layout=cfg.layout, bn=cfg.bn, bi=cfg.bi,
                   nnz=min(int(nnz), TRIAL_NNZ_CAP)) as _sp:
        return _trial_time_ms_body(
            _sp, cfg, shape, ranks, nnz, dtype=dtype, precision=precision,
            interpret=interpret, repeats=repeats,
        )


def _trial_time_ms_body(_sp, cfg, shape, ranks, nnz, *, dtype, precision,
                        interpret, repeats) -> float:
    import jax
    import jax.numpy as jnp

    from repro.core import hooi as _hooi
    from repro.core.engine import make_engine

    coo = _synthetic_coo(shape, min(int(nnz), TRIAL_NNZ_CAP), dtype)
    eng = make_engine(
        "pallas", precision=precision, interpret=interpret,
        fuse_core=cfg.layout == "fused",
    )
    eng.apply_blocks(cfg)
    factors = _hooi.init_factors(shape, ranks, jax.random.PRNGKey(0))
    scheds = tuple(eng.device_schedule(coo, m) for m in range(len(shape)))
    xnorm2 = jnp.square(coo.norm())

    def sweep():
        # the scan program donates its factor buffers: hand it copies
        fs = tuple(jnp.array(f, copy=True) for f in factors)
        out = _hooi._scan_sweeps(
            coo.indices, coo.values, fs, xnorm2,
            jnp.float32(0.0), scheds,
            shape=tuple(shape), ranks=tuple(ranks), method="gram",
            n_iter=1, engine_name="pallas",
            interpret=eng.resolved_interpret(),
            use_reuse=False, precision=eng.precision,
            bl=eng.bl, bk=eng.bk, fuse_core=eng.fuse_core,
        )
        jax.block_until_ready(out)

    sweep()  # compile + schedule build
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        sweep()
        best = min(best, time.perf_counter() - t0)
    _sp.set_attr("best_ms", best * 1e3)
    return best * 1e3


def autotune(
    shape: Sequence[int],
    ranks: Sequence[int],
    nnz: int,
    *,
    dtype: str = "float32",
    precision: str = "fp32",
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
    table: Optional[TuningTable] = None,
    max_trials: int = 4,
    force: bool = False,
) -> BlockConfig:
    """Return the tuned :class:`BlockConfig` for this problem.

    Warm path: the fingerprint is already in the table — zero searches,
    zero trials (``COUNTERS['table_hits']`` bumps). Cold path: prune + rank
    candidates, time the top ``max_trials`` (default always among them),
    persist the winner atomically, return it."""
    import jax

    own_table = table is None
    if own_table:
        table = TuningTable()
    fp = fingerprint(
        shape, ranks, nnz, dtype=dtype, precision=precision, backend=backend
    )
    if not force:
        hit = table.get(fp)
        if hit is not None:
            _count("table_hits")
            _obs_event("autotune.table_hit", fingerprint=fp)
            return hit
    _count("searches")
    with _obs_span("autotune.search", fingerprint=fp,
                   max_trials=int(max_trials)) as _sp:
        cands = candidate_configs(shape, ranks, nnz, precision=precision)
        cands = cands[: max(1, int(max_trials))]
        best_cfg, best_ms = DEFAULT_CONFIG, float("inf")
        for cfg in cands:
            try:
                ms = trial_time_ms(
                    cfg, shape, ranks, nnz,
                    dtype=dtype, precision=precision, interpret=interpret,
                )
            except Exception:
                # off-TPU an untunable candidate loses in silence. On the
                # chip the VMEM model already pruned what cannot fit, so a
                # failed trial is a compiler refusal that must surface.
                if jax.default_backend() == "tpu":
                    raise
                continue
            if ms < best_ms:
                best_cfg, best_ms = cfg, ms
        _sp.set_attr("layout", best_cfg.layout)
        _sp.set_attr(
            "best_ms", None if best_ms == float("inf") else best_ms
        )
    table.put(
        fp, best_cfg,
        key={
            "shape": list(map(int, shape)), "ranks": list(map(int, ranks)),
            "nnz_bucket": nnz_bucket(nnz), "dtype": str(dtype),
            "precision": str(precision),
        },
        trial_ms=None if best_ms == float("inf") else best_ms,
    )
    table.save()
    return best_cfg
