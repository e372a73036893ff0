"""The config-matrix sweep behind ``python -m repro.analysis``.

One :class:`Cell` = one (engine x shard x snapshot x precision) point: a
spec (plus optional prebuilt engine), lowered through
``TuckerPlan.lower_hlo`` and pushed through every applicable contract lint.
``run_matrix`` sweeps the default matrix (or a chosen subset), applies the
committed baseline, and returns a report the CLI/CI gate turns into an
exit code. Nothing here EXECUTES a program — lowering and host-side
schedule audits only — so the sweep is safe on any machine; sharded cells
self-skip below 2 attached devices (CI forces
``XLA_FLAGS=--xla_force_host_platform_device_count``).
"""
from __future__ import annotations

import dataclasses
import os
import tempfile
from typing import Any, List, Optional, Sequence

from repro.analysis.findings import Baseline, Finding
from repro.analysis.hlo_lints import (
    collective_lint,
    donation_lint,
    precision_lint,
    transfer_lint,
    transfer_lint_jaxpr,
)
from repro.analysis.schedule_lints import scatter_race_lint
from repro.analysis.spec_lints import retrace_hazard_lint


@dataclasses.dataclass
class Cell:
    """One point of the lint matrix. ``batch > 0`` lints the vmapped batched
    program (``TuckerPlan.lower_batch_hlo`` over that many member tensors)
    instead of the per-tensor pipeline."""

    name: str
    spec: object  # TuckerSpec
    engine: Optional[object] = None  # prebuilt SweepEngine override
    min_devices: int = 1
    batch: int = 0


@dataclasses.dataclass
class CellReport:
    name: str
    findings: List[Finding]
    suppressed: int = 0
    skipped: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.skipped is not None or not self.findings

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "findings": [f.to_json() for f in self.findings],
            "suppressed": self.suppressed,
            "skipped": self.skipped,
        }


@dataclasses.dataclass
class MatrixReport:
    cells: List[CellReport]

    @property
    def findings(self) -> List[Finding]:
        return [f for c in self.cells for f in c.findings]

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_json(self) -> dict:
        return {
            "ok": self.ok,
            "n_findings": len(self.findings),
            "cells": [c.to_json() for c in self.cells],
        }


def default_matrix(snapshot_dir: Optional[str] = None) -> List[Cell]:
    """The committed lint matrix: both engines, both precisions, Kron reuse,
    the fused megakernel, the snapshot segment program, and (given >= 2
    devices) the sharded program in plain and resumable form. Small fixed
    shapes — the contracts are structural, not scale-dependent."""
    from repro.core.engine import make_engine
    from repro.tucker.spec import ShardSpec, SnapshotSpec, TuckerSpec

    snap_dir = snapshot_dir or os.path.join(
        tempfile.gettempdir(), "repro-analysis-snap"
    )
    base = dict(
        shape=(12, 10, 8), ranks=(3, 3, 2), method="gram", n_iter=3, tol=1e-7
    )
    snap = SnapshotSpec(every_n_sweeps=2, directory=snap_dir)
    cells = [
        Cell("xla/scan/fp32", TuckerSpec(engine="xla", **base)),
        Cell(
            "xla/scan/householder",
            TuckerSpec(engine="xla", **{**base, "method": "householder"}),
        ),
        Cell(
            "xla/scan/kron-reuse",
            TuckerSpec(engine="xla", use_kron_reuse=True, **base),
        ),
        Cell(
            "xla/scan/bf16acc",
            TuckerSpec(engine="xla", precision="bf16_fp32acc", **base),
        ),
        Cell("pallas/scan/fp32", TuckerSpec(engine="pallas", **base)),
        Cell(
            "pallas/scan/bf16acc",
            TuckerSpec(engine="pallas", precision="bf16_fp32acc", **base),
        ),
        Cell(
            "pallas/scan/fused",
            TuckerSpec(engine="pallas", **base),
            engine=make_engine("pallas", fuse_core=True),
        ),
        Cell(
            "xla/segment/fp32", TuckerSpec(engine="xla", snapshot=snap, **base)
        ),
        Cell(
            "xla/batched/fp32", TuckerSpec(engine="xla", **base), batch=4
        ),
        Cell(
            "sharded/scan/fp32",
            TuckerSpec(
                engine="xla", shard=ShardSpec(num_devices=2), **base
            ),
            min_devices=2,
        ),
        Cell(
            "sharded/segment/fp32",
            TuckerSpec(
                engine="xla", shard=ShardSpec(num_devices=2),
                snapshot=snap, **base,
            ),
            min_devices=2,
        ),
    ]
    return cells


def lint_plan(plan: Any, x: Any, *, baseline: Optional[Baseline] = None,
              where: Optional[str] = None) -> List[Finding]:
    """Every applicable contract lint against one plan's compiled program.
    This is the engine behind ``TuckerPlan.lint``."""
    spec = plan.spec
    text, meta = plan.lower_hlo(x)
    where = where or f"{meta['engine']}/{meta['kind']}/{meta['precision']}"
    findings = transfer_lint(text, where=where)
    findings += donation_lint(
        text, donated_params=meta["donated_params"], where=where
    )
    findings += precision_lint(text, precision=meta["precision"], where=where)
    itemsize = {"float64": 8, "bfloat16": 2, "float16": 2}.get(
        meta["working_dtype"], 4
    )
    findings += collective_lint(
        text,
        sharded=meta["sharded"],
        shape=spec.shape,
        ranks=spec.ranks,
        n_sweeps=meta["n_sweeps"],
        itemsize=itemsize,
        where=where,
    )
    if plan.engine is not None and plan.engine.name == "pallas":
        coo = plan._check_sparse_input(x)
        findings += scatter_race_lint(
            plan.engine, coo, ranks=spec.ranks,
            precision=meta["precision"], where=where,
        )
    if not meta["sharded"]:
        findings += transfer_lint_jaxpr(_closed_jaxpr(plan, x), where=where)
    if baseline is not None:
        findings, _suppressed = baseline.filter(findings)
    return findings


def lint_batch_plan(
    plan: Any, coos: Sequence[Any], *, keys: Any = None,
    baseline: Optional[Baseline] = None, where: Optional[str] = None,
) -> List[Finding]:
    """Contract lints against the vmapped batched program — the ONE XLA
    dispatch ``TuckerPlan.batch`` (and every serving flush) runs for k
    member tensors. The engine behind ``TuckerPlan.lint_batch``.

    The donation contract here is the INVERSE of the per-tensor pipelines':
    the batched program must donate nothing — member tensors and PRNG keys
    are caller-owned (a service flush reuses them for retries and metrics),
    so any input/output alias in the executable means a caller buffer would
    be consumed by the dispatch.
    """
    text, meta = plan.lower_batch_hlo(coos, keys=keys)
    where = where or f"{meta['engine']}/{meta['kind']}/{meta['precision']}"
    findings = transfer_lint(text, where=where)
    findings += donation_lint(
        text, donated_params=meta["donated_params"], where=where
    )
    from repro.utils.hlo import parse_input_output_aliases

    for (param, _idx, kind) in parse_input_output_aliases(text).values():
        findings.append(
            Finding(
                "donation", "error", f"{where}/param{param}",
                f"batched program aliases input parameter {param} to an "
                f"output ({kind}) — the flush dispatch donates nothing, so "
                "a caller-owned member/key buffer would be consumed",
            )
        )
    findings += precision_lint(text, precision=meta["precision"], where=where)
    findings += transfer_lint_jaxpr(
        _batched_closed_jaxpr(plan, coos, keys), where=where
    )
    if baseline is not None:
        findings, _suppressed = baseline.filter(findings)
    return findings


def _batched_closed_jaxpr(plan: Any, coos: Sequence[Any],
                          keys: Any = None) -> Any:
    """The closed jaxpr of the batched program (pre-XLA twin of the HLO
    pass, same as ``_closed_jaxpr`` for the per-tensor pipelines)."""
    import jax
    import jax.numpy as jnp

    from repro.core import hooi as _hooi
    from repro.sparse.layout import pad_coo_batch
    from repro.tucker.planning import _stack_keys

    spec = plan.spec
    coos = [plan._check_sparse_input(c) for c in coos]
    if keys is None:
        keys = [None] * len(coos)
    idx, val = pad_coo_batch(coos)
    jkeys = _stack_keys(list(keys))

    def f(indices: Any, values: Any, keys_: Any, tol: Any) -> Any:
        return _hooi._batched_scan_sweeps.__wrapped__(
            indices, values, keys_, tol,
            shape=spec.shape, ranks=spec.ranks, method=spec.method,
            n_iter=spec.n_iter, dtype=spec.resolved_dtype(),
        )

    return jax.make_jaxpr(f)(idx, val, jkeys, jnp.float32(spec.tol))


def _closed_jaxpr(plan: Any, x: Any) -> Any:
    """The closed jaxpr of the plan's (unsharded) program — the pre-XLA
    view transfer-lint also audits, so a host callback is caught even if a
    backend lowers it to something the HLO pass doesn't recognize."""
    import jax
    import jax.numpy as jnp

    from repro.core import hooi as _hooi

    spec, eng = plan.spec, plan.engine
    coo = plan._check_sparse_input(x)
    factors = plan._init_factors(None, None)
    scheds = tuple(eng.device_schedule(coo, m) for m in range(coo.ndim))
    common = dict(
        shape=spec.shape, ranks=spec.ranks, method=spec.method,
        engine_name=eng.name,
        interpret=eng.resolved_interpret() if eng.name == "pallas" else False,
        use_reuse=eng.use_kron_reuse and eng.name == "xla",
        precision=eng.precision, bl=eng.bl, bk=eng.bk,
        fuse_core=eng.fuse_core and eng.name == "pallas",
    )

    if spec.snapshot is not None:
        core = jnp.zeros(
            tuple(spec.ranks),
            dtype=jnp.promote_types(coo.values.dtype, jnp.float32),
        )

        def f(indices: Any, values: Any, factors_: Any, xnorm2: Any, tol: Any) -> Any:
            return _hooi._segment_scan_sweeps_impl(
                indices, values, factors_, core, xnorm2, tol,
                jnp.float32(jnp.inf), jnp.asarray(False), jnp.int32(0),
                jnp.int32(spec.n_iter), scheds,
                segment_len=spec.snapshot.segment_len, **common,
            )
    else:

        def f(indices: Any, values: Any, factors_: Any, xnorm2: Any, tol: Any) -> Any:
            return _hooi._scan_sweeps_impl(
                indices, values, factors_, xnorm2, tol, scheds,
                n_iter=spec.n_iter, **common,
            )

    return jax.make_jaxpr(f)(
        coo.indices, coo.values, tuple(factors),
        jnp.square(coo.norm()), jnp.float32(spec.tol),
    )


def run_matrix(
    cells: Optional[Sequence[Cell]] = None,
    *,
    baseline: Optional[Baseline] = None,
    seed: int = 0,
    density: float = 0.08,
) -> MatrixReport:
    """Sweep the lint matrix. Includes one global retrace-hazard audit of
    the plan-cache key classes alongside the per-cell program lints."""
    import jax

    from repro.sparse.generators import random_sparse_tensor
    from repro.tucker.planning import TuckerPlan

    if cells is None:
        cells = default_matrix()
    n_dev = len(jax.devices())
    reports: List[CellReport] = []

    spec_findings = retrace_hazard_lint()
    suppressed = 0
    if baseline is not None:
        spec_findings, dropped = baseline.filter(spec_findings)
        suppressed = len(dropped)
    reports.append(
        CellReport("plan-cache", spec_findings, suppressed=suppressed)
    )

    for cell in cells:
        if n_dev < cell.min_devices:
            reports.append(
                CellReport(
                    cell.name, [],
                    skipped=(
                        f"needs {cell.min_devices} devices, have {n_dev} "
                        "(set XLA_FLAGS=--xla_force_host_platform_"
                        f"device_count={cell.min_devices})"
                    ),
                )
            )
            continue
        plan_obj = TuckerPlan(cell.spec, engine=cell.engine)
        if cell.batch > 0:
            # distinct nnz per member, so the lint sees the padded batch
            # exactly as a mixed-nnz serving flush would dispatch it
            coos = [
                random_sparse_tensor(
                    cell.spec.shape, density * (1.0 + 0.25 * i),
                    seed=seed + i,
                )
                for i in range(cell.batch)
            ]
            findings = lint_batch_plan(plan_obj, coos, where=cell.name)
        else:
            coo = random_sparse_tensor(cell.spec.shape, density, seed=seed)
            findings = lint_plan(plan_obj, coo, where=cell.name)
        suppressed = 0
        if baseline is not None:
            findings, dropped = baseline.filter(findings)
            suppressed = len(dropped)
        reports.append(CellReport(cell.name, findings, suppressed=suppressed))
    return MatrixReport(reports)


def default_baseline_path() -> str:
    """The committed suppression file: ``analysis-baseline.json`` in the
    current directory if present, else at the repo root next to this
    package's ``src/`` tree."""
    local = os.path.join(os.getcwd(), "analysis-baseline.json")
    if os.path.exists(local):
        return local
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.normpath(
        os.path.join(here, "..", "..", "..", "analysis-baseline.json")
    )
