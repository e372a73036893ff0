"""Contract lints over the optimized HLO (and closed jaxpr) of a compiled
Tucker program.

These are the data-movement invariants the paper's hybrid split lives on —
the TTM/Kron hot loop never leaves the accelerator, donated carries alias
in place, sharded sweeps psum exactly once per mode — checked statically on
``compiled.as_text()`` via the :mod:`repro.utils.hlo` parser, so every
(engine x shard x snapshot x precision) cell can be audited
without executing anything.
"""
from __future__ import annotations

from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from repro.analysis.findings import Finding
from repro.utils.hlo import (
    Computation,
    computation_multipliers,
    is_host_transfer,
    iter_ops,
    parse_input_output_aliases,
    shape_bytes,
    split_computations,
)

_COLLECTIVE_OPCODES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
# opcodes that ACCUMULATE (order-sensitive reductions): under
# precision="bf16_fp32acc" these must produce f32 — bf16 operands feeding
# them are the whole point of the mode.
_ACCUM_OPCODES = ("dot", "convolution", "scatter", "reduce", "reduce-window")


def _parsed(text: str) -> Tuple[Dict[str, Computation], Dict[str, float]]:
    comps = split_computations(text)
    return comps, computation_multipliers(comps)


# -- transfer-lint ----------------------------------------------------------


def transfer_lint(text: str, *, where: str = "program") -> List[Finding]:
    """No device->host transfers or host callbacks anywhere in the compiled
    sweep program. The one fit-history readback happens AFTER dispatch (a
    ``device_get`` on the result), so any in-program transfer — and
    especially one inside the trip-multiplied sweep loop — breaks the
    paper's single-transfer contract."""
    comps, mult = _parsed(text)
    findings: List[Finding] = []
    for name, comp in comps.items():
        m = mult.get(name, 0.0)
        if m <= 0:
            continue
        for op in iter_ops(comp):
            if is_host_transfer(op):
                runs = f" (runs x{int(m)} per dispatch)" if m > 1 else ""
                findings.append(
                    Finding(
                        "transfer", "error", f"{where}/{name}",
                        f"host transfer '{op.opcode}' ({op.name}) inside "
                        f"the compiled sweep program{runs}; the only "
                        "permitted device->host traffic is the fit-history "
                        "readback after dispatch",
                    )
                )
    return findings


_CALLBACK_PRIMS = ("callback", "infeed", "outfeed", "host")


def transfer_lint_jaxpr(closed_jaxpr: Any, *, where: str = "program") -> List[Finding]:
    """The jaxpr-level twin of :func:`transfer_lint`: walk every equation of
    the closed jaxpr (recursing into call/scan/cond sub-jaxprs) and flag
    callback/infeed/outfeed primitives before XLA ever sees them."""
    findings: List[Finding] = []
    seen: set = set()

    def walk(jaxpr: Any, path: str) -> None:
        if id(jaxpr) in seen:
            return
        seen.add(id(jaxpr))
        for eqn in jaxpr.eqns:
            pname = eqn.primitive.name
            if any(marker in pname for marker in _CALLBACK_PRIMS):
                findings.append(
                    Finding(
                        "transfer", "error", f"{where}/{path}",
                        f"host-callback primitive '{pname}' in the traced "
                        "jaxpr; the sweep loop must stay on device",
                    )
                )
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    walk(sub, f"{path}/{pname}")

    def _subjaxprs(v: Any) -> Iterator[Any]:
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            inner = getattr(item, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner  # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item  # raw Jaxpr

    walk(closed_jaxpr.jaxpr, "jaxpr")
    return findings


# -- donation-lint ----------------------------------------------------------


def donation_lint(
    text: str, *, donated_params: Sequence[int], where: str = "program"
) -> List[Finding]:
    """Every donated input buffer must actually alias an output in the
    compiled executable (the module-header ``input_output_alias`` map). A
    silently dropped donation keeps both the input and output buffers live —
    doubling HBM residency of that factor for the whole sweep loop."""
    aliases = parse_input_output_aliases(text)
    aliased_params = {param for (param, _idx, _kind) in aliases.values()}
    findings: List[Finding] = []
    for p in donated_params:
        if p not in aliased_params:
            findings.append(
                Finding(
                    "donation", "error", f"{where}/param{p}",
                    f"donated input parameter {p} is not aliased to any "
                    "output in the executable — the donation was dropped "
                    "and the buffer is double-resident for the dispatch",
                )
            )
    return findings


# -- precision-lint ---------------------------------------------------------


def precision_lint(
    text: str, *, precision: str, where: str = "program"
) -> List[Finding]:
    """Under ``precision="bf16_fp32acc"`` the accumulator paths must stay in
    f32: any dot/scatter/reduce producing a bf16 result means a downcast
    crept onto an accumulation (exactly the error the mode's name forbids),
    and a bf16 program output leaks reduced precision to the caller. Under
    ``precision="fp32"`` the program must contain no bf16 values at all."""
    comps, mult = _parsed(text)
    findings: List[Finding] = []
    for name, comp in comps.items():
        if mult.get(name, 0.0) <= 0:
            continue
        bf16_ops = 0
        for op in iter_ops(comp):
            if "bf16[" not in op.result_type:
                continue
            if precision == "fp32":
                bf16_ops += 1
            elif op.opcode in _ACCUM_OPCODES:
                findings.append(
                    Finding(
                        "precision", "error", f"{where}/{name}",
                        f"accumulating op '{op.opcode}' ({op.name}) "
                        f"produces {op.result_type.split('{')[0].strip()} "
                        "under bf16_fp32acc — accumulators must stay f32",
                    )
                )
        if precision == "fp32" and bf16_ops:
            findings.append(
                Finding(
                    "precision", "error", f"{where}/{name}",
                    f"{bf16_ops} bf16-valued op(s) in an fp32-precision "
                    "program — an unintended downcast is losing mantissa",
                )
            )
    if precision != "fp32":
        # the entry ROOT (the program's outputs) must stay full precision.
        for name, comp in comps.items():
            if not name.startswith("main"):
                continue
            for op in iter_ops(comp):
                if op.line.lstrip().startswith("ROOT") and (
                    "bf16[" in op.result_type
                ):
                    findings.append(
                        Finding(
                            "precision", "error", f"{where}/{name}",
                            "program output contains bf16 — results must "
                            "be returned at full working precision",
                        )
                    )
    return findings


# -- collective-lint --------------------------------------------------------


def collective_lint(
    text: str,
    *,
    sharded: bool,
    shape: Optional[Sequence[int]] = None,
    ranks: Optional[Sequence[int]] = None,
    n_sweeps: Optional[int] = None,
    itemsize: int = 4,
    where: str = "program",
) -> List[Finding]:
    """Sharded programs perform EXACTLY one psum (all-reduce) per mode per
    sweep, each moving the partial mode unfolding ``I_n x prod(other
    ranks)`` — the byte oracle of ``core.distributed.psum_bytes_per_sweep``.
    Unsharded programs must contain no collectives at all. The count is a
    static upper bound: a cond-masked early-exit sweep still *contains* its
    psums, it just may not run them."""
    comps, mult = _parsed(text)
    # (opcode, operand bytes, computation, multiplier) of every reachable
    # collective. all-reduce results are operand-shaped, so result bytes ==
    # payload bytes.
    colls: List[Tuple[str, int, str, float]] = []
    for name, comp in comps.items():
        m = mult.get(name, 0.0)
        if m <= 0:
            continue
        for op in iter_ops(comp):
            if op.opcode in _COLLECTIVE_OPCODES:
                colls.append((op.opcode, shape_bytes(op.result_type), name, m))

    findings: List[Finding] = []
    if not sharded:
        for kind, nbytes, name, _m in colls:
            findings.append(
                Finding(
                    "collective", "error", f"{where}/{name}",
                    f"unexpected collective '{kind}' ({nbytes} bytes) in an "
                    "unsharded program",
                )
            )
        return findings

    assert shape is not None and ranks is not None and n_sweeps is not None
    ndim = len(shape)
    # per-mode psum payload: the partial unfolding Y_(n) is (I_n, K_n) with
    # K_n = prod of the other modes' ranks. The total per sweep is the
    # distributed module's published oracle.
    import numpy as np

    from repro.core.distributed import psum_bytes_per_sweep

    expected_mode_bytes = set()
    for n in range(ndim):
        k = 1
        for t, r in enumerate(ranks):
            if t != n:
                k *= int(r)
        expected_mode_bytes.add(int(shape[n]) * k * itemsize)
    expected_total = int(
        psum_bytes_per_sweep(shape, ranks, dtype=np.dtype(f"f{itemsize}"))
    )

    for kind, nbytes, name, _m in colls:
        if kind != "all-reduce":
            findings.append(
                Finding(
                    "collective", "error", f"{where}/{name}",
                    f"collective '{kind}' in the sharded sweep program — "
                    "the contract allows only the per-mode psum "
                    "(all-reduce)",
                )
            )
        elif nbytes not in expected_mode_bytes:
            findings.append(
                Finding(
                    "collective", "error", f"{where}/{name}",
                    f"all-reduce moves {nbytes} bytes, which is no mode's "
                    f"partial unfolding (expected one of "
                    f"{sorted(expected_mode_bytes)})",
                )
            )

    n_exec = sum(m for kind, _b, _n, m in colls if kind == "all-reduce")
    want = ndim * n_sweeps
    if round(n_exec) != want:
        findings.append(
            Finding(
                "collective", "error", f"{where}",
                f"{round(n_exec)} psum executions per dispatch, expected "
                f"exactly {want} (one per mode x {n_sweeps} sweeps)",
            )
        )
    bytes_exec = sum(
        b * m for kind, b, _n, m in colls if kind == "all-reduce"
    )
    want_bytes = expected_total * n_sweeps
    if round(bytes_exec) != want_bytes:
        findings.append(
            Finding(
                "collective", "error", f"{where}",
                f"psum moves {round(bytes_exec)} bytes per dispatch, but "
                f"psum_bytes_per_sweep predicts {want_bytes} "
                f"({expected_total} x {n_sweeps} sweeps)",
            )
        )
    return findings
