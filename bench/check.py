"""The numbers that decide ``correct``, and their comparison with limits.

Each number compares what the timed path returned with the plain reference
(``bench/reference.py``); the largest reading over the answers checked is
held against the configuration's limit for it:

* ``core_gap``: ``||G - G_ref|| / ||G_ref||``, where ``G_ref`` is the core
  the reference computes from the answer's own factors. It sees the last
  mode's Kronecker accumulation, the core TTM, the cross-chip sum and which
  tensor the answer belongs to, and it is the number that a lower compute
  precision moves.
* ``fit_gap``: the largest difference between the answer's fit history and
  the reference's, run from the same start: every sweep, every mode, the
  factor updates included.

The factors' subspace is not compared: on the planted surrogates it agrees
with the reference to about 1e-12 at any precision, so a lower precision
cannot be told apart by it.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def fit_gap(hist: Sequence[float], ref: Sequence[float]) -> float:
    h = np.asarray(hist, np.float64)
    r = np.asarray(ref, np.float64)
    if h.shape != r.shape:
        return float("inf")
    return float(np.max(np.abs(h - r)))


def core_gap(core, ref: np.ndarray) -> float:
    c = np.asarray(core, np.float64)
    if c.shape != ref.shape:
        return float("inf")
    return float(np.linalg.norm(c - ref) / np.linalg.norm(ref))


def verdict(readings: Dict[str, float],
            limits: Dict[str, float]) -> Tuple[bool, List[Tuple[str, float, float]]]:
    """``(correct, [(name, reading, limit), ...])``: correct when every
    limited number was read and is finite and within its limit."""
    rows = [(name, float(readings.get(name, float("nan"))), float(lim))
            for name, lim in sorted(limits.items())]
    ok = bool(rows) and all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
