"""Reduction of a profiler trace (``.xplane.pb``) of the window to what the
metrics read: per chip the device operations and the union of their busy
intervals, inside the window that the harness marks with the host
annotation ``bench.window``.

* busy time: the union of the intervals of the operations on a chip's
  ``XLA Ops`` line, clipped to the window, leaving out control flow (a while
  loop or a conditional spans the operations it runs); idle share is one
  minus busy over the window, averaged over the chips;
* operation time: the summed durations of the operations whose HLO text
  matches;
* exposed time: the part of the matched operations' intervals that no other
  operation on that chip covers (a collective that nothing overlaps);
* breakdown: the device operations that took most time, and the longest
  idle gaps named by the program span (``repro.obs``) that was open on the
  host in the middle of each, mapped onto the trace's clock through the
  annotation's start.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import xplane

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
_CONTAINER = re.compile(r"(?<![-\w])(while|conditional|call)\(")


def op_name(hlo: str) -> str:
    """The operation's name from the event's HLO text: ``%fusion.12 = f32[..]
    fusion(...)`` gives ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_label(hlo: str) -> str:
    """Name and result shape, ``fusion.12 f32[4208640]``: names repeat across
    the computations of one program, shapes tell them apart."""
    m = re.match(r"\S+ = ([a-z0-9]+\[[0-9,]*\])", hlo)
    return f"{op_name(hlo)} {m.group(1)}" if m else op_name(hlo)


def is_container(hlo: str) -> bool:
    return bool(_CONTAINER.search(hlo.split(" = ", 1)[-1]))


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def union(iv: np.ndarray) -> np.ndarray:
    """Merge ``(n, 2)`` intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), dtype=bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    last = np.r_[np.flatnonzero(new)[1:] - 1, len(iv) - 1]
    return np.stack([iv[new, 0], ends[last]], axis=1)


def measure(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


def intersect(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the overlap of two disjoint sorted interval sets."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


@dataclasses.dataclass
class Device:
    names: Dict[int, str]  # metadata id -> HLO text
    ids: np.ndarray  # (n,) of the operations in the window
    spans: np.ndarray  # (n, 2) ns, clipped to the window
    busy: np.ndarray  # union of spans

    def mask(self, match: Callable[[str], bool]) -> np.ndarray:
        hit = [k for k, v in self.names.items() if match(v)]
        return np.isin(self.ids, hit)


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]  # ns on the trace's clock
    devices: List[Device]
    host: List[Tuple[float, float, str]]  # program spans on the trace's clock
    spans: Optional[list] = None  # the program's spans as recorded

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return float(np.mean([measure(d.busy) for d in self.devices])) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, match: Callable[[str], bool], per_device: bool = False):
        per = []
        for d in self.devices:
            m = d.mask(match)
            per.append(float(np.sum(d.spans[m, 1] - d.spans[m, 0])) / 1e9)
        return per if per_device else sum(per)

    def exposed_seconds(self, match: Callable[[str], bool]) -> List[float]:
        out = []
        for d in self.devices:
            m = d.mask(match)
            mine, rest = union(d.spans[m]), union(d.spans[~m])
            out.append((measure(mine) - intersect(mine, rest)) / 1e9)
        return out

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        tot: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            ids, inv = np.unique(d.ids, return_inverse=True)
            sums = np.bincount(inv, weights=d.spans[:, 1] - d.spans[:, 0],
                               minlength=len(ids))
            for k, v in zip(ids, sums):
                tot[op_label(d.names[int(k)])] += float(v) / 1e9 / self.n_devices
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for d in self.devices:
            edges = np.concatenate([[self.window[0]], d.busy.ravel(), [self.window[1]]])
            gaps += [(e - s, (s + e) / 2) for s, e in edges.reshape(-1, 2) if e > s]
        gaps.sort(key=lambda g: -g[0])
        return {"device_ops": [[n, v] for n, v in ops],
                "idle_gaps": [[self._host_at(mid), float(g) / 1e9] for g, mid in gaps[:top]]}

    def _host_at(self, t: float) -> str:
        """The innermost program span open on the host at ``t``."""
        best: Optional[Tuple[float, float, str]] = None
        for s, e, name in self.host:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best is not None else "harness"


def reduce(path: str, n_devices: int, spans: Optional[Sequence] = None,
           anchor: Optional[float] = None) -> Reduced:
    """Reduce the trace at ``path`` to the first ``n_devices`` chips. The
    host annotation ``bench.window`` bounds the window; ``spans`` (program
    spans on ``time.perf_counter``) are placed on the trace's clock by
    matching ``anchor``, the perf_counter time at the annotation's start."""
    planes = xplane.read(path)
    window = None
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        wid = [k for k, v in p.names.items() if v == WINDOW]
        for ln in p.lines:
            hit = np.flatnonzero(np.isin(ln.ids, wid))
            if len(hit):
                window = (float(ln.start_ns[hit[-1]]), float(ln.end_ns[hit[-1]]))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    chips = sorted((p for p in planes
                    if p.name.startswith("/device:") and p.line(OPS_LINE) is not None),
                   key=lambda p: p.name)[:n_devices]
    if not chips:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line in {path}")
    devices = []
    for p in chips:
        ln = p.line(OPS_LINE)
        ops = [k for k, v in p.names.items() if not is_container(v)]
        s = np.maximum(ln.start_ns, window[0])
        e = np.minimum(ln.end_ns, window[1])
        keep = (e > s) & np.isin(ln.ids, ops)
        iv = np.stack([s[keep], e[keep]], axis=1)
        devices.append(Device(p.names, ln.ids[keep], iv, union(iv)))
    host = []
    if spans is not None and anchor is not None:
        off = window[0] - anchor * 1e9
        host = [(sp.t0 * 1e9 + off, sp.t1 * 1e9 + off, sp.name) for sp in spans]
    return Reduced(window, devices, host)
