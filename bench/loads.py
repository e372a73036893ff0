"""The load generator: one closed and one open loop, driven by a traffic
file's parameters.

A traffic file (``bench/traffic/<mix>.json``) names its ``loop``:

* ``closed``: one caller runs the system's request back to back until the
  window's length has passed; the last call runs to its end.
* ``open``: ``rate_per_s * seconds`` requests, due at Poisson arrival times
  and sent by a pool of ``CLIENTS`` threads whether or not earlier requests
  have finished; the pool is large enough that a send waits for a free
  thread only when that many requests are in flight. The arrival times are
  one fixed Poisson schedule, drawn from ``arrival_seed`` and rescaled to
  fill the window exactly, the same for every run: in a window of a few
  dozen requests the order of the gaps decides the bursts, and with it the
  tail (the run's seed still decides which request comes in which slot).
  Each request is timed from when it was due.

A system offers ``request(i)``, which runs request ``i`` to its answer and
returns it. Every record keeps when its request was due, sent and done.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, List, Optional

import numpy as np

CLIENTS = 64


@dataclasses.dataclass
class Record:
    index: int
    due: float  # perf_counter seconds
    sent: float
    done: float
    answer: Any = None
    error: Optional[BaseException] = None


@dataclasses.dataclass
class Window:
    t0: float  # perf_counter seconds at the window's start
    seconds: float  # the window's nominal length
    records: List[Record]

    @property
    def t1(self) -> float:
        """When the last request finished (at least the nominal end)."""
        return max([self.t0 + self.seconds] + [r.done for r in self.records])

    @property
    def failed(self) -> int:
        return sum(r.error is not None for r in self.records)

    def latencies_s(self) -> np.ndarray:
        """Due-to-done seconds of every request; a failed one counts as the
        window's length."""
        return np.asarray([self.seconds if r.error is not None else r.done - r.due
                           for r in self.records])


def offered(traffic: dict, seconds: float) -> int:
    """How many requests an open loop offers in the window."""
    return int(round(float(traffic["rate_per_s"]) * seconds))


def arrivals(n: int, seconds: float, gaps_seed: int = 0) -> np.ndarray:
    """Offsets of ``n`` Poisson arrivals: ``n + 1`` exponential gaps from
    ``gaps_seed``, rescaled so that the last gap ends at the window's end."""
    t = np.cumsum(np.random.default_rng(gaps_seed).exponential(1.0, n + 1))
    return t[:n] / t[n] * seconds


def closed(request: Callable[[int], Any], seconds: float) -> Window:
    records = []
    t0 = time.perf_counter()
    end = t0 + seconds
    i = 0
    while True:
        start = time.perf_counter()
        if start >= end and records:
            break
        try:
            records.append(Record(i, start, start, 0.0, answer=request(i)))
        except Exception as e:  # a failed call is counted, not fatal
            records.append(Record(i, start, start, 0.0, error=e))
        records[-1].done = time.perf_counter()
        i += 1
    return Window(t0, seconds, records)


def open_loop(request: Callable[[int], Any], seconds: float, offsets: np.ndarray,
              clients: int = CLIENTS) -> Window:
    records = [Record(i, 0.0, 0.0, 0.0) for i in range(len(offsets))]
    lock = threading.Lock()
    nxt = [0]
    t0 = time.perf_counter() + 0.05  # every client is waiting when it opens

    def client() -> None:
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            if i >= len(records):
                return
            rec = records[i]
            rec.due = t0 + offsets[i]
            wait = rec.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            rec.sent = time.perf_counter()
            try:
                rec.answer = request(i)
            except Exception as e:  # a failed request is counted, not fatal
                rec.error = e
            rec.done = time.perf_counter()

    threads = [threading.Thread(target=client, name=f"bench-client-{k}", daemon=True)
               for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 600)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("open loop: a client did not finish within 10 minutes "
                           "of the window's end")
    return Window(t0, seconds, records)


def run(traffic: dict, request: Callable[[int], Any], seconds: float) -> Window:
    loop = traffic["loop"]
    if loop == "closed":
        return closed(request, seconds)
    if loop == "open":
        offsets = arrivals(offered(traffic, seconds), seconds,
                           int(traffic.get("arrival_seed", 0)))
        return open_loop(request, seconds, offsets)
    raise ValueError(f"unknown loop {loop!r} in traffic file")
