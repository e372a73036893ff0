"""How the benchmark drives the system under test, by the configuration's
``entry``:

* ``plan``: ``tucker.plan(spec)(coo)`` on one seeded tensor, the call a
  re-fitting job makes; with ``chips`` above 1 the spec shards the nonzeros
  over that many chips.
* ``service``: ``TuckerService.submit`` then ``ticket.result()`` for a
  stream of seeded requests from several tenants.

Each system builds its inputs from the seed and warms what it can in
``setup``, answers ``request(i)``, and after the window
frees the program's state and compares the answers with the reference in
``check``.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
from typing import Dict, List

import jax
import numpy as np

from bench import check, reference, surrogate


def configure(config: dict) -> None:
    """Process-wide JAX settings the configuration states: the matmul
    precision, which every program compiled afterwards follows."""
    if config.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision", config["matmul_precision"])


def _key(rng: np.random.Generator):
    return jax.random.PRNGKey(int(rng.integers(2**31)))


def _host(res) -> dict:
    return {
        "core": np.asarray(res.core),
        "factors": [np.asarray(f) for f in res.factors],
        "hist": np.asarray(res.fit_history),
    }


def _digest(ans: dict) -> bytes:
    h = hashlib.sha1(ans["hist"].tobytes())
    h.update(ans["core"].tobytes())
    for f in ans["factors"]:
        h.update(f.tobytes())
    return h.digest()


def memory_peak(devices) -> int:
    """Peak bytes on the fullest of ``devices``. This runtime leaves program
    temp out of ``peak_bytes_in_use`` and counts it in
    ``peak_bytes_reserved``, so the larger of the two is read."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_reserved", 0)),
                   int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _free() -> None:
    from repro import tucker

    tucker.clear_plan_cache()
    gc.collect()


def _spec(config: dict, ranks, chips: int):
    from repro import tucker

    shard = tucker.ShardSpec(num_devices=chips) if chips > 1 else None
    return tucker.TuckerSpec(
        shape=tuple(config["shape"]), ranks=tuple(ranks),
        n_iter=int(config["n_iter"]), precision=config["precision"],
        shard=shard, **config.get("spec", {}))


def _readings(answers: List[dict], x: reference.Tensor, ranks, n_iter: int,
              key) -> Dict[str, float]:
    """The largest of each compared number over ``answers`` of one tensor."""
    _, _, hist_ref = reference.hooi(x, ranks, n_iter, key)
    out = {"core_gap": 0.0, "fit_gap": 0.0}
    for ans in answers:
        g = reference.core_given(x, ans["factors"])
        out["core_gap"] = max(out["core_gap"], check.core_gap(ans["core"], g))
        out["fit_gap"] = max(out["fit_gap"], check.fit_gap(ans["hist"], hist_ref))
    return out


class PlanSystem:
    def __init__(self, config: dict, traffic: dict, seed: int, seconds: float) -> None:
        self.config = config
        self.rng = np.random.default_rng(seed)
        self.chips = int(config["chips"])
        self.devices = jax.devices()[: self.chips]

    def setup(self) -> None:
        from repro import tucker
        from repro.core.coo import SparseCOO

        c = self.config
        self.shape = tuple(c["shape"])
        pat = surrogate.pattern(self.shape, int(c["nnz"]), int(c["components"]),
                                np.random.default_rng(int(c["pattern_seed"])))
        self.indices, self.values = pat.indices, pat.values(self.rng)
        self.key = _key(self.rng)
        self.coo = SparseCOO.from_parts(self.indices, self.values, self.shape)
        self.plan = tucker.plan(_spec(c, c["ranks"], self.chips))
        self.request(0)  # schedules, compile, first run

    def request(self, i: int):
        res = self.plan(self.coo, key=self.key)
        jax.block_until_ready((res.core, res.factors))
        return res

    def memory_peak(self) -> int:
        return memory_peak(self.devices)

    def check(self, window) -> Dict[str, float]:
        answers = {}
        for r in window.records:
            if r.error is None:
                ans = _host(r.answer)
                answers.setdefault(_digest(ans), ans)
            r.answer = None
        del self.plan, self.coo
        _free()
        c = self.config
        x = reference.Tensor(self.indices, self.values, self.shape,
                             int(c["reference_block"]), device=self.devices[0])
        return _readings(list(answers.values()), x, c["ranks"], int(c["n_iter"]), self.key)


@dataclasses.dataclass
class _Request:
    tenant: int
    pattern: surrogate.Pattern
    values: np.ndarray
    key: object


class ServiceSystem:
    """Each request is one day of the source's ``days``: day ``d`` has a
    sparsity pattern of its own, drawn from ``[pattern_seed, d]``, and goes to
    tenant ``d % tenants``. A window of ``n`` requests serves days ``0 .. n-1``
    (around again past ``days``) in the order the run's seed draws, with values
    and starting factors from the seed, so every seed does the same work. The
    warm-up serves one more day per tenant that no window serves."""

    def __init__(self, config: dict, traffic: dict, seed: int, seconds: float) -> None:
        from bench import loads

        self.config = config
        self.rng = np.random.default_rng(seed)
        self.devices = jax.devices()[: int(config["chips"])]
        self.n = loads.offered(traffic, seconds)

    def day(self, d: int) -> surrogate.Pattern:
        c = self.config
        lo, hi = c["nnz_per_request"]
        fixed = np.random.default_rng([int(c["pattern_seed"]), d])
        return surrogate.pattern(tuple(c["shape"]), int(fixed.integers(lo, hi + 1)),
                                 int(c["components"]), fixed)

    def window_requests(self, rng: np.random.Generator) -> List[_Request]:
        c = self.config
        tenants, days = len(c["tenant_ranks"]), int(c["days"])
        served = [self.day(j % days) for j in range(self.n)]
        out = []
        for j in rng.permutation(self.n):
            p = served[j]
            out.append(_Request(int(j) % tenants, p, p.values(rng), _key(rng)))
        return out

    def setup(self) -> None:
        from repro.serve import ServiceConfig, TuckerService

        c, rng = self.config, self.rng
        self.specs = [_spec(c, r, 1) for r in c["tenant_ranks"]]
        self.requests = self.window_requests(rng)
        sample = rng.choice(self.n, size=min(self.n, int(c["check_requests"])),
                            replace=False)
        largest = max(range(self.n), key=lambda i: self.requests[i].pattern.nnz)
        self.sample = sorted(set(int(i) for i in sample) | {largest})

        self.svc = TuckerService(ServiceConfig(**c.get("service", {})))
        warm = []
        for t, spec in enumerate(self.specs):
            p = self.day(int(c["days"]) + t)
            warm.append(self.svc.submit(p.indices, p.values(rng), spec, key=_key(rng)))
        for ticket in warm:
            ticket.result()

    def request(self, i: int):
        r = self.requests[i]
        return self.svc.submit(r.pattern.indices, r.values, self.specs[r.tenant],
                               key=r.key).result()

    def memory_peak(self) -> int:
        return memory_peak(self.devices)

    def check(self, window) -> Dict[str, float]:
        answers = {}
        for r in window.records:
            if r.index in self.sample:
                answers[r.index] = None if r.error is not None else _host(r.answer)
            r.answer = None
        self.svc.close()
        del self.svc
        _free()
        c = self.config
        out = {"core_gap": 0.0, "fit_gap": 0.0}
        for i, ans in answers.items():
            if ans is None:  # a sampled request that never came
                return {k: float("inf") for k in out}
            req = self.requests[i]
            x = reference.Tensor(req.pattern.indices, req.values, tuple(c["shape"]),
                                 int(c["reference_block"]), device=self.devices[0])
            got = _readings([ans], x, c["tenant_ranks"][req.tenant], int(c["n_iter"]),
                            req.key)
            for k in out:
                out[k] = max(out[k], got[k])
        return out


SYSTEMS = {"plan": PlanSystem, "service": ServiceSystem}
