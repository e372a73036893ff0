"""Bring-up run of the plan, serve and shard paths on a TPU.

    python chip_smoke.py               # one chip: decompose + serve
    python chip_smoke.py --four-chips  # four chips: the sharded path only

One process owns the chip(s) from start to end: nothing here forks or spawns.
Every phase goes through the entry points a user calls (``tucker.plan``,
``TuckerService``), on seeded surrogates of published tensors:

* decompose: nell-2's shape (FROSTT), 12,092 x 9,184 x 28,818, at 2^22
  nonzeros, ranks (16, 16, 16), the default spec (``engine="auto"``). The
  Pallas run is checked against the XLA engine at ``highest`` matmul
  precision on the same tensor, at fp32 and at ``bf16_fp32acc``.
* serve: 16 requests of uber day-slice shape, 24 x 1,140 x 1,717, with
  10,000-40,000 nonzeros each, from 4 tenants; each ticket is checked against
  a sequential ``plan(coo)``, then the same tensors run through the vmapped
  batched program (``plan.batch``, XLA engine).
* four chips: the nell-2 surrogate through ``ShardSpec(num_devices=4)``,
  checked against the one-chip XLA result computed in the same process.

Earlier lines print ``[phase] key=value`` measurements of one uncontrolled
run (not a benchmark). The last line is the JSON verdict. Without a TPU the
script exits nonzero before any phase, and any failed check exits nonzero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

# nell-2 (FROSTT): 12,092 x 9,184 x 28,818 with 76,879,419 nonzeros. The
# compiled sweep program holds about 2 KiB of device temp per nonzero (its
# (nnz, R) operands are lane-padded to 128 in HBM), so 2^22 nonzeros (about
# 8 GiB) is what one 16 GiB chip holds with room for the reference run.
NELL2_SHAPE = (12_092, 9_184, 28_818)
NELL2_NNZ = 76_879_419
NNZ = 2**22
RANKS = (16, 16, 16)  # assumed: a rank a user of nell-2 would pick
N_ITER = 5

# uber (FROSTT) is 183 days x 24 h x 1,140 x 1,717: one request is one day.
UBER_SLICE = (24, 1_140, 1_717)
SERVE_REQUESTS = 16
SERVE_NNZ = (10_000, 40_000)
TENANT_RANKS = ((4, 8, 8), (8, 8, 8), (4, 16, 16), (8, 16, 16))  # assumed

# Each run is compared with its reference on two numbers: the fit history,
# absolute, and the subspace gap, 1 - ||U^T U_ref||_F^2 / R, the largest over
# the modes (0 for the same subspace, 1 for orthogonal ones). The Pallas
# program keeps the default matmul precision in its XLA parts (QRP, core
# fold): on a TPU that rounds f32 operands to bf16 (2^-8), which reaches the
# fit at first order through U's orthogonality. A wrong kernel loses the
# planted blocks: its fit falls by tenths and its subspace turns away.
TOL = {  # (fit history, subspace gap or None)
    "fp32": (5e-3, 1e-3),  # Pallas (default precision) vs XLA at "highest"
    "bf16_fp32acc": (1e-2, 1e-2),  # bf16 loads, f32 accumulation, same ref
    "served": (1e-6, 1e-6),  # a ticket vs plan(coo): same plan, same program
    # vmapped XLA batch vs the served Pallas run; a tenant's ranks above the
    # planted count pick noise directions, which no run determines
    "batched": (5e-3, None),
    "sharded": (5e-3, 1e-3),  # 4-chip psum vs one chip: other reduction order
}


class Failed(Exception):
    """A check of the smoke run did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Failed(what)


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def surrogate(shape, nnz: int, components: int, seed: int):
    """Seeded stand-in for a sparse tensor with a known Tucker structure.

    About half the nonzeros form ``components`` dense rank-1 blocks on
    disjoint, randomly scattered index sets, with distinct block weights;
    they carry 3/4 of the energy, so a Tucker model of rank ``components``
    per mode has a well-defined fit (about 1/2 relative error) and a
    well-separated subspace. The rest are distinct coordinates drawn
    uniformly over ``shape`` with count values 1 + Poisson(3), which touch
    every row of every mode. A uniform pattern alone would keep ~1e-6 of its
    energy at rank 16 and leave nothing for a comparison to see."""
    from repro.core.coo import SparseCOO

    rng = np.random.default_rng(seed)
    ndim = len(shape)
    side = [max(1, min(s // components,
                       round((nnz / 2 / components) ** (1 / ndim))))
            for s in shape]
    groups = [rng.permutation(s)[:components * d].reshape(components, d)
              for s, d in zip(shape, side)]
    local = np.indices(side).reshape(ndim, -1)  # offsets inside one block
    coords, vals = [], []
    for r in range(components):
        coords.append(np.stack([g[r][o] for g, o in zip(groups, local)], 1))
        block = 2.0 ** (-r / 8)  # distinct singular values
        for d in side:
            block = np.multiply.outer(block, rng.uniform(0.5, 1.5, d))
        vals.append(block.ravel())
    planted = np.concatenate(coords)
    planted_vals = np.concatenate(vals)
    check(planted.shape[0] < nnz, f"{planted.shape[0]} planted of {nnz}")

    total = int(np.prod(shape, dtype=np.int64))
    taken = np.ravel_multi_index(planted.T, shape)
    need = nnz - taken.size
    lin = np.unique(rng.integers(0, total, size=need + need // 8 + 64))
    lin = rng.permutation(lin[~np.isin(lin, taken)])
    check(lin.size >= need, f"drew only {lin.size} distinct coordinates")
    noise = np.stack(np.unravel_index(lin[:need], shape), axis=1)
    noise_vals = rng.poisson(3.0, need) + 1.0
    planted_vals *= np.sqrt(3 * np.sum(noise_vals**2)
                            / np.sum(planted_vals**2))

    order = rng.permutation(nnz)
    all_coords = np.concatenate([planted, noise])[order].astype(np.int32)
    all_vals = np.concatenate([planted_vals, noise_vals])[order]
    return SparseCOO.from_parts(all_coords, all_vals.astype(np.float32), shape)


def compare(pairs) -> dict:
    """Largest fit-history difference and subspace gap over ``(res, ref)``
    pairs."""
    fit = gap = 0.0
    for res, ref in pairs:
        h = np.asarray(res.fit_history, np.float64)
        r = np.asarray(ref.fit_history, np.float64)
        check(h.shape == r.shape, "fit histories differ in length")
        fit = max(fit, float(np.max(np.abs(h - r))))
        for u, v in zip(res.factors, ref.factors):
            # orthonormal bases of the two spans, so that U's own rounding
            # away from orthogonality does not count as a gap
            u, v = (np.linalg.qr(np.asarray(x, np.float64))[0] for x in (u, v))
            gap = max(gap, 1.0 - float(np.sum(np.square(u.T @ v))) / u.shape[1])
    return {"fit_maxdiff": fit, "subspace_gap": gap}


def within(diff: dict, what: str) -> None:
    tol_fit, tol_gap = TOL[what]
    check(diff["fit_maxdiff"] <= tol_fit
          and (tol_gap is None or diff["subspace_gap"] <= tol_gap),
          f"{what}: {diff} outside tolerance {TOL[what]}")


def timed(plan, coo):
    import jax

    t0 = time.perf_counter()
    res = plan(coo)
    jax.block_until_ready((res.core, res.factors))
    return res, time.perf_counter() - t0


def peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def decompose_phase(coo) -> None:
    import jax

    from repro import tucker

    spec = tucker.TuckerSpec(shape=coo.shape, ranks=RANKS, n_iter=N_ITER)
    plan = tucker.plan(spec)
    t0 = time.perf_counter()
    text, meta = plan.lower_hlo(coo)
    compile_s = time.perf_counter() - t0
    n_kernels = text.count("tpu_custom_call")
    cold, cold_s = timed(plan, coo)
    warm, warm_s = timed(plan, coo)
    log("decompose", engine=warm.engine, precision=warm.precision,
        compile_s=compile_s, cold_s=cold_s, warm_s=warm_s,
        tpu_custom_call=n_kernels, temp_bytes=meta["temp_bytes"],
        argument_bytes=meta["argument_bytes"], peak_bytes_in_use=peak_bytes(),
        warm_retraces=warm.retraces, warm_schedule_builds=warm.schedule_builds,
        rel_error=float(warm.rel_error), n_sweeps=warm.n_sweeps)
    check(warm.engine == "pallas", f"engine='auto' resolved to {warm.engine!r}")
    check(n_kernels > 0, "no tpu_custom_call in the compiled program")
    check(warm.retraces == 0, f"warm call retraced {warm.retraces} times")
    check(warm.schedule_builds == 0,
          f"warm call rebuilt {warm.schedule_builds} schedules")
    check(warm.n_sweeps == N_ITER and np.isfinite(float(warm.rel_error)),
          "the decomposition did not run its sweeps to a finite error")
    check(np.array_equal(cold.fit_history, warm.fit_history),
          "cold and warm calls of one plan disagree")
    del cold, plan
    tucker.clear_plan_cache()  # frees the Pallas plan's device schedules

    with jax.default_matmul_precision("highest"):
        ref_plan = tucker.plan(dataclasses.replace(spec, engine="xla"))
        _, ref_cold_s = timed(ref_plan, coo)
        ref, ref_s = timed(ref_plan, coo)
    del ref_plan
    log("decompose-ref", engine=ref.engine, matmul_precision="highest",
        cold_s=ref_cold_s, warm_s=ref_s, rel_error=float(ref.rel_error),
        memory_stats=jax.devices()[0].memory_stats())
    d = compare([(warm, ref)])
    log("decompose-vs-ref", precision="fp32", **d, tol=TOL["fp32"])
    within(d, "fp32")
    tucker.clear_plan_cache()

    bf16, bf16_s = timed(
        tucker.plan(dataclasses.replace(spec, precision="bf16_fp32acc")), coo)
    d = compare([(bf16, ref)])
    log("decompose-vs-ref", precision=bf16.precision, engine=bf16.engine,
        cold_s=bf16_s, rel_error=float(bf16.rel_error), **d,
        tol=TOL["bf16_fp32acc"])
    check(bf16.engine == "pallas" and bf16.precision == "bf16_fp32acc",
          f"bf16 run took engine={bf16.engine} precision={bf16.precision}")
    within(d, "bf16_fp32acc")
    tucker.clear_plan_cache()


def serve_phase(seed: int) -> None:
    from repro import tucker
    from repro.serve import TuckerService

    specs = [tucker.TuckerSpec(shape=UBER_SLICE, ranks=r) for r in TENANT_RANKS]
    rng = np.random.default_rng(seed)
    nnzs = rng.integers(SERVE_NNZ[0], SERVE_NNZ[1] + 1, size=SERVE_REQUESTS)
    components = min(min(r) for r in TENANT_RANKS)
    reqs = [(specs[i % len(specs)],
             surrogate(UBER_SLICE, int(n), components, seed + 1 + i))
            for i, n in enumerate(nnzs)]

    t0 = time.perf_counter()
    with TuckerService() as svc:
        tickets = [svc.submit_coo(coo, spec) for spec, coo in reqs]
        served = [t.result(timeout=900) for t in tickets]
    serve_s = time.perf_counter() - t0
    engines = sorted({r.engine for r in served})
    log("serve", requests=len(served), tenants=len(specs),
        nnz_min=int(nnzs.min()), nnz_max=int(nnzs.max()), engines=engines,
        wall_s=serve_s, peak_bytes_in_use=peak_bytes())
    check(engines == ["pallas"], f"served engines {engines}")

    t0 = time.perf_counter()
    seq = [tucker.plan(spec)(coo) for spec, coo in reqs]
    d = compare(zip(served, seq))
    log("serve-vs-sequential", requests=len(seq), **d, tol=TOL["served"],
        wall_s=time.perf_counter() - t0)
    within(d, "served")

    t0 = time.perf_counter()
    pairs, dispatches = [], 0
    for spec in specs:
        members = [i for i, (s, _) in enumerate(reqs) if s == spec]
        bplan = tucker.plan(dataclasses.replace(spec, engine="xla"))
        check(bplan.supports_batched_dispatch, "XLA plan cannot batch")
        out = bplan.batch([reqs[i][1] for i in members])
        dispatches += sum(r.dispatches for r in out)
        pairs += [(r, served[i]) for r, i in zip(out, members)]
    d = compare(pairs)
    log("serve-batched", engine="xla", batches=len(specs),
        dispatches=dispatches, **d, tol=TOL["batched"],
        wall_s=time.perf_counter() - t0, peak_bytes_in_use=peak_bytes())
    check(dispatches == len(specs), f"{dispatches} dispatches for "
          f"{len(specs)} batches")
    within(d, "batched")
    tucker.clear_plan_cache()


def shard_phase(coo) -> None:
    import jax

    from repro import tucker

    n = 4
    check(len(jax.devices()) >= n, f"{len(jax.devices())} devices attached")
    spec = tucker.TuckerSpec(shape=coo.shape, ranks=RANKS, n_iter=N_ITER,
                             shard=tucker.ShardSpec(num_devices=n))
    plan = tucker.plan(spec)
    mesh_devices = list(plan.mesh.devices.flat)
    check(len({d.id for d in mesh_devices}) == n
          and all(d.platform == "tpu" for d in mesh_devices),
          f"mesh devices {mesh_devices}")
    cold, cold_s = timed(plan, coo)
    res, warm_s = timed(plan, coo)
    (sched,) = plan.engine.shard_schedules.values()
    shards = sched.values.addressable_shards
    sizes = [int(s.data.shape[0]) for s in shards]
    on = {s.device.id for s in shards}
    log("shard", devices=n, engine=res.engine, cold_s=cold_s, warm_s=warm_s,
        shard_nnz=sizes, nnz_padded=sched.nnz_padded,
        warm_retraces=res.retraces, rel_error=float(res.rel_error),
        peak_bytes_in_use=peak_bytes())
    check(on == {d.id for d in mesh_devices} and len(sizes) == n,
          f"nonzeros sit on devices {sorted(on)}")
    check(len(set(sizes)) == 1 and sum(sizes) == sched.nnz_padded >= coo.nnz,
          f"uneven shards {sizes}")
    check(res.retraces == 0, f"warm sharded call retraced {res.retraces} times")
    del plan
    tucker.clear_plan_cache()

    one_plan = tucker.plan(tucker.TuckerSpec(shape=coo.shape, ranks=RANKS,
                                             n_iter=N_ITER, engine="xla"))
    _, one_cold_s = timed(one_plan, coo)
    one, one_s = timed(one_plan, coo)
    d = compare([(res, one)])
    log("shard-vs-one-chip", device=str(jax.devices()[0]), cold_s=one_cold_s,
        warm_s=one_s, rel_error=float(one.rel_error), **d, tol=TOL["sharded"])
    within(d, "sharded")
    tucker.clear_plan_cache()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded phase and its 1-chip "
                         "reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere else

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2

    from repro.utils.compile_cache import enable_compile_cache

    log("setup", device_kind=devices[0].device_kind, devices=len(devices),
        jax=jax.__version__, compile_cache=enable_compile_cache())
    try:
        t0 = time.perf_counter()
        coo = surrogate(NELL2_SHAPE, NNZ, min(RANKS), args.seed)
        log("setup", tensor="nell-2 surrogate", shape=NELL2_SHAPE, nnz=NNZ,
            nnz_published=NELL2_NNZ, ranks=RANKS, planted_blocks=min(RANKS),
            make_s=time.perf_counter() - t0,
            cut="nnz cut to 2^22: about 2 KiB of device temp per nonzero")
        if args.four_chips:
            shard_phase(coo)
        else:
            decompose_phase(coo)
            del coo
            serve_phase(args.seed)
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
