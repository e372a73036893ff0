"""repro.tucker — the unified plan/execute decomposition front-end.

The stable API every scaling PR targets (sharding, async serving,
multi-backend):

    from repro import tucker

    spec = tucker.TuckerSpec(shape=coo.shape, ranks=(16, 16, 16),
                             method="gram", engine="auto")
    plan = tucker.plan(spec)          # validated once; owns engine + program
    res = plan(coo)                   # TuckerResult; 0 retraces when warm
    results = plan.batch([coo_a, coo_b])   # one dispatch for k tensors

    res = tucker.decompose(coo, (16, 16, 16))   # one-shot convenience

    # data-parallel over a device mesh: one shard_map dispatch per decompose
    sharded = tucker.TuckerSpec(shape=coo.shape, ranks=(16, 16, 16),
                                shard=tucker.ShardSpec(num_devices=4))
    res = tucker.plan(sharded)(coo)

    # fault-tolerant long-running fit: snapshot every 5 sweeps, resume after
    # a crash — on the same devices or elastically on fewer
    ft = tucker.TuckerSpec(shape=coo.shape, ranks=(16, 16, 16),
                           snapshot=tucker.SnapshotSpec(
                               every_n_sweeps=5, directory="ckpt/job"))
    res = tucker.plan(ft)(coo)              # snapshots as it sweeps
    res = tucker.resume(ft, coo)            # picks up from the latest one
"""
from repro.tucker.planning import (
    PlanCache,
    TuckerPlan,
    add_plan_eviction_hook,
    clear_plan_cache,
    decompose,
    engine_for_spec,
    mesh_fingerprint,
    mesh_for_shard,
    plan,
    plan_cache_info,
    resume,
    set_plan_cache_capacity,
)
from repro.tucker.result import RequestTiming, TuckerResult
from repro.tucker.snapshot import SnapshotState, load_snapshot
from repro.tucker.spec import (
    ALGORITHMS,
    METHODS,
    ShardSpec,
    SnapshotSpec,
    TuckerSpec,
    spec_for,
)

__all__ = [
    "ALGORITHMS",
    "METHODS",
    "PlanCache",
    "RequestTiming",
    "ShardSpec",
    "SnapshotSpec",
    "SnapshotState",
    "TuckerPlan",
    "TuckerResult",
    "TuckerSpec",
    "add_plan_eviction_hook",
    "clear_plan_cache",
    "decompose",
    "engine_for_spec",
    "load_snapshot",
    "mesh_fingerprint",
    "mesh_for_shard",
    "plan",
    "plan_cache_info",
    "resume",
    "set_plan_cache_capacity",
    "spec_for",
]
