"""Record the scoped trace that the stage tests read: on one TPU, a Pallas
decomposition of a small surrogate tensor, two calls inside the harness's
``bench.window`` annotation, with the program's spans on.

    python3 tests/bench/record_trace.py    # from the repository root, one TPU

Writes ``tests/bench/data/pallas_scoped.xplane.pb`` and
``pallas_scoped.spans.json``: the spans as recorded (``time.perf_counter``
seconds) and the anchor, ``perf_counter`` at the annotation's start, as the
harness takes it. The profiler's ``/host:metadata`` plane and the
operations' source-location stats are dropped to keep the file small;
nothing a reduction reads is among them.
"""
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))
OUT = os.path.join(ROOT, "tests", "bench", "data", "pallas_scoped")
SHAPE, RANKS, NNZ = (600, 500, 400), (8, 8, 8), 16384
DROPPED_STATS = ("source", "source_stack")


def strip(path: str, out: str) -> None:
    from bench import scopes

    space = scopes.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for i in reversed(range(len(space.planes))):
        if space.planes[i].name == "/host:metadata":
            del space.planes[i]
    for p in space.planes:
        drop = {k for k, v in p.stat_metadata.items() if v.name in DROPPED_STATS}
        for meta in p.event_metadata.values():
            keep = [s for s in meta.stats if s.metadata_id not in drop]
            del meta.stats[:]
            meta.stats.extend(keep)
    with open(out, "wb") as f:
        f.write(space.SerializeToString())


def main() -> int:
    import jax
    import numpy as np

    import repro.obs as obs
    from bench import surrogate, trace
    from repro import tucker
    from repro.core.coo import SparseCOO

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_default_matmul_precision", "highest")
    rng = np.random.default_rng(0)
    pat = surrogate.pattern(SHAPE, NNZ, 8, np.random.default_rng(1))
    coo = SparseCOO.from_parts(pat.indices, pat.values(rng), SHAPE)
    plan = tucker.plan(tucker.TuckerSpec(shape=SHAPE, ranks=RANKS, n_iter=2, engine="pallas"))
    jax.block_until_ready(plan(coo).core)  # schedules and compile
    obs.configure(enabled=True)
    obs.tracer.clear()
    tdir = tempfile.mkdtemp(prefix="record-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    anchor = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for _ in range(2):
            jax.block_until_ready(plan(coo).core)
    jax.profiler.stop_trace()
    obs.configure(enabled=False)
    strip(trace.find_xplane(tdir), OUT + ".xplane.pb")
    spans = [{"name": s.name, "t0": s.t0, "t1": s.t1, "thread_id": s.thread_id}
             for s in obs.tracer.events()]
    with open(OUT + ".spans.json", "w") as f:
        json.dump({"anchor": anchor, "spans": spans}, f, indent=1)
        f.write("\n")
    print(f"record_trace: {os.path.getsize(OUT + '.xplane.pb')} bytes, {len(spans)} spans")
    return 0


if __name__ == "__main__":
    sys.exit(main())
