import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf cell C component: cross-pod gradient sync, raw vs QRP-compressed.

The paper's QRP (module 3, Gram form) as a PowerSGD-style compressor for the
slow pod axis: on the 2x16x16 mesh, lower + compile

  raw:        per-pod grads -> pmean over "pod"
  compressed: per-pod grads -> QRP_gram rank-r factors -> pmean(Q), pmean(P)
              over "pod" -> decompress (error feedback kept locally)

and measure the pod-crossing collective bytes of both from the partitioned
HLO. Numerical properties (exactness at rank >= true rank, error-feedback
convergence) are covered by tests/test_optim.py.

  python -m repro.launch.compress_bench [--rank 64]
"""
import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.optim.compression import compress_matrix, decompress_matrix
from repro.utils import hlo as hlo_lib


def grad_matrices(cfg):
    """The layer-stacked weight grads of the config, as (name, m, n) mats
    (leading dims collapsed) — what crosses the pod axis every step."""
    shapes = model_lib.param_shapes(cfg)["layers"]
    mats = []
    for name, leaf in shapes.items():
        if len(leaf.shape) >= 2:
            m = int(np.prod(leaf.shape[:-1]))
            mats.append((name, m, int(leaf.shape[-1])))
    return mats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--arch", default="granite-moe-1b-a400m")
    ap.add_argument("--out", default="results/compress_bench.json")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=True)
    cfg = get_config(args.arch)
    mats = grad_matrices(cfg)

    def make_inputs():
        shapes = tuple(jax.ShapeDtypeStruct((2, m, n), jnp.float32) for _, m, n in mats)
        shardings = tuple(
            jax.sharding.NamedSharding(mesh, P("pod", None, None)) for _ in mats
        )
        return shapes, shardings

    def raw_sync(*gs):
        return tuple(jax.lax.pmean(g[0], "pod") for g in gs)

    def compressed_sync(*gs):
        outs = []
        for g in gs:
            g0 = g[0]
            q, p = compress_matrix(g0, args.rank)
            q = jax.lax.pmean(q, "pod")
            p = jax.lax.pmean(p, "pod")
            outs.append(decompress_matrix(q, p))
        return tuple(outs)

    shapes, shardings = make_inputs()
    results = {}
    for name, fn in (("raw", raw_sync), ("qrp_compressed", compressed_sync)):
        sm = jax.shard_map(
            fn, mesh=mesh,
            in_specs=tuple(P("pod", None, None) for _ in mats),
            out_specs=tuple(P(None, None) for _ in mats),
            check_vma=False,
        )
        compiled = jax.jit(sm, in_shardings=shardings).lower(*shapes).compile()
        summary = hlo_lib.analyze_hlo(compiled.as_text())
        results[name] = dict(
            coll_bytes=summary.total_coll_bytes,
            coll_xpod_bytes=summary.coll_xpod_bytes,
            dot_flops=summary.dot_flops,
        )
        print(f"{name:16s} coll={summary.total_coll_bytes/2**20:9.2f} MiB/dev "
              f"xpod={summary.coll_xpod_bytes/2**20:9.2f} MiB/dev "
              f"(extra dot GF: {summary.dot_flops/1e9:.2f})")
    ratio = results["raw"]["coll_bytes"] / max(results["qrp_compressed"]["coll_bytes"], 1)
    analytic = sum(m * n for _, m, n in mats) / sum(
        args.rank * (m + n) for _, m, n in mats
    )
    print(f"measured reduction: {ratio:.1f}x (analytic r*(m+n) model: {analytic:.1f}x)")
    results["reduction"] = ratio
    results["analytic_reduction"] = analytic
    results["rank"] = args.rank
    import pathlib
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(args.out).write_text(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
