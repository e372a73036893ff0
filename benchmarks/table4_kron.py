"""Paper Table IV: Kronecker-product module performance (rank 32..256).

The ``--engine`` axis times the module on each sweep engine:
  xla     jit'd jnp reference (``kernels.ref.kron_contrib_ref``)
  pallas  the Pallas kernel (``kernels.ops.kron_contrib``; Mosaic on TPU,
          interpret mode on CPU — interpret timings are NOT hardware numbers,
          the deliverable there is correctness vs the oracle)
  auto    whatever ``core.engine.resolve_engine`` picks on this host
  both    one row per engine
"""
from __future__ import annotations

import argparse

import numpy as np


def run(ranks=(32, 64, 128, 256), nnz=128, engine: str = "both",
        blocks=(None,)) -> list:
    """``blocks`` is a list of ``bn`` values (nonzeros per kernel block) to
    sweep; ``None`` means the kernel default. Only pallas rows vary by
    block."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import engine_list, time_fn
    from repro.kernels import ops, ref
    from repro.kernels.kron_kernel import DEFAULT_BN

    paper = {32: (9.655e-6, 0.578e-6), 64: (14.72e-6, 2.301e-6),
             128: (24.87e-6, 9.195e-6), 256: (48.24e-6, 38.55e-6)}
    engines = engine_list(engine)
    ref_jit = jax.jit(ref.kron_contrib_ref)
    rows = []
    rng = np.random.default_rng(0)
    for r in ranks:
        a = jnp.asarray(rng.standard_normal((nnz, r)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((nnz, r)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((nnz,)).astype(np.float32))
        want = np.asarray(ref.kron_contrib_ref(a, b, v))
        for bn in blocks:
            bn_eff = bn if bn is not None else DEFAULT_BN
            for eng in engines:
                if eng == "pallas":
                    fn = lambda x, y, z: ops.kron_contrib(x, y, z, bn=bn)
                else:
                    fn = lambda x, y, z: ref_jit(x, y, z)
                t, _ = time_fn(fn, a, b, v)
                err = float(np.abs(np.asarray(fn(a, b, v)) - want).max())
                rows.append(dict(
                    size=f"1x{r} (x) 1x{r}", engine=eng, block=bn_eff,
                    us_per_kron=t / nnz * 1e6, maxerr_vs_ref=err,
                    paper_cpu_us=paper[r][0] * 1e6,
                    paper_fpga_us=paper[r][1] * 1e6,
                ))
    return rows


def main(argv=None):
    from benchmarks.common import add_engine_arg
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    # argv=None (e.g. from benchmarks.run) means "no CLI args": don't let
    # argparse pick up the aggregator's own sys.argv.
    p = argparse.ArgumentParser(description=__doc__)
    add_engine_arg(p)
    p.add_argument("--nnz", type=int, default=128)
    p.add_argument("--block", action="append", type=int, default=None,
                   metavar="BN",
                   help="kron block size(s) to sweep, e.g. --block 64 "
                        "--block 256 (default: kernel default)")
    args = p.parse_args([] if argv is None else argv)
    blocks = args.block if args.block else [None]
    print("table4_kron: size,engine,block,us_per_kron,maxerr_vs_ref,"
          "paper_cpu_us,paper_fpga_us")
    for r in run(nnz=args.nnz, engine=args.engine, blocks=blocks):
        print(f"{r['size']},{r['engine']},{r['block']},{r['us_per_kron']:.3f},"
              f"{r['maxerr_vs_ref']:.2e},{r['paper_cpu_us']:.3f},"
              f"{r['paper_fpga_us']:.3f}")


if __name__ == "__main__":
    import sys

    main(sys.argv[1:])
