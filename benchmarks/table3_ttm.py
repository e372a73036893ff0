"""Paper Table III: TTM module performance.

Paper setting: Y (R1R2 x I3) x U (R3 x I3), R1=R2=R3=32, I3 in 32..256.

The ``--engine`` axis times the module on each sweep engine:
  xla     jit'd jnp reference (``kernels.ref.ttm_ref``)
  pallas  the blocked Pallas kernel (``kernels.ops.ttm``; Mosaic on TPU,
          interpret mode on CPU — interpret timings are NOT hardware
          numbers: the CPU deliverable is the kernel's correctness plus its
          analytic VMEM/MXU occupancy, reported alongside; paper wall-times
          are quoted for context).
"""
from __future__ import annotations

import argparse

import numpy as np


def run(i3_list=(32, 64, 128, 256), r=32, engine: str = "both",
        blocks=((None, None),)) -> list:
    """``blocks`` is a list of (bl, bk) TTM tile shapes to sweep; (None,
    None) means the kernel defaults. Only the pallas rows vary by block —
    the XLA reference has no tiles and is reported once per (shape,
    block) pair for easy row pairing."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import engine_list, time_fn
    from repro.kernels import ops, ref
    from repro.kernels.ttm_kernel import DEFAULT_BK, DEFAULT_BL

    paper = {32: (0.493e-3, 0.148e-3), 64: (0.596e-3, 0.281e-3),
             128: (1.165e-3, 0.546e-3), 256: (2.021e-3, 1.077e-3)}
    engines = engine_list(engine)
    ref_jit = jax.jit(ref.ttm_ref)
    rows = []
    rng = np.random.default_rng(0)
    l = r * r
    for i3 in i3_list:
        y = jnp.asarray(rng.standard_normal((l, i3)).astype(np.float32))
        u = jnp.asarray(rng.standard_normal((r, i3)).astype(np.float32))
        want = np.asarray(ref.ttm_ref(y, u))
        for bl, bk in blocks:
            bl_eff = bl if bl is not None else DEFAULT_BL
            bk_eff = bk if bk is not None else DEFAULT_BK
            for eng in engines:
                fn = (
                    (lambda a, b: ops.ttm(a, b, bl=bl, bk=bk))
                    if eng == "pallas" else (lambda a, b: ref_jit(a, b))
                )
                t, _ = time_fn(fn, y, u)
                err = float(np.abs(np.asarray(fn(y, u)) - want).max())
                # analytic kernel occupancy on the v5e target
                flops = 2 * l * i3 * r
                vmem = (min(bl_eff, l) * min(bk_eff, i3)
                        + r * min(bk_eff, i3)
                        + 2 * min(bl_eff, l) * r) * 4
                rows.append(dict(
                    tensor=f"{r}x{r}x{i3}", engine=eng,
                    block=f"{bl_eff}x{bk_eff}", ms=t * 1e3,
                    maxerr_vs_ref=err, kernel_flops=flops,
                    kernel_vmem_kib=vmem / 1024,
                    paper_cpu_ms=paper[i3][0] * 1e3,
                    paper_fpga_ms=paper[i3][1] * 1e3,
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
    p.add_argument("--block", action="append", default=None,
                   metavar="BLxBK",
                   help="TTM tile(s) to sweep, e.g. --block 128x256 "
                        "--block 256x512 (default: kernel defaults)")
    args = p.parse_args([] if argv is None else argv)
    blocks = (
        [tuple(int(x) for x in b.lower().split("x")) for b in args.block]
        if args.block else [(None, None)]
    )
    print("table3_ttm: tensor,engine,block,ms,maxerr_vs_ref,kernel_flops,"
          "kernel_vmem_kib,paper_cpu_ms,paper_fpga_ms")
    for r in run(engine=args.engine, blocks=blocks):
        print(f"{r['tensor']},{r['engine']},{r['block']},{r['ms']:.4f},"
              f"{r['maxerr_vs_ref']:.2e},"
              f"{r['kernel_flops']},{r['kernel_vmem_kib']:.0f},"
              f"{r['paper_cpu_ms']:.3f},{r['paper_fpga_ms']:.3f}")


if __name__ == "__main__":
    import sys

    main(sys.argv[1:])
