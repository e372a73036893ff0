"""Paper Fig. 6: sparse vs dense Tucker on 200^3 tensors across sparsity.

Reproduces the *algorithmic* claim on CPU: the sparse Kron-accumulation
algorithm (Alg. 2) beats the dense HOOI baseline (Alg. 1, our stand-in for
the dense accelerator [25]) with a margin that grows as sparsity increases.
"""
from __future__ import annotations



def run(sparsities=(1e-5, 1e-4, 1e-3), size=200, rank=16, n_iter=2) -> list:
    import jax
    import jax.numpy as jnp

    from benchmarks.common import time_fn
    from repro import tucker
    from repro.sparse.generators import random_sparse_tensor

    rows = []
    for sp in sparsities:
        coo = random_sparse_tensor((size,) * 3, sp, seed=int(sp * 1e7) % 997)
        sparse_plan = tucker.plan(tucker.spec_for(
            coo, (rank,) * 3, n_iter=n_iter, method="gram"))
        t0, _ = time_fn(lambda: sparse_plan(coo), warmup=1, iters=3)
        dense = coo.to_dense()
        dense_plan = tucker.plan(tucker.spec_for(
            dense, (rank,) * 3, n_iter=n_iter, method="svd"))
        t1, _ = time_fn(lambda: dense_plan(dense), warmup=1, iters=3)
        rows.append(dict(sparsity=sp, nnz=coo.nnz, sparse_s=t0, dense_s=t1,
                         speedup=t1 / t0))
    return rows


def main():
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("fig6_sparsity: sparsity,nnz,sparse_hooi_s,dense_hooi_s,speedup")
    for r in run():
        print(f"{r['sparsity']:.0e},{r['nnz']},{r['sparse_s']:.4f},"
              f"{r['dense_s']:.4f},{r['speedup']:.2f}")


if __name__ == "__main__":
    main()
