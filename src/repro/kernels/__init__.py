"""Pallas TPU kernels for the perf-critical compute layers.

  ttm_kernel       paper module 1 (Alg. 3): tiled dense TTM on the MXU
  kron_kernel      paper module 2 (Alg. 4 + Eq. 13): Kron rows + one-hot
                   MXU scatter-accumulation, plus the fused
                   kron-contrib→scatter pipeline used by the sweep engine
  flash_attention  LM hot spot: blockwise online-softmax GQA attention
  ssd_scan         Mamba-2 SSD within-chunk fused kernel
  ops              jit'd dispatch wrappers (interpret on CPU, Mosaic on TPU)
  ref              pure-jnp oracles for allclose validation

These kernels are the production path of a plan with ``engine="pallas"``:
``core.engine`` streams nonzeros through them on a host-side
``sparse.layout.SortedCOO`` schedule. ``tests/test_engine.py`` holds the
differential harness that gates any change here against the dense oracle.
"""
