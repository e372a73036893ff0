"""Core library: the paper's sparse Tucker decomposition in JAX.

Modules mirror the paper's accelerator decomposition:
  coo.py          COO storage (Sec. III-A, Table I)
  ttm.py          dense TTM, module 1 (Sec. III-B, Alg. 3)
  kron.py         sparse Kron-accumulation, module 2 (Sec. III-C, Alg. 4)
  qrp.py          QR with column pivoting, module 3 (Sec. III-D)
  hooi.py         sweep machinery + the compiled sweep programs
                  (the public front-end is repro.tucker's plan/execute API)
  engine.py       sweep engine selection: XLA vs Pallas-kernel hot loops
  reconstruct.py  Eq. 7 reconstruction + error metrics
  distributed.py  pod-scale shard_map data-parallel Alg. 2
"""
from repro.core.coo import SparseCOO, fold_dense, unfold_dense
from repro.core.engine import (
    ENGINES,
    SweepEngine,
    available_engines,
    make_engine,
    resolve_engine,
)
from repro.core.hooi import (
    HooiResult,
    effective_ranks,
    init_factors,
)
from repro.core.kron import (
    kron_rows,
    precompute_kron_reuse,
    sparse_ttm_chain,
    sparse_ttm_chain_reuse,
    sparse_ttm_chain_reuse_device,
)
from repro.core.qrp import factor_update, qrp, qrp_gram, qrp_householder, svd_factor
from repro.core.reconstruct import (
    compression_ratio,
    reconstruct_at,
    reconstruct_dense,
    relative_error_dense,
)
from repro.core.ttm import ttm, ttm_chain, ttm_unfolded
