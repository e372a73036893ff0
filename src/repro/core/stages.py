"""Names of the decomposition's stages on the device.

Every stage of a sweep program runs under one ``jax.named_scope`` of these
names. The compiler keeps a scope in each operation's ``op_name`` metadata
and the profiler reports it as the operation's ``tf_op`` path, for example
``jit(_scan_sweeps_impl)/while/body/.../tucker.kron/pallas_call``, so a
device trace can be cut by stage. Scopes are siblings, never nested: an
operation carries exactly one of them. A scope is trace-time metadata only;
the compiled instructions are the same without it.

The eager preamble of the per-tensor pipelines (starting factors and the
norm, dispatched before the sweep program) runs as programs of its own, and
JAX starts each top-level program's name stack afresh, so ``INIT`` reaches
the device only where the preamble is traced inside a program (the batched
program).
"""

INIT = "tucker.init"  # starting factors and the tensor's norm
ORDER_GATHER = "tucker.order_gather"  # nonzeros into the schedule's order
ROW_GATHER = "tucker.row_gather"  # each nonzero's factor rows
KRON = "tucker.kron"  # Kronecker accumulation: Pallas kernels or XLA scatter-add
QRP = "tucker.qrp"  # factor update (module 3)
CORE = "tucker.core"  # core TTM, fold, fit
PSUM = "tucker.psum"  # the sharded program's all-reduce of Y_(n)

STAGES = (INIT, ORDER_GATHER, ROW_GATHER, KRON, QRP, CORE, PSUM)
