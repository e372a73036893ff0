"""Device milliseconds per decomposition in the Kronecker-accumulation
kernels, averaged over the cell's chips. A kernel is a ``tpu_custom_call``
whose operation is named after the function that builds it (``_fused_call``
for the fused Kron-scatter kernel today) or after its kernel body; both
name lists are below. The XLA gathers that feed the kernels are not counted
here."""

KERNELS = ("_fused_call", "_mega_call", "_scatter_call", "kron_contrib_pallas",
           "_fused_kernel", "_kron_kernel", "_scatter_kernel", "_mega_kernel")


def matches(hlo: str) -> bool:
    name = hlo.split(" = ", 1)[0].lstrip("%").split(".")[0]
    return "tpu_custom_call" in hlo and name in KERNELS


def read(ctx):
    if ctx.trace is None or ctx.completed == 0:
        return None
    s = ctx.trace.op_seconds(matches) / ctx.trace.n_devices
    return s / ctx.completed * 1e3 if s > 0 else None
