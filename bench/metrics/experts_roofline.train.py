"""The held experts' grouped matmuls (kernels/grouped_matmul.py, named
``moe_gmm``: gate, up and down, forward and dx) against their roofline,
training cells whose architecture routes to experts: the least time the
chip needs for the expert work the traced steps require (the pairs routed
to held experts, from the program's counter ``moe_pairs_here``; each
expert's weights and the rows moved once a direction), over the kernel's
device time. Remat's second forward is not required work, so it lowers
the share. Nothing to read where no such kernel ran."""
from bench.lib import peaks


def is_kernel(op) -> bool:
    return op.is_kernel and "moe_gmm" in op.instr


def read(ctx):
    if ctx["kind"] != "train" or "trace" not in ctx:
        return None
    w = ctx["traced"]["work"]
    if "experts_flops" not in w:
        return None
    t = ctx["trace"].time_of(is_kernel)
    if t <= 0:
        return None
    return 100.0 * peaks.roofline_s(w["experts_flops"], w["experts_bytes"],
                                    ctx["device_kind"]) / t
