"""Fused adapted linear (kernels/tt_linear.py: the forward call and the dx
call of its VJP) against its roofline, training cells: the least time the
chip needs for the adapted matmuls one step requires (forward and dx once
each; x, W, A, B and the output each moved once), over the kernel's device
time in the traced steps. Remat's second forward is not required work, so
it lowers the share. The kernel's operations are named after the program's
entry point ``tt_linear`` (the batched-A serving variant excluded)."""
from bench.lib import peaks


def is_kernel(op) -> bool:
    return op.is_kernel and "tt_linear" in op.instr \
        and "batched" not in op.instr


def read(ctx):
    if ctx["kind"] != "train" or "trace" not in ctx:
        return None
    t = ctx["trace"].time_of(is_kernel)
    if t <= 0:
        return None
    w = ctx["traced"]["work"]
    return 100.0 * peaks.roofline_s(w["tt_linear_flops"],
                                    w["tt_linear_bytes"],
                                    ctx["device_kind"]) / t
