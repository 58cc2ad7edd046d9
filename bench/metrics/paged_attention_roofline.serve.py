"""Paged attention kernel (kernels/paged_attention.py) against its
roofline, serving cells: the least time the chip needs for the attention
the traced call's requests required (queries at their real positions, K/V
of the pages in context read once per step and slot; no padding, no empty
grid cells), over the kernel's device time in the trace. The kernel's
operations are named after the program's entry point
``paged_decode_attention``."""
from bench.lib import peaks


def is_kernel(op) -> bool:
    return op.is_kernel and "paged_decode_attention" in op.instr


def read(ctx):
    if ctx["kind"] != "serve" or "trace" not in ctx:
        return None
    t = ctx["trace"].time_of(is_kernel, whole=True)
    if t <= 0:
        return None
    w = ctx["traced"]["work"]
    return 100.0 * peaks.roofline_s(w["paged_flops"], w["paged_bytes"],
                                    ctx["device_kind"]) / t
