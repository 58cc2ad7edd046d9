"""Flash attention (kernels/flash_attention.py: forward, dq and dkv)
against its roofline, training cells: the least time the chip needs for
causal attention's required work in the traced steps (forward QK^T and PV,
backward dV, dP, dQ, dK; causal pairs only; q, k, v, o, dO and the
gradients moved once), over the kernels' device time. Remat's second
forward is not required work. The kernels' operations are named after the
program's entry points ``flash_attention_fwd`` / ``flash_attention_bwd``."""
from bench.lib import peaks


def is_kernel(op) -> bool:
    return op.is_kernel and "flash_attention" in op.instr


def read(ctx):
    if ctx["kind"] != "train" or "trace" not in ctx:
        return None
    t = ctx["trace"].time_of(is_kernel)
    if t <= 0:
        return None
    w = ctx["traced"]["work"]
    return 100.0 * peaks.roofline_s(w["flash_flops"], w["flash_bytes"],
                                    ctx["device_kind"]) / t
