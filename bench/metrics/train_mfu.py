"""Whole training step's share of the chip's peak: forward, plus backward
for activations and adapter parameters (no frozen-weight gradients, no remat
recompute), per token, times the untraced window's token rate, over the
bf16 peak."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    w = ctx["window"]
    return 100.0 * w["work"]["model_flops"] / w["seconds"] \
        / ctx["peak"]["bf16_flops"]
