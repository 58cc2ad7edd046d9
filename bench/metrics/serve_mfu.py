"""Whole serving step's share of the chip's peak: the operations the
window's requests required (a forward pass per prompt position computed and
per generated token fed back, at its real context; a readout per generated
token; no padding of the (slots, chunk) block) over the untraced window's
wall time, over the bf16 peak."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    w = ctx["window"]
    return 100.0 * w["work"]["model_flops"] / w["seconds"] \
        / ctx["peak"]["bf16_flops"]
