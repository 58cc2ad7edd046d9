"""Operations and bytes that the latent-attention MoE decoder's training
step requires, from the shapes and the counters of the run, counted as
``bench.lib.flops`` says.

``train_step`` returns the dict that the readers in ``bench/metrics/``
read. The held experts' work follows ``counts["moe_pairs_here"]``, the
pairs that the step routed to them over all its layers (``seam.counts``).
"""
from __future__ import annotations

from bench.lib.flops import BF16, causal_pairs

from . import weights as W


def _mat(shape) -> float:
    """Operations of one token through a (d_in, d_out) matmul."""
    return 2.0 * shape[0] * shape[1]


def train_step(cfg: dict, adapter: dict, batch: int, seq: int,
               counts: dict) -> dict:
    """Work of one training step on ``batch`` rows of ``seq`` tokens.

    model_flops: the forward pass, plus the backward pass for activations
    (every matmul's dx, attention's four backward products, the readout's
    dh) and for the adapter's parameters; the held experts' matmuls for
    the pairs routed to them, forward and dx. tt_linear_*: the fused
    adapted linears (q_b and kv_b), forward and dx. flash_*: causal flash
    attention at q/k head dim 192 and v head dim 128, forward (QK^T, PV)
    and backward (dV, dP, dQ, dK). experts_*: the grouped matmuls of the
    held experts (gate, up and down, forward and dx) over the routed
    pairs, each expert's weights read once a direction."""
    m = W.dims(cfg)
    L, n_dense = m["L"], m["dense"]
    n_moe = L - n_dense
    tok = batch * seq
    pairs = float(counts.get("moe_pairs_here", 0.0))
    mla = sum(_mat(s) for s in W.mla_shapes(cfg).values())
    dense = 3 * _mat((m["d"], m["ff"]))
    shared = 3 * _mat((m["d"], m["eff"]))
    router = _mat((m["d"], m["E"]))
    expert_pair = 3 * _mat((m["d"], m["eff"]))
    r = adapter["rank"]
    sh = W.mla_shapes(cfg)
    ad = sum(2.0 * r * sum(sh[W.MATRIX_LEAF[t]]) for t in adapter["matrices"])
    att_fwd = 2.0 * m["h"] * (m["qk"] + m["dv"]) * causal_pairs(seq) * batch
    head = _mat((m["d"], m["V"]))
    model = (tok * (L * (2 * mla + 3 * ad) + n_dense * 2 * dense
                    + n_moe * 2 * (shared + router))
             + 2 * pairs * expert_pair + 3 * L * att_fwd + 2 * tok * head)
    ttf = ttb = 0.0
    for t in adapter["matrices"]:
        k, n = sh[W.MATRIX_LEAF[t]]
        one = 2.0 * tok * (k * n + r * (k + n))
        ttf += 2 * one                         # forward and dx
        # x, W, A, B, out; then dy, W, A, B, dx
        ttb += 2 * BF16 * (tok * k + k * n + k * r + r * n + tok * n)
    aq = tok * m["h"] * m["qk"] * BF16        # q (and k: one per head)
    av = tok * m["h"] * m["dv"] * BF16        # v, o, dO
    lse = tok * m["h"] * 4
    fl_b = ((2 * aq + 2 * av + lse)           # fwd: q, k, v in; o, lse out
            + (2 * aq + 3 * av + 2 * lse)     # bwd: q, k, v, o, dO, lse, D
            + (2 * aq + av))                  # bwd: dq, dk, dv out
    w_experts = 3 * m["held"] * m["d"] * m["eff"] * BF16 * n_moe
    rows = pairs * 3 * (m["d"] + m["eff"]) * BF16
    return {"model_flops": model, "tokens": tok,
            "tt_linear_flops": L * ttf, "tt_linear_bytes": L * ttb,
            "flash_flops": L * 3 * att_fwd, "flash_bytes": L * fl_b,
            "experts_flops": 2 * pairs * expert_pair,
            "experts_bytes": 2 * (w_experts + rows)}
