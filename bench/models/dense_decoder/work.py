"""Operations and bytes that the dense decoder's work requires, from the
shapes alone, counted as ``bench.lib.flops`` says.

The two entry points, ``train_step`` and ``serve_forward``, return the dicts
that the readers in ``bench/metrics/`` read. Both take ``counts``, the
counters that the run reported (``seam.counts``): the dense decoder's work
follows from the shapes alone, and it reads none.
"""
from __future__ import annotations

import numpy as np

from bench.lib.flops import BF16, causal_pairs

from . import weights as W


def matmul_per_token(cfg: dict) -> float:
    """Base matmul operations of one layer for one token."""
    return float(sum(2 * a * b for a, b in W.leaf_shapes(cfg).values()))


def adapter_per_token(cfg: dict, adapter: dict) -> float:
    """Adapter operations of one layer for one token: x A then (.) B on
    every adapted matrix, rank r."""
    r = adapter["rank"]
    sh = W.leaf_shapes(cfg)
    return float(sum(2 * r * sum(sh[W.MATRIX_LEAF[m]])
                     for m in adapter["matrices"]))


def attn_per_query(cfg: dict, ctx) -> np.ndarray:
    """QK^T and PV operations of one layer for a query that attends ``ctx``
    keys."""
    m = W.dims(cfg)
    return 4.0 * m["h"] * m["hd"] * np.asarray(ctx, np.float64)


def head_per_token(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


# ---------------------------------------------------------------- serving

def serve_forward(cfg: dict, adapter: dict, reqs, chunk: int,
                  counts: dict) -> dict:
    """Work of serving ``reqs``: an iterable of (prompt_len, n_generated,
    prompt_done) per request. A forward pass runs at every prompt position
    computed and at every generated token fed back (positions 0 ..
    plen + n - 2); a readout runs once per generated token.

    Returns {"model_flops", "paged_flops", "paged_bytes"}: the whole
    model's operations, and the paged attention kernel's required
    operations and bytes (K/V of the context read once per step and slot;
    the prompt is fed ``chunk`` tokens per step)."""
    m = W.dims(cfg)
    L = m["L"]
    per_tok = L * (matmul_per_token(cfg) + adapter_per_token(cfg, adapter))
    kv_row = 2 * m["kvd"] * BF16              # K and V of one position
    q_row = 2 * m["q"] * BF16                 # q in, output out
    model = paged_f = paged_b = 0.0
    for plen, n, done in reqs:
        fed = done + max(n - 1, 0)            # positions forwarded
        if fed <= 0:
            continue
        ctx = np.arange(1, fed + 1)
        att = float(attn_per_query(cfg, ctx).sum()) * L
        model += fed * per_tok + att + n * head_per_token(cfg)
        paged_f += att
        # prompt chunks: one read of the context per chunk
        ends = np.minimum(np.arange(chunk, done + chunk, chunk), done)
        b = float(ends.sum()) * kv_row + done * q_row
        # decode steps: one read of the context per generated token fed
        dec = np.arange(done + 1, fed + 1)
        b += float(dec.sum()) * kv_row + len(dec) * q_row
        paged_b += b * L
    return {"model_flops": model, "paged_flops": paged_f,
            "paged_bytes": paged_b}


# --------------------------------------------------------------- training

def train_step(cfg: dict, adapter: dict, batch: int, seq: int,
               counts: dict) -> dict:
    """Work of one training step on ``batch`` rows of ``seq`` tokens.

    model_flops: the forward pass, plus the backward pass for activations
    (every matmul's dx, attention's four backward products, the readout's
    dh) and for the adapter's parameters. tt_linear_*: the fused adapted
    linears, forward and dx, on the adapted matrices. flash_*: causal flash
    attention, forward (QK^T, PV) and backward (dV, dP, dQ, dK)."""
    m = W.dims(cfg)
    L, tok = m["L"], batch * seq
    mat = matmul_per_token(cfg)
    ad = adapter_per_token(cfg, adapter)
    att_fwd = 4.0 * m["h"] * m["hd"] * causal_pairs(seq) * batch
    head = head_per_token(cfg)
    model = tok * L * (2 * mat + 3 * ad) + 3 * L * att_fwd + 2 * tok * head
    sh = W.leaf_shapes(cfg)
    r = adapter["rank"]
    ttf = ttb = 0.0
    for name in adapter["matrices"]:
        k, n = sh[W.MATRIX_LEAF[name]]
        one = 2.0 * tok * (k * n + r * (k + n))
        ttf += 2 * one                         # forward and dx
        # x, W, A, B, out; then dy, W, A, B, dx
        ttb += 2 * BF16 * (tok * k + k * n + k * r + r * n + tok * n)
    act = tok * m["q"] * BF16                 # one (tokens, heads*hd) tensor
    kv = tok * m["kvd"] * BF16
    lse = tok * m["h"] * 4
    fl_b = (act + 2 * kv + act + lse          # fwd: q, k, v in; o, lse out
            + 2 * act + 2 * kv + 2 * lse      # bwd: q, o, dO, k, v, lse, D
            + act + act + 2 * kv)             # bwd: dO; dq, dk, dv out
    return {"model_flops": model, "tokens": tok,
            "tt_linear_flops": L * ttf, "tt_linear_bytes": L * ttb,
            "flash_flops": L * 3 * att_fwd, "flash_bytes": L * fl_b}
