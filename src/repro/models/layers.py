"""Shared building blocks for the model zoo."""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels import quant as quant_lib
from repro.peft import api as peft_api
from repro.sharding import (BATCH, SEQ, get_serve_rp, get_serve_tp,
                            maybe_shard, serve_psum, serve_tp_slice)


@dataclasses.dataclass
class AdapterCtx:
    """Everything a layer needs to apply the (global) adapter.

    spec is static; broadcast is closed over the scan; layer is this layer's
    slice of the per-layer factors (sliced by the scan / by position);
    task is the MTL task index (4+1d) — None otherwise; policy is the
    resolved kernel-dispatch policy (kernels/dispatch.py) — None keeps the
    unfused reference path.
    """
    spec: peft_api.AdapterSpec
    broadcast: Any
    layer: Any
    task: Optional[Any] = None
    policy: Optional[dispatch.KernelPolicy] = None

    def at(self, layer_slice) -> "AdapterCtx":
        return AdapterCtx(self.spec, self.broadcast, layer_slice, self.task,
                          self.policy)


NO_ADAPTER = AdapterCtx(peft_api.NONE, {}, None)


def adapted_linear(x: jnp.ndarray, w: jnp.ndarray, ctx: AdapterCtx, m: str,
                   b: jnp.ndarray | None = None) -> jnp.ndarray:
    """y = x·W (+ bias) + adapter delta for matrix type ``m``.

    This is the paper's Eq. (5): the frozen pre-trained map plus the TT
    (or baseline-adapter) low-rank update. When the dispatch policy routes
    to Pallas, the adapter is folded into lora-form (A, B) and base matmul
    + rank-r epilogue run as ONE fused kernel — the delta is applied while
    the output tile is still in VMEM instead of three HBM round-trips of
    the (M, N) output (kernels/tt_linear.py).

    ``w`` may be a packed int8 leaf (``{"q8", "scale"}``, kernels/quant.py
    — the serving engine quantizes the frozen base once at construction):
    adapted matmuls then run the fused w8a16 kernels (int8 W tile
    dequantized in-register, fp rank-r epilogue); unadapted ones
    dequantize into the plain XLA matmul (still int8 HBM reads — XLA
    fuses the scale multiply into the GEMM's operand load).
    """
    pol = ctx.policy
    wq = quant_lib.is_quantized(w)
    if pol is not None and pol.fused_linear and ctx.spec.adapts(m):
        form = peft_api.lora_form_factors(ctx.spec, ctx.broadcast, ctx.layer,
                                          m, task=ctx.task)
        if form is not None:
            fa, fb, alpha = form
            fa, fb = fa.astype(x.dtype), fb.astype(x.dtype)
            if fa.ndim == 3:      # (B,) task vector: per-slot A operand
                y = (dispatch.tt_linear_batched_a_q(x, w, fa, fb,
                                                    alpha=alpha, policy=pol)
                     if wq else
                     dispatch.tt_linear_batched_a(x, w.astype(x.dtype), fa,
                                                  fb, alpha=alpha,
                                                  policy=pol))
            else:
                y = (dispatch.tt_linear_q(x, w, fa, fb, alpha=alpha,
                                          policy=pol)
                     if wq else
                     dispatch.tt_linear(x, w.astype(x.dtype), fa, fb,
                                        alpha=alpha, policy=pol))
            if b is not None:
                y = y + b.astype(y.dtype)
            return y
    wd = quant_lib.dequantize(w, x.dtype) if wq else w.astype(x.dtype)
    y = x @ wd
    if b is not None:
        y = y + b.astype(x.dtype)
    d = peft_api.adapter_delta(ctx.spec, ctx.broadcast, ctx.layer, x, m,
                               task=ctx.task)
    if d is not None:
        y = y + d.astype(y.dtype)
    return y


# --------------------------------------------------------------------------
# row-/column-parallel serve-TP linears (DESIGN.md §11). The default serve
# TP keeps every matmul full-width and replicated, paying an all-gather of
# the attention-head outputs instead; behind ServeConfig(row_parallel=True)
# the engine traces these variants: the FIRST matmul of a pair splits its
# OUTPUT columns per shard (exact — no reduction order changes) and the
# SECOND splits its INPUT rows, producing per-shard partial sums that one
# psum reduces. The psum reorders the K-axis reduction, which is why this
# mode is near-parity (~1e-3 in bf16) rather than bit-exact — the
# column-only mode stays the oracle.
# --------------------------------------------------------------------------


def _slice_w(w, axis: int):
    """This shard's stripe of a (possibly int8-packed) weight leaf along
    ``axis`` (negative, from the end). Packed leaves slice the int8 cells;
    per-output-channel scales slice with N (axis -1) and are K-independent
    under a row slice (axis -2) — grouped scales tile K, so ServeConfig
    forbids row_parallel with group_size > 0."""
    if quant_lib.is_quantized(w):
        q = serve_tp_slice(w["q8"], w["q8"].ndim + axis)
        s = w["scale"]
        if axis == -1:
            s = serve_tp_slice(s, s.ndim - 1)
        return {"q8": q, "scale": s}
    return serve_tp_slice(w, w.ndim + axis)


def _apply_linear(x, w, form, pol):
    """base matmul + optional lora-form (A, B, alpha) delta, routed
    through the fused kernels when the policy allows (the sliced-operand
    twin of adapted_linear's fused branch; no bias — rp adds it once
    after the psum)."""
    wq = quant_lib.is_quantized(w)
    if form is not None:
        fa, fb, alpha = form
        fa, fb = fa.astype(x.dtype), fb.astype(x.dtype)
        if pol is not None and pol.fused_linear:
            if fa.ndim == 3:
                return (dispatch.tt_linear_batched_a_q(
                    x, w, fa, fb, alpha=alpha, policy=pol) if wq else
                    dispatch.tt_linear_batched_a(
                        x, w.astype(x.dtype), fa, fb, alpha=alpha,
                        policy=pol))
            return (dispatch.tt_linear_q(x, w, fa, fb, alpha=alpha,
                                         policy=pol) if wq else
                    dispatch.tt_linear(x, w.astype(x.dtype), fa, fb,
                                       alpha=alpha, policy=pol))
        wd = quant_lib.dequantize(w, x.dtype) if wq else w.astype(x.dtype)
        y = x @ wd
        if fa.ndim == 3:        # (B,) task vector: per-slot A operand
            p = jnp.einsum("btk,bkr->btr", x, fa)
        else:
            p = x @ fa
        return y + alpha * (p @ fb)
    wd = quant_lib.dequantize(w, x.dtype) if wq else w.astype(x.dtype)
    return x @ wd


def _lora_form(ctx: AdapterCtx, m: str):
    return (peft_api.lora_form_factors(ctx.spec, ctx.broadcast, ctx.layer,
                                       m, task=ctx.task)
            if ctx.spec.adapts(m) else None)


def serve_cp_linear(x: jnp.ndarray, w, ctx: AdapterCtx, m: str,
                    b=None) -> jnp.ndarray:
    """Column-parallel adapted linear: this shard computes its contiguous
    N/tp output stripe (weight columns, lora-form B columns and the bias
    slice with it). Bitwise-exact per column. Falls back to
    adapted_linear outside a serve-TP trace context."""
    if get_serve_tp() is None:
        return adapted_linear(x, w, ctx, m, b)
    form = _lora_form(ctx, m)
    if form is not None:
        fa, fb, alpha = form
        form = (fa, serve_tp_slice(fb, fb.ndim - 1), alpha)
    y = _apply_linear(x, _slice_w(w, -1), form, ctx.policy)
    if b is not None:
        y = y + serve_tp_slice(b, b.ndim - 1).astype(y.dtype)
    return y


def serve_rp_linear(x: jnp.ndarray, w, ctx: AdapterCtx, m: str,
                    b=None) -> jnp.ndarray:
    """Row-parallel adapted linear: ``x`` arrives SHARDED on its last dim
    (this shard's K/tp contraction rows — attention's local head group,
    the FFN's local d_ff stripe), the weight's K rows and the lora-form A
    rows slice to match, and ONE psum reduces the partial outputs; the
    bias adds once after. Falls back to adapted_linear outside a serve-TP
    trace context (x is then full-width)."""
    if get_serve_tp() is None:
        return adapted_linear(x, w, ctx, m, b)
    form = _lora_form(ctx, m)
    if form is not None:
        fa, fb, alpha = form
        form = (serve_tp_slice(fa, fa.ndim - 2), fb, alpha)
    y = serve_psum(_apply_linear(x, _slice_w(w, -2), form, ctx.policy))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def layernorm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
              eps: float = 1e-5) -> jnp.ndarray:
    h = x.astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean((h - mu) ** 2, axis=-1, keepdims=True)
    h = (h - mu) * jax.lax.rsqrt(var + eps)
    return (h * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def norm(x, weights: dict, eps: float):
    if "b" in weights:
        return layernorm(x, weights["w"], weights["b"], eps)
    return rmsnorm(x, weights["w"], eps)


# --------------------------------------------------------------------------
# RoPE (half-split / llama convention)
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               freqs: Optional[jnp.ndarray] = None,
               amp: float = 1.0) -> jnp.ndarray:
    """x: (B, T, n_heads, head_dim); positions: (B, T) or (T,). ``freqs``
    (hd/2,) replaces the plain ``theta`` frequencies, and ``amp`` scales
    cos and sin (YaRN, models/attention.py::mla_rope)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                   # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B?, T, hd/2)
    if ang.ndim == 2:
        ang = ang[None]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    if amp != 1.0:
        cos, sin = cos * amp, sin * amp
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# dense FFN variants
# --------------------------------------------------------------------------

def dense_ffn(x: jnp.ndarray, w: dict, ctx: AdapterCtx, kind: str) -> jnp.ndarray:
    """kind: swiglu | geglu | gelu. Adapted matrix types ffn_up / ffn_down
    (off by default — paper adapts attention q/v only, App. A.2).

    Under row-parallel serve TP (DESIGN.md §11) the whole FFN runs
    megatron-style: wg/wu column-parallel (each shard activates its own
    d_ff/tp stripe), wd row-parallel with the psum epilogue — the one
    place the default serve TP leaves real decode FLOPs fully replicated."""
    if get_serve_rp():
        if kind in ("swiglu", "geglu"):
            act = jax.nn.silu if kind == "swiglu" else (
                lambda v: jax.nn.gelu(v, approximate=True))
            h = act(serve_cp_linear(x, w["wg"], ctx, "ffn_gate")) \
                * serve_cp_linear(x, w["wu"], ctx, "ffn_up")
        elif kind == "gelu":
            h = jax.nn.gelu(serve_cp_linear(x, w["wu"], ctx, "ffn_up"),
                            approximate=True)
        else:
            raise ValueError(kind)
        return serve_rp_linear(h, w["wd"], ctx, "ffn_down")
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else (
            lambda v: jax.nn.gelu(v, approximate=True))
        g = act(adapted_linear(x, w["wg"], ctx, "ffn_gate"))
        u = adapted_linear(x, w["wu"], ctx, "ffn_up")
        h = g * u
    elif kind == "gelu":
        h = jax.nn.gelu(adapted_linear(x, w["wu"], ctx, "ffn_up"),
                        approximate=True)
    else:
        raise ValueError(kind)
    h = maybe_shard(h, BATCH, None, "model")
    return adapted_linear(h, w["wd"], ctx, "ffn_down")


def embed_tokens(tokens: jnp.ndarray, embed: jnp.ndarray,
                 compute_dtype) -> jnp.ndarray:
    return jnp.take(embed, tokens, axis=0).astype(compute_dtype)


def lm_logits(h: jnp.ndarray, embed: jnp.ndarray) -> jnp.ndarray:
    """Tied-embedding readout, always vocab-sharded on "model".

    The activations are gathered (MBs) rather than the table (GBs): under
    sequence parallelism h arrives T-sharded on "model" and XLA all-gathers
    it here; constraining logits T-sharded instead would force an all-gather
    (and on CPU an f32 upcast) of the ENTIRE (V, d) embedding — a ~19 GB/chip
    mistake the kimi-k2 dry-run exposed (EXPERIMENTS.md §Perf, iteration 0).
    """
    h = maybe_shard(h, BATCH, None, None)
    logits = h @ embed.T.astype(h.dtype)
    return maybe_shard(logits, BATCH, None, "model")
