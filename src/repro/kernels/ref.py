"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.quant import dequantize_int8


def tt_linear_ref(x: jnp.ndarray, w: jnp.ndarray, a: jnp.ndarray,
                  b: jnp.ndarray, alpha: float = 1.0) -> jnp.ndarray:
    """y = x·W + α·(x·A)·B  — the adapted linear layer (paper Eq. (5) with
    the middle cores pre-merged into A = G1·G2[l]·G3[m], B = G4)."""
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    p = jnp.dot(x, a, preferred_element_type=jnp.float32)
    y = y + alpha * jnp.dot(p, b.astype(p.dtype),
                            preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def tt_linear_q_ref(x: jnp.ndarray, wq: jnp.ndarray, scale: jnp.ndarray,
                    a: jnp.ndarray, b: jnp.ndarray,
                    alpha: float = 1.0) -> jnp.ndarray:
    """w8a16 oracle: dequantize the int8 base (per-channel or group-wise
    scales — quant.py owns the layout rule) then run the fp adapted
    linear. The Pallas twin dequantizes the W tile in-register; same
    math, same f32 accumulation."""
    return tt_linear_ref(x, dequantize_int8(wq, scale), a, b, alpha)


def tt_linear_batched_a_q_ref(x: jnp.ndarray, wq: jnp.ndarray,
                              scale: jnp.ndarray, a: jnp.ndarray,
                              b: jnp.ndarray,
                              alpha: float = 1.0) -> jnp.ndarray:
    """Per-row-A (slot-task-routed) w8a16 oracle. x: (S, K); a: (S, K, r)."""
    w = dequantize_int8(wq, scale)
    p = jnp.einsum("sk,skr->sr", x, a.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    y = y + alpha * jnp.dot(p, b.astype(p.dtype),
                            preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True) -> jnp.ndarray:
    """q,k,v: (B, H, T, d) -> (B, H, T, d), softmax in f32."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        t, s_len = q.shape[2], k.shape[2]
        mask = jnp.arange(t)[:, None] >= jnp.arange(s_len)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def flash_attention_bwd_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            o: jnp.ndarray, lse: jnp.ndarray,
                            g: jnp.ndarray, causal: bool = True):
    """Recompute-from-lse twin of the blockwise flash backward.

    q, o, g: (B, H, T, d); k, v: (B, H, S, d); lse: (B, H, T) f32 per-row
    log-sum-exp stashed by the forward. Returns (dq, dk, dv) via the same
    math the Pallas kernels run — p = exp(s − lse), ds = p·(dp − D)·scale
    with D = rowsum(g ⊙ o) — including the dtype casts of p/ds back to the
    operand dtype before each contraction, so bf16 parity with the kernel
    is exact rather than merely close.
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        t, s_len = q.shape[2], k.shape[2]
        mask = jnp.arange(t)[:, None] >= jnp.arange(s_len)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jnp.exp(s - lse.astype(jnp.float32)[..., None])      # (B,H,T,S)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                  # (B,H,T)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p.astype(g.dtype), g,
                    preferred_element_type=jnp.float32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g, v,
                    preferred_element_type=jnp.float32)
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds.astype(k.dtype), k,
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds.astype(q.dtype), q,
                    preferred_element_type=jnp.float32)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def decode_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         pos: jnp.ndarray) -> jnp.ndarray:
    """Single-token cached decode. q: (BH, d); k, v: (BH, S, d);
    pos: (BH,) — each row attends cache cells [0, pos[row]]."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bd,bsd->bs", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(k.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bs,bsd->bd", p.astype(v.dtype), v)


def paged_decode_attention_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                               v_cache: jnp.ndarray, tables: jnp.ndarray,
                               pos: jnp.ndarray) -> jnp.ndarray:
    """Block-table attention over a paged KV cache (the Pallas twin's
    allclose target).

    q: (B, C, H, d) — C co-batched query tokens per slot, slot b's query c
    at absolute position pos[b] + c; k_cache, v_cache: (N, KV, page, d)
    flat block pools; tables: (B, P) int32 logical-page -> physical-block
    map (entries may be an out-of-range sentinel: the gather clamps and
    the position mask hides whatever it reads); pos: (B,) base positions.
    Returns (B, C, H, d): query c attends cache cells [0, pos[b] + c].
    """
    b, c, h, d = q.shape
    n, kv, page, _ = k_cache.shape
    g = h // kv
    tbl = jnp.clip(tables, 0, n - 1)
    # (B, P, KV, page, d) -> (B, S, KV, d) with S = P * page cells in
    # logical-position order — same valid set, same order as a dense cache
    kg = k_cache[tbl].transpose(0, 1, 3, 2, 4).reshape(b, -1, kv, d)
    vg = v_cache[tbl].transpose(0, 1, 3, 2, 4).reshape(b, -1, kv, d)
    if g > 1:
        kg = jnp.repeat(kg, g, axis=2)
        vg = jnp.repeat(vg, g, axis=2)
    scale = d ** -0.5
    s = jnp.einsum("bchd,bshd->bhcs", q, kg,
                   preferred_element_type=jnp.float32) * scale
    ki = jnp.arange(kg.shape[1])
    qpos = pos[:, None] + jnp.arange(c)[None, :]            # (B, C)
    mask = ki[None, None, :] <= qpos[:, :, None]            # (B, C, S)
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhcs,bshd->bchd", p.astype(vg.dtype), vg)
