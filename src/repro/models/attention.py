"""GQA/MQA attention with RoPE, KV cache, cross-attention and a
flash-style chunked path for long sequences; multi-head latent attention
(``mla_attention``, DeepSeek-V3 / Kimi K2) for training and prefill.

Three execution paths:
  * naive      — full score matrix; smoke tests / short sequences.
  * chunked    — queries processed in chunks under ``lax.map`` with
                 ``jax.checkpoint`` on the chunk body, so backward recomputes
                 scores per chunk: O(chunk × S) live memory (flash-attention
                 memory behaviour expressed in pure XLA; the Pallas kernel in
                 kernels/flash_attention.py is the TPU-native variant).
  * decode     — one query token against a sequence-sharded KV cache
                 (S over "model": GQA kv-heads are often < |model|, see
                 sharding/rules.py).

Adapter hook: q/k/v/o projections go through ``adapted_linear`` with matrix
types "<prefix>_q" etc., so MetaTT's M axis addresses them (paper §2.2).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig, YaRN
from repro.kernels import dispatch
from repro.kernels import quant as quant_lib
from repro.models.layers import (AdapterCtx, adapted_linear, apply_rope,
                                 rmsnorm, serve_rp_linear)
from repro.sharding import (BATCH, SEQ, current_mesh, get_serve_rp,
                            maybe_shard, serve_tp_gather, serve_tp_slice)

NEG_INF = -1e30


def _flash_ok(ctx: AdapterCtx) -> bool:
    """Pallas attention applies per device: under an AMBIENT >1-chip
    GSPMD mesh the sharded XLA paths (context-parallel scores,
    sequence-sharded caches) own the layout decisions, so the kernels
    stand down. Inside the serving engine's ``shard_map`` region there is
    no ambient mesh — each shard invokes the kernels on its LOCAL head
    group / cache shard (DESIGN.md §9), which is exactly the
    single-device shape they support."""
    pol = ctx.policy
    if pol is None or not pol.flash_attn:
        return False
    mesh = current_mesh()
    return mesh is None or mesh.size == 1


def _split_heads(x, n_heads, head_dim):
    b, t, _ = x.shape
    return x.reshape(b, t, n_heads, head_dim)


def _gqa_scores(q, k, scale):
    """q: (B,T,KV,G,hd)  k: (B,S,KV,hd) -> (B,KV,G,T,S) in f32."""
    return jnp.einsum("btkgh,bskh->bkgts", q, k,
                      preferred_element_type=jnp.float32) * scale


def _gqa_out(probs, v):
    """probs: (B,KV,G,T,S)  v: (B,S,KV,hd) -> (B,T,KV,G,hd)."""
    return jnp.einsum("bkgts,bskh->btkgh", probs.astype(v.dtype), v)


def _softmax_attend(q, k, v, mask, scale):
    s = _gqa_scores(q, k, scale)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _gqa_out(p, v)


def _causal_mask(t, s, q_offset=0):
    qi = jnp.arange(t)[:, None] + q_offset
    ki = jnp.arange(s)[None, :]
    return (qi >= ki)[None, None, None]         # (1,1,1,T,S)


def _chunked_attend(q, k, v, scale, causal, chunk):
    """Query-chunked attention: lax.map over q chunks, checkpointed chunk
    body -> flash-like live memory, recompute in backward."""
    b, t, kv, g, hd = q.shape
    s = k.shape[1]
    n = t // chunk

    @jax.checkpoint
    def one(args):
        qc, off = args                           # (B, chunk, KV, G, hd)
        mask = _causal_mask(chunk, s, off) if causal else None
        return _softmax_attend(qc, k, v, mask, scale)

    qs = q.reshape(b, n, chunk, kv, g, hd).transpose(1, 0, 2, 3, 4, 5)
    offs = jnp.arange(n) * chunk
    out = jax.lax.map(one, (qs, offs))           # (n, B, chunk, KV, G, dv)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, t, kv, g, -1)


def attention(x: jnp.ndarray, w: dict, ctx: AdapterCtx, cfg: ModelConfig, *,
              causal: bool = True,
              positions: Optional[jnp.ndarray] = None,
              prefix: str = "attn",
              use_rope: bool = True,
              chunk: int = 0,
              kv_x: Optional[jnp.ndarray] = None,
              cache: Optional[dict] = None,
              cache_pos: Optional[jnp.ndarray] = None,
              block_tables: Optional[jnp.ndarray] = None):
    """Returns (y, new_cache).

    Self-attention when kv_x is None; cross-attention otherwise (kv_x is the
    encoder output; cache then holds precomputed k/v and is not updated).
    Decode mode when ``cache is not None and x.shape[1] == 1`` for self-attn.
    Paged mode when ``block_tables`` is given: ``cache`` holds flat
    (N, KV, page, hd) block pools and x is a (B, C) chunk of co-batched
    decode/prefill tokens (see _paged_attend).
    """
    hd = cfg.resolved_head_dim
    n_h, n_kv = cfg.num_heads, cfg.num_kv_heads
    g = n_h // n_kv
    scale = hd ** -0.5
    b, t, _ = x.shape

    q = _split_heads(adapted_linear(x, w["wq"], ctx, f"{prefix}_q"), n_h, hd)
    if kv_x is None:
        kv_in = x
    else:
        kv_in = kv_x
    if cache is not None and kv_x is not None and "k" in cache:
        # cross-attention decode: reuse precomputed encoder k/v
        k, v = cache["k"], cache["v"]
        new_cache = cache
    else:
        k = _split_heads(adapted_linear(kv_in, w["wk"], ctx, f"{prefix}_k"),
                         n_kv, hd)
        v = _split_heads(adapted_linear(kv_in, w["wv"], ctx, f"{prefix}_v"),
                         n_kv, hd)
        new_cache = None

    if positions is None:
        positions = jnp.arange(t)
    if use_rope and kv_x is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        if new_cache is None or "k" not in (cache or {}):
            k = apply_rope(k, positions, cfg.rope_theta)

    if block_tables is not None and kv_x is None:
        assert cache is not None and positions.ndim == 2, \
            "paged attention needs a paged cache and (B, C) positions"
        return _paged_attend(x, q, k, v, w, ctx, cache, block_tables,
                             positions, n_h, hd)

    if cache is not None and kv_x is None:
        # ---- self-attention decode: new tokens into a full-length cache.
        # cache_pos is a scalar (whole batch at one position) or a (B,)
        # vector of per-row positions (the serving engine's decode slots —
        # each slot advances independently under continuous batching).
        # t > 1 is the speculative verifier's co-batched pass: token j of
        # row b lands at cache_pos[b]+j, and each query column attends
        # [0, cache_pos+j] through EXACTLY the t == 1 code — per-column
        # bit-identity with t sequential single-token steps (the q/k/v/o
        # projections and FFN still batch all t columns in one GEMM).
        # serve-TP (DESIGN.md §9): inside the engine's shard_map region
        # the cache arrives kv-head-sharded — slice this shard's
        # contiguous head group (q heads stay kv-aligned: H/tp = G·KV/tp)
        # and all-gather the per-head outputs below. No-ops unsharded.
        q = serve_tp_slice(q, 2)
        k = serve_tp_slice(k, 2)
        v = serve_tp_slice(v, 2)
        kv_l = k.shape[2]
        h_l = kv_l * g
        # the cache is head-major, (B, KV, S, hd), like the paged pools
        if jnp.ndim(cache_pos) == 0 and t == 1:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.transpose(0, 2, 1, 3).astype(cache["k"].dtype),
                (0, 0, cache_pos, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.transpose(0, 2, 1, 3).astype(cache["v"].dtype),
                (0, 0, cache_pos, 0))
        else:
            rows = jnp.arange(b)
            cp0 = jnp.broadcast_to(jnp.asarray(cache_pos), (b,))
            ck, cv = cache["k"], cache["v"]
            for j in range(t):
                # mode="drop": columns past the cache end (a draft chunk
                # overhanging cache_len) discard instead of clamping
                ck = ck.at[rows, :, cp0 + j].set(
                    k[:, j].astype(ck.dtype), mode="drop")
                cv = cv.at[rows, :, cp0 + j].set(
                    v[:, j].astype(cv.dtype), mode="drop")
        ck = maybe_shard(ck, BATCH, None, "model", None)
        cv = maybe_shard(cv, BATCH, None, "model", None)
        s_len = ck.shape[2]
        cp = jnp.broadcast_to(jnp.asarray(cache_pos), (b,))
        cols = []
        for j in range(t):
            if _flash_ok(ctx):
                # decode-shaped Pallas kernel: per-slot position masking
                # and the GQA broadcast happen inside the dispatch seam
                cols.append(dispatch.decode_attention(
                    q[:, j:j + 1], ck, cv, cp + j, policy=ctx.policy))
            else:
                qh = q[:, j:j + 1].reshape(b, 1, kv_l, g, hd)
                mask = (jnp.arange(s_len)[None, :] <= (cp + j)[:, None]
                        )[:, None, None, None, :]
                s = jnp.einsum("btkgh,bksh->bkgts", qh, ck,
                               preferred_element_type=jnp.float32) * scale
                p = jax.nn.softmax(jnp.where(mask, s, NEG_INF), axis=-1)
                cols.append(jnp.einsum("bkgts,bksh->btkgh",
                                       p.astype(cv.dtype), cv))
        out = cols[0] if t == 1 else jnp.concatenate(
            [c.reshape(b, 1, kv_l, g, hd) for c in cols], axis=1)
        out = out.reshape(b, t, h_l, hd)
        # row-parallel serve TP (DESIGN.md §11): keep the local head
        # group — the wo epilogue below row-slices and psums instead of
        # all-gathering the per-head outputs here
        if not get_serve_rp():
            out = serve_tp_gather(out, 2)
        new_cache = {"k": ck, "v": cv}
    else:
        # ---- train / prefill / cross
        mesh = current_mesh()
        n_model = (mesh.shape["model"] if mesh is not None
                   and "model" in mesh.axis_names else 1)
        # TP applies when the QUERY heads divide the model axis (k/v may
        # stay replicated under GQA — they are the cheap operands)
        heads_shardable = n_model == 1 or n_h % n_model == 0
        q = maybe_shard(q, BATCH, None, "model", None)
        k = maybe_shard(k, BATCH, None, "model", None)
        v = maybe_shard(v, BATCH, None, "model", None)
        qh = q.reshape(b, t, n_kv, g, hd)
        eff_causal = causal and kv_x is None
        if _flash_ok(ctx) and (not eff_causal or t == k.shape[1]):
            # train/prefill flash route: blockwise online softmax — the
            # (T, S) score matrix stays out of HBM in both directions (the
            # backward runs the two-pass recompute kernels from the stashed
            # per-row lse, see kernels/dispatch.py)
            out = dispatch.flash_attention(q, k, v, causal=eff_causal,
                                           policy=ctx.policy)
            out = out.reshape(b, t, n_kv, g, hd)
        elif (not heads_shardable and t % n_model == 0
                and t // n_model <= max(chunk, 512)):
            # §Perf iteration W1 (whisper: 20 heads vs 16-way model axis):
            # context-parallel scores — shard the query-T axis of the score
            # tensor over "model"; each chip computes a T/16 query stripe
            # against the full KV instead of all heads redundantly.
            mask = _causal_mask(t, k.shape[1]) if (causal and kv_x is None) \
                else None
            s = _gqa_scores(qh, k, scale)
            s = maybe_shard(s, BATCH, None, None, "model", None)
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            p = maybe_shard(p, BATCH, None, None, "model", None)
            out = _gqa_out(p, v)
        elif chunk and t % chunk == 0 and t > chunk:
            out = _chunked_attend(qh, k, v, scale, causal and kv_x is None,
                                  chunk)
        else:
            mask = _causal_mask(t, k.shape[1]) if (causal and kv_x is None) \
                else None
            out = _softmax_attend(qh, k, v, mask, scale)
        if cache is not None and kv_x is not None and new_cache is None:
            new_cache = {"k": k, "v": v}     # prefill of a cross cache
        elif kv_x is None and cache is None and new_cache is None:
            # prefill returns the cache to the caller, head-major like
            # init_cache
            new_cache = {"k": k.transpose(0, 2, 1, 3),
                         "v": v.transpose(0, 2, 1, 3)}

    # row-parallel: out still carries only this shard's head group —
    # contiguous head slices align with contiguous wo rows, so the
    # row-sliced projection + psum reconstructs the full epilogue
    out = out.reshape(b, t, -1)
    if get_serve_rp():
        y = serve_rp_linear(out, w["wo"], ctx, f"{prefix}_o")
    else:
        y = adapted_linear(out, w["wo"], ctx, f"{prefix}_o")
    return maybe_shard(y, BATCH, SEQ, None), new_cache


def _paged_attend(x, q, k, v, w, ctx: AdapterCtx, cache: dict,
                  block_tables, positions, n_h: int, hd: int):
    """Paged-cache step: scatter the chunk's k/v into the flat block pools
    by block table, then attend with per-slot per-query position masks.

    x: (B, C, d_model) — C co-batched tokens per slot (decode: 1 real
    token; chunked prefill: up to C prompt tokens), token c of slot b at
    absolute position positions[b, c]; q/k/v: projected+RoPE'd heads;
    cache: {"k","v"} (N, KV, page, hd) head-major pools shared by every
    slot (the paged kernel's layout: each block's last two dims are whole);
    block_tables: (B, P) int32, sentinel >= N for unallocated pages.

    Write-then-attend: a token's own k/v lands in its cell before the
    masked attention reads it, so cells holding stale data (pad columns of
    earlier steps) are always overwritten by the step that owns their
    position before any query's mask reaches them. Writes through
    sentinel or out-of-table pages drop (``mode="drop"``) — that is what
    keeps an evicted slot's garbage out of blocks reassigned to new
    requests.

    int8 KV mode (cache carries ``k_s``/``v_s`` per-cell scale pools,
    DESIGN.md §8): the RoPE'd k and the v quantize at write time — one
    amax/127 scale per (token, kv-head) cell — and scales scatter through
    the SAME block table as the cells, so COW and prefix sharing
    round-trip the quantized representation; attention dequantizes
    in-register inside the paged kernel.

    Serve-TP (DESIGN.md §9): inside the engine's shard_map region the
    pools arrive kv-head-sharded; this shard slices its contiguous
    q/k/v head group (post-RoPE — per-head ops commute with the slice),
    scatters/attends against its LOCAL pool shard only, and the per-head
    outputs are all-gathered before the replicated output projection.
    Block ids, positions and masks are shard-independent, so the
    host-side BlockManager never sees the mesh.
    """
    b, t, _ = x.shape
    q = serve_tp_slice(q, 2)
    k = serve_tp_slice(k, 2)
    v = serve_tp_slice(v, 2)
    n_blocks, page = cache["k"].shape[0], cache["k"].shape[2]
    p_tab = block_tables.shape[1]
    pidx = positions // page                                 # (B, C)
    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(pidx, 0, p_tab - 1), axis=1)
    blk = jnp.where(pidx < p_tab, blk, n_blocks)             # drop, not clamp
    off = positions % page
    quantized = "k_s" in cache
    if quantized:
        k, k_s = quant_lib.quantize_kv(k)
        v, v_s = quant_lib.quantize_kv(v)
    # cell (blk, :, off) of a (N, KV, page, ...) pool takes token (b, c)'s
    # (KV, ...) heads
    ck = cache["k"].at[blk, :, off].set(k.astype(cache["k"].dtype),
                                        mode="drop")
    cv = cache["v"].at[blk, :, off].set(v.astype(cache["v"].dtype),
                                        mode="drop")
    new_cache = {"k": ck, "v": cv}
    scales = {}
    if quantized:
        # scale pools are (N, KV, 1, page): cell (blk, :, 0, off)
        new_cache["k_s"] = cache["k_s"].at[blk, :, 0, off].set(
            k_s, mode="drop")
        new_cache["v_s"] = cache["v_s"].at[blk, :, 0, off].set(
            v_s, mode="drop")
        scales = dict(k_scale=new_cache["k_s"], v_scale=new_cache["v_s"])
    pol = ctx.policy if _flash_ok(ctx) else None
    out = dispatch.paged_decode_attention(q, ck, cv, block_tables,
                                          positions[:, 0], policy=pol,
                                          **scales)
    if get_serve_rp():
        # row-parallel (DESIGN.md §11): skip the head all-gather — wo
        # row-slices against the local head group and psums partials
        out = out.reshape(b, t, -1)
        y = serve_rp_linear(out, w["wo"], ctx, "attn_o")
        return maybe_shard(y, BATCH, SEQ, None), new_cache
    out = serve_tp_gather(out, 2)
    out = out.reshape(b, t, n_h * hd)
    y = adapted_linear(out, w["wo"], ctx, "attn_o")
    return maybe_shard(y, BATCH, SEQ, None), new_cache


def init_cache(cfg: ModelConfig, batch: int, length: int, dtype) -> dict:
    """Dense self-attention KV cache, head-major (B, KV, S, hd) like the
    paged pools: the decode kernel reads whole (S-tile, hd) blocks."""
    hd = cfg.resolved_head_dim
    shape = (batch, cfg.num_kv_heads, length, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_cache(cfg: ModelConfig, num_blocks: int, page_size: int,
                     dtype, kv_quant: bool = False) -> dict:
    """Flat per-layer KV block pool: (num_blocks, KV, page, hd), head-major
    so the paged kernel DMAs one whole (page, hd) tile per (block, head).
    Which request owns which block lives host-side
    (serving/block_manager.py). ``kv_quant`` stores cells as int8 plus
    per-cell f32 scale pools (``k_s``/``v_s``, (num_blocks, KV, 1, page):
    one (1, page) row per block and head, the tile the paged kernel reads)
    in the same block layout."""
    hd = cfg.resolved_head_dim
    shape = (num_blocks, cfg.num_kv_heads, page_size, hd)
    if kv_quant:
        s_shape = (num_blocks, cfg.num_kv_heads, 1, page_size)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(s_shape, jnp.float32),
                "v_s": jnp.zeros(s_shape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V3 / Kimi K2 block), train/prefill
# ---------------------------------------------------------------------------


def yarn_mscale(s: float, m: float) -> float:
    """YaRN's attention temperature: ``mscale(s, m) = 0.1·m·ln s + 1``
    (1 for s <= 1)."""
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def yarn_inv_freq(d: int, base: float, y: Optional[YaRN]) -> jnp.ndarray:
    """Rotary frequencies of a ``d``-wide rope part, (d/2,) f32.

    Plain rope: ``base^(-2i/d)``. With YaRN (factor s, original length
    ``orig``):
        freq_extra[i] = base^(-2i/d),   freq_inter = freq_extra / s
        corr(b) = d·ln(orig / (2π·b)) / (2·ln base)
        low = floor(corr(beta_fast)), high = ceil(corr(beta_slow))
              (clipped to [0, d-1]; high += 0.001 if equal)
        ramp[i] = clip((i - low) / (high - low), 0, 1)
        inv_freq = freq_inter·ramp + freq_extra·(1 - ramp)
    Kimi K2 (d 64, base 50000, s 32, orig 4096, beta 1/1): low 19, high
    20."""
    i = jnp.arange(d // 2, dtype=jnp.float32)
    extra = base ** (-2.0 * i / d)
    if y is None:
        return extra

    def corr(b):
        return d * math.log(y.original_max_position / (2 * math.pi * b)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (extra / y.factor) * ramp + extra * (1.0 - ramp)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``qk_head_dim^-0.5``, times ``mscale(s, mscale_all_dim)²`` under
    YaRN (Kimi K2: 192^-0.5 · 1.8133)."""
    y = cfg.rope_scaling
    sc = cfg.qk_head_dim ** -0.5
    if y is not None and y.mscale_all_dim:
        sc *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return sc


def mla_rope(x: jnp.ndarray, positions: jnp.ndarray,
             cfg: ModelConfig) -> jnp.ndarray:
    """Rotate x (B, T, n, d_rope) at ``positions`` ((T,) or (B, T)) with
    YaRN's frequencies; cos and sin carry YaRN's factor
    ``mscale(s, mscale) / mscale(s, mscale_all_dim)`` (1 for Kimi K2).
    Layout: half-split pairs (column i with column i + d/2), the layout the
    rest of the program uses; the published checkpoint interleaves its
    pairs, a fixed permutation of the rope columns of q_b and kv_a."""
    y = cfg.rope_scaling
    amp = 1.0 if y is None else (yarn_mscale(y.factor, y.mscale)
                                 / yarn_mscale(y.factor, y.mscale_all_dim))
    return apply_rope(x, positions, cfg.rope_theta,
                      freqs=yarn_inv_freq(x.shape[-1], cfg.rope_theta, y),
                      amp=amp)


def mla_attention(x: jnp.ndarray, w: dict, ctx: AdapterCtx,
                  cfg: ModelConfig, *, positions=None, chunk: int = 0):
    """Multi-head latent attention over a whole causal sequence.
    x: (B, T, d_model) -> (B, T, d_model).

        c_q = q_a(x) ;  q = q_b(RMSNorm(c_q))          (B, T, H, 128 + 64)
        [c, k_pe] = kv_a(x)                  c: 512 wide, k_pe: 64, one head
        [k_nope, v] = kv_b(RMSNorm(c))                 (B, T, H, 128 + 128)
        q = [q_nope, rope(q_pe)] ;  k = [k_nope, rope(k_pe)]
                                      (rope(k_pe) broadcast over the heads)
        o = softmax(q·kᵀ · scale, causal) · v ;  y = o_proj(o)

    Both RMSNorms use ``norm_eps`` and no bias; ``scale`` is
    ``mla_softmax_scale``; rope is ``mla_rope``. The q_a, q_b, kv_a, kv_b
    and o matrices are the adaptable types ``mla_q_a``, ``mla_q_b``,
    ``mla_kv_a``, ``mla_kv_b`` and ``mla_o``. On the kernel path the
    attention runs in the flash kernels with q/k head dim 192 and v head
    dim 128; elsewhere in plain XLA."""
    b, t, _ = x.shape
    h, dn = cfg.num_heads, cfg.qk_nope_head_dim
    dr, dv, r = cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    if positions is None:
        positions = jnp.arange(t)
    with jax.named_scope("mla"):
        cq = adapted_linear(x, w["wq_a"], ctx, "mla_q_a")
        q = adapted_linear(rmsnorm(cq, w["q_norm"], cfg.norm_eps),
                           w["wq_b"], ctx, "mla_q_b").reshape(b, t, h,
                                                              dn + dr)
        ckv = adapted_linear(x, w["wkv_a"], ctx, "mla_kv_a")
        c, k_pe = ckv[..., :r], ckv[..., r:]
        kv = adapted_linear(rmsnorm(c, w["kv_norm"], cfg.norm_eps),
                            w["wkv_b"], ctx, "mla_kv_b").reshape(b, t, h,
                                                                 dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        q_pe = mla_rope(q[..., dn:], positions, cfg)
        k_pe = mla_rope(k_pe[:, :, None, :], positions, cfg)
        q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (b, t, h, dr))], axis=-1)
        scale = mla_softmax_scale(cfg)
        if _flash_ok(ctx):
            out = dispatch.flash_attention(q, k, v, causal=True,
                                           policy=ctx.policy, scale=scale)
        elif chunk and t % chunk == 0 and t > chunk:
            out = _chunked_attend(q[:, :, :, None], k, v, scale, True,
                                  chunk)
        else:
            out = _softmax_attend(q[:, :, :, None], k, v,
                                  _causal_mask(t, t), scale)
        y = adapted_linear(out.reshape(b, t, h * dv), w["wo"], ctx, "mla_o")
    return maybe_shard(y, BATCH, SEQ, None)
