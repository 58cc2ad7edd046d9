"""Decoder-LM / encoder-decoder assembly.

The network is a ``jax.lax.scan`` over *super-blocks* (config.block_pattern
repeats num_super_blocks times — DESIGN.md §3). All per-layer weights are
stacked on a leading ``nb`` axis; the adapter's per-layer factors (leading
axis L = total layers) are reshaped to (nb, P, ...) and ride through the scan
as xs, so the global TT addresses every layer with O(1) HLO.

Weight layout (one entry per pattern position, each leaf stacked over nb):

  blocks[p] = {"norm1": …, "mixer": {…}, ["norm2": …, "ffn": {…}],
               ["norm3": …, "xattn": {…}]}          (xattn: enc-dec decoder)

KV/state caches mirror the same structure: caches[p] leaves stacked over nb,
threaded through the scan as (xs -> updated ys).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import (AdapterCtx, dense_ffn, embed_tokens,
                                 lm_logits, norm)
from repro.peft import api as peft_api
from repro.sharding import (BATCH, SEQ, get_serve_tp, maybe_shard,
                            serve_tp_gather, serve_tp_slice)

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _nrm(key, shape, scale, dtype):
    return scale * jax.random.normal(key, shape, jnp.float32)


def _linear_init(key, d_in, d_out, nb, dtype):
    w = jax.random.normal(key, (nb, d_in, d_out), jnp.float32)
    return (w / jnp.sqrt(d_in)).astype(dtype)


def _norm_init(cfg: ModelConfig, nb):
    w = {"w": jnp.zeros((nb, cfg.d_model), jnp.float32)}
    if cfg.norm_kind == "layernorm":
        w = {"w": jnp.ones((nb, cfg.d_model), jnp.float32),
             "b": jnp.zeros((nb, cfg.d_model), jnp.float32)}
    return w


def _attn_init(cfg: ModelConfig, key, nb, dtype):
    ks = jax.random.split(key, 4)
    return {
        "wq": _linear_init(ks[0], cfg.d_model, cfg.q_dim, nb, dtype),
        "wk": _linear_init(ks[1], cfg.d_model, cfg.kv_dim, nb, dtype),
        "wv": _linear_init(ks[2], cfg.d_model, cfg.kv_dim, nb, dtype),
        "wo": _linear_init(ks[3], cfg.q_dim, cfg.d_model, nb, dtype),
    }


def _mamba_init(cfg: ModelConfig, key, nb, dtype):
    di, ds = cfg.mamba_d_inner, cfg.mamba_d_state
    dtr, k = cfg.resolved_dt_rank, cfg.mamba_conv
    ks = jax.random.split(key, 5)
    a = jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32), (di, 1))
    return {
        "w_in": _linear_init(ks[0], cfg.d_model, 2 * di, nb, dtype),
        "conv_w": (jax.random.normal(ks[1], (nb, k, di), jnp.float32)
                   / jnp.sqrt(k)).astype(dtype),
        "conv_b": jnp.zeros((nb, di), dtype),
        "w_x": _linear_init(ks[2], di, dtr + 2 * ds, nb, dtype),
        "w_dt": _linear_init(ks[3], dtr, di, nb, dtype),
        "dt_bias": jnp.zeros((nb, di), dtype),
        "a_log": jnp.tile(jnp.log(a)[None], (nb, 1, 1)),
        "d": jnp.ones((nb, di), jnp.float32),
        "w_out": _linear_init(ks[4], di, cfg.d_model, nb, dtype),
    }


def _mlstm_init(cfg: ModelConfig, key, nb, dtype):
    d, h = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 7)
    return {
        "wq": _linear_init(ks[0], d, d, nb, dtype),
        "wk": _linear_init(ks[1], d, d, nb, dtype),
        "wv": _linear_init(ks[2], d, d, nb, dtype),
        "w_i": _linear_init(ks[3], d, h, nb, dtype),
        "w_f": _linear_init(ks[4], d, h, nb, dtype),
        "w_og": _linear_init(ks[5], d, d, nb, dtype),
        "w_out": _linear_init(ks[6], d, d, nb, dtype),
    }


def _slstm_init(cfg: ModelConfig, key, nb, dtype):
    d, h = cfg.d_model, cfg.num_heads
    hd = d // h
    ks = jax.random.split(key, 9)
    out = {n: _linear_init(k, d, d, nb, dtype)
           for n, k in zip(("w_z", "w_i", "w_f", "w_o", "w_out"), ks[:5])}
    for n, k in zip(("r_z", "r_i", "r_f", "r_o"), ks[5:]):
        out[n] = (jax.random.normal(k, (nb, h, hd, hd), jnp.float32)
                  / jnp.sqrt(hd)).astype(dtype)
    return out


def _ffn_init(cfg: ModelConfig, key, nb, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    w = {"wu": _linear_init(ks[1], d, ff, nb, dtype),
         "wd": _linear_init(ks[2], ff, d, nb, dtype)}
    if cfg.mlp in ("swiglu", "geglu"):
        w["wg"] = _linear_init(ks[0], d, ff, nb, dtype)
    return w


def _moe_init(cfg: ModelConfig, key, nb, dtype):
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 7)
    w = {
        "router": _linear_init(ks[0], d, e, nb, jnp.float32),
        "e_wg": (jax.random.normal(ks[1], (nb, e, d, ff), jnp.float32)
                 / jnp.sqrt(d)).astype(dtype),
        "e_wu": (jax.random.normal(ks[2], (nb, e, d, ff), jnp.float32)
                 / jnp.sqrt(d)).astype(dtype),
        "e_wd": (jax.random.normal(ks[3], (nb, e, ff, d), jnp.float32)
                 / jnp.sqrt(ff)).astype(dtype),
    }
    if cfg.num_shared_experts:
        sff = ff * cfg.num_shared_experts
        w["s_wg"] = _linear_init(ks[4], d, sff, nb, dtype)
        w["s_wu"] = _linear_init(ks[5], d, sff, nb, dtype)
        w["s_wd"] = _linear_init(ks[6], sff, d, nb, dtype)
    return w


_MIXER_INIT = {"attn": _attn_init, "mamba": _mamba_init,
               "mlstm": _mlstm_init, "slstm": _slstm_init}


def _block_init(cfg: ModelConfig, key, nb, *, decoder_cross: bool, dtype):
    out = []
    for mixer, ffn in cfg.block_pattern:
        key, k1, k2, k3 = jax.random.split(key, 4)
        blk: dict = {"norm1": _norm_init(cfg, nb)}
        if mixer != "none":
            blk["mixer"] = _MIXER_INIT[mixer](cfg, k1, nb, dtype)
        if decoder_cross:
            blk["norm3"] = _norm_init(cfg, nb)
            blk["xattn"] = _attn_init(cfg, k3, nb, dtype)
        if ffn != "none":
            blk["norm2"] = _norm_init(cfg, nb)
            blk["ffn"] = (_moe_init if ffn == "moe" else _ffn_init)(
                cfg, k2, nb, dtype)
        out.append(blk)
    return out


def init_base_params(cfg: ModelConfig, key) -> dict:
    """Random stand-in for the frozen pre-trained weights."""
    dtype = cfg.param_dtype
    k_embed, k_blocks, k_enc = jax.random.split(key, 3)
    nb = cfg.num_super_blocks
    params = {
        "embed": {"tok": (jax.random.normal(
            k_embed, (cfg.padded_vocab, cfg.d_model), jnp.float32)
            * 0.02).astype(dtype)},
        "blocks": _block_init(cfg, k_blocks, nb,
                              decoder_cross=cfg.is_encdec, dtype=dtype),
        "final_norm": _norm_init(cfg, 1),
    }
    if cfg.is_encdec:
        enc_cfg = dataclasses.replace(cfg, block_pattern=(("attn", "dense"),),
                                      num_layers=cfg.encoder_layers)
        params["enc_blocks"] = _block_init(enc_cfg, k_enc,
                                           cfg.encoder_layers,
                                           decoder_cross=False, dtype=dtype)
        params["enc_final_norm"] = _norm_init(cfg, 1)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _split_layers(per_layer, nb: int, p: int, offset: int = 0):
    """(L, ...) adapter factors -> (nb, P, ...) for the scan (slice
    [offset : offset + nb*p] of the global layer axis first)."""
    if per_layer is None:
        return None
    def one(a):
        sl = jax.lax.slice_in_dim(a, offset, offset + nb * p, axis=0)
        return sl.reshape((nb, p) + a.shape[1:])
    return jax.tree_util.tree_map(one, per_layer)


def _sublayer(h, blk, mixer, ffn, ctx: AdapterCtx, cfg: ModelConfig, *,
              causal, positions, cache, cache_pos, enc_out, chunk,
              block_tables=None):
    aux = {}
    new_cache = {}
    if mixer != "none":
        hn = norm(h, blk["norm1"], cfg.norm_eps)
        if mixer == "attn":
            y, c = attn_lib.attention(
                hn, blk["mixer"], ctx, cfg, causal=causal,
                positions=positions, chunk=chunk,
                cache=(cache or {}).get("self"), cache_pos=cache_pos,
                block_tables=block_tables)
            if c is not None:
                new_cache["self"] = c
        elif mixer == "mamba":
            y, c = mamba_lib.mamba_mixer(hn, blk["mixer"], ctx, cfg,
                                         cache=(cache or {}).get("ssm"))
            if c is not None:
                new_cache["ssm"] = c
        elif mixer == "mlstm":
            y, c = xlstm_lib.mlstm_mixer(hn, blk["mixer"], ctx, cfg,
                                         cache=(cache or {}).get("mlstm"))
            if c is not None:
                new_cache["mlstm"] = c
        elif mixer == "slstm":
            y, c = xlstm_lib.slstm_mixer(hn, blk["mixer"], ctx, cfg,
                                         cache=(cache or {}).get("slstm"))
            if c is not None:
                new_cache["slstm"] = c
        else:
            raise ValueError(mixer)
        h = h + y
    if "xattn" in blk and enc_out is not None:
        hn = norm(h, blk["norm3"], cfg.norm_eps)
        y, c = attn_lib.attention(hn, blk["xattn"], ctx, cfg, causal=False,
                                  prefix="xattn", use_rope=False,
                                  kv_x=enc_out,
                                  cache=(cache or {}).get("cross"))
        if c is not None:
            new_cache["cross"] = c
        h = h + y
    if ffn != "none":
        hn = norm(h, blk["norm2"], cfg.norm_eps)
        if ffn == "moe":
            y, moe_aux = moe_lib.moe_ffn(hn, blk["ffn"], ctx, cfg)
            aux.update(moe_aux)
        else:
            y = dense_ffn(hn, blk["ffn"], ctx, cfg.mlp)
        h = h + y
    return h, new_cache, aux


def run_blocks(h, blocks, pattern, spec: peft_api.AdapterSpec, broadcast,
               per_layer, cfg: ModelConfig, *, causal=True, positions=None,
               caches=None, cache_pos=None, enc_out=None, layer_offset=0,
               task=None, remat=False, chunk=0, nb=None, policy=None,
               block_tables=None):
    """Scan over super-blocks. blocks: list of per-position dicts (leaves
    stacked over nb). Returns (h, new_caches, aux). ``policy`` is the
    resolved kernel-dispatch policy (kernels/dispatch.py), carried into
    every layer by AdapterCtx. ``block_tables`` switches attention to the
    paged cache layout (one table shared by every layer)."""
    p = len(pattern)
    nb = nb if nb is not None else (
        jax.tree_util.tree_leaves(blocks)[0].shape[0])
    pl = _split_layers(per_layer, nb, p, layer_offset)
    has_cache = caches is not None

    def body(h, xs):
        blks, pl_b, cch = xs
        aux_acc = {}
        new_cch = []
        for i, (mixer, ffn) in enumerate(pattern):
            ly = (None if pl_b is None
                  else jax.tree_util.tree_map(lambda a: a[i], pl_b))
            ctx = AdapterCtx(spec, broadcast, ly, task, policy)
            h, nc, aux = _sublayer(
                h, blks[i], mixer, ffn, ctx, cfg, causal=causal,
                positions=positions,
                cache=(cch[i] if has_cache else None),
                cache_pos=cache_pos, enc_out=enc_out, chunk=chunk,
                block_tables=block_tables)
            new_cch.append(nc)
            for k, v in aux.items():
                aux_acc[k] = aux_acc.get(k, 0.0) + v
        return h, (new_cch, aux_acc)

    if remat:
        body = jax.checkpoint(body)

    xs = (blocks, pl, caches if has_cache else [{} for _ in range(p)])
    h, (new_caches, aux_stack) = jax.lax.scan(body, h, xs, length=nb)
    aux = {k: jnp.sum(v) for k, v in aux_stack.items()}
    return h, new_caches, aux


@dataclasses.dataclass(frozen=True)
class ModelOutputs:
    logits: jnp.ndarray
    aux: dict
    caches: Any = None
    enc_out: Any = None


# the (fixed) encoder super-block pattern — shared with core/merge.py's
# whole-model fold so the two can't drift
ENC_PATTERN = (("attn", "dense"),)


def encode(base, cfg: ModelConfig, enc_embeds, spec, broadcast, per_layer,
           policy=None):
    """Whisper-style encoder over precomputed (stub) frame embeddings."""
    h = maybe_shard(enc_embeds.astype(cfg.compute_dtype), BATCH, SEQ, None)
    pos = jnp.arange(h.shape[1])
    h, _, aux = run_blocks(
        h, base["enc_blocks"], ENC_PATTERN, spec, broadcast,
        per_layer, cfg, causal=False, positions=pos, layer_offset=0,
        nb=cfg.encoder_layers, policy=policy)
    h = norm(h, jax.tree_util.tree_map(lambda a: a[0],
                                       base["enc_final_norm"]), cfg.norm_eps)
    return h, aux


def forward(base, cfg: ModelConfig, spec, broadcast, per_layer, tokens=None,
            *, embeds=None, enc_embeds=None, task=None, remat=False,
            chunk=0, return_caches=False, cache_len=0, policy=None):
    """Train / prefill forward. Returns ModelOutputs with (B, T, V) logits.

    tokens: (B, T) int32; embeds: optional precomputed prefix embeddings
    (B, Tp, d) prepended to the token embeddings (VLM patch stub);
    enc_embeds: encoder-side stub input for enc-dec models; policy: the
    resolved kernel-dispatch policy (None -> reference XLA paths).
    """
    aux = {}
    enc_out = None
    layer_offset = 0
    if cfg.is_encdec:
        enc_out, aux = encode(base, cfg, enc_embeds, spec, broadcast,
                              per_layer, policy=policy)
        layer_offset = cfg.encoder_layers

    h = embed_tokens(tokens, base["embed"]["tok"], cfg.compute_dtype)
    if embeds is not None:
        h = jnp.concatenate([embeds.astype(h.dtype), h], axis=1)
    h = maybe_shard(h, BATCH, SEQ, None)
    t = h.shape[1]
    positions = jnp.arange(t)

    h, new_caches, aux2 = run_blocks(
        h, base["blocks"], cfg.block_pattern, spec, broadcast, per_layer,
        cfg, causal=True, positions=positions, enc_out=enc_out,
        layer_offset=layer_offset, task=task, remat=remat, chunk=chunk,
        caches=None, policy=policy)
    aux.update(aux2)
    h = norm(h, jax.tree_util.tree_map(lambda a: a[0], base["final_norm"]),
             cfg.norm_eps)
    logits = lm_logits(h, base["embed"]["tok"])
    return ModelOutputs(logits=logits, aux=aux, caches=new_caches,
                        enc_out=enc_out)


def init_caches(cfg: ModelConfig, batch: int, length: int, dtype, *,
                num_super_blocks: Optional[int] = None) -> list:
    """Stacked (over nb) cache pytree, one entry per pattern position.
    ``num_super_blocks`` overrides cfg's — the speculative drafter's
    layer-strided sub-model keeps its own (smaller) cache region."""
    nb = (cfg.num_super_blocks if num_super_blocks is None
          else num_super_blocks)

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (nb,) + a.shape), tree)

    out = []
    for mixer, _ in cfg.block_pattern:
        ent = {}
        if mixer == "attn":
            ent["self"] = stack(attn_lib.init_cache(cfg, batch, length,
                                                    dtype))
            # NOTE: cross-attention k/v are recomputed from enc_out each
            # decode step (one GEMM per layer); a real serving deployment
            # prefills them once — see examples/serve.py.
        elif mixer == "mamba":
            ent["ssm"] = stack(mamba_lib.init_mamba_cache(cfg, batch, dtype))
        elif mixer == "mlstm":
            ent["mlstm"] = stack(xlstm_lib.init_mlstm_cache(cfg, batch))
        elif mixer == "slstm":
            ent["slstm"] = stack(xlstm_lib.init_slstm_cache(cfg, batch))
        out.append(ent)
    return out


def init_paged_caches(cfg: ModelConfig, num_blocks: int, page_size: int,
                      dtype, kv_quant: bool = False, *,
                      num_super_blocks: Optional[int] = None) -> list:
    """Paged cache pytree: one flat (nb, num_blocks, KV, page, hd) block
    pool per pattern position. Attention-only — the paged engine rejects
    stateful mixers up front (their caches are not position-indexed).
    ``kv_quant`` makes the pools int8 with per-cell scale pools riding in
    the same block layout (``copy_cache_block`` and the host-side block
    bookkeeping treat them like any other leaf). ``num_super_blocks``
    overrides cfg's for the drafter's layer-strided cache region; both
    regions are indexed by the SAME host-side block tables."""
    nb = (cfg.num_super_blocks if num_super_blocks is None
          else num_super_blocks)

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (nb,) + a.shape), tree)

    out = []
    for mixer, _ in cfg.block_pattern:
        if mixer != "attn":
            raise NotImplementedError(
                f"paged caches are attention-only (got {mixer!r})")
        out.append({"self": stack(attn_lib.init_paged_cache(
            cfg, num_blocks, page_size, dtype, kv_quant=kv_quant))})
    return out


def copy_cache_block(caches, src, dst):
    """Device-side copy-on-write: duplicate physical block ``src`` into
    ``dst`` across every layer of a paged cache pytree (leaves stacked
    (nb, N, KV, page, hd)). ``src``/``dst`` may be traced scalars; the
    host-side BlockManager decides when a copy is needed
    (serving/block_manager.py). A ``dst`` >= N drops the write — the
    data-striped engine passes the sentinel on replicas that do not own
    the copy (block ids are replica-local, DESIGN.md §11)."""
    def one(c):
        return c.at[:, dst].set(c[:, src], mode="drop")
    return jax.tree_util.tree_map(one, caches)


def migrate_cache_blocks(dst_caches, src_caches, src_ids, dst_ids):
    """Batched pool-to-pool block copy: ``dst_caches[:, dst_ids[i]] =
    src_caches[:, src_ids[i]]`` across every layer — the device half of
    the disaggregated prefill→decode handoff (DESIGN.md §11; the host
    half is BlockManager.migrate_to). ``src_ids``/``dst_ids`` are
    fixed-width (P,) int32 vectors so one trace serves every handoff
    size: pad entries (and, under data striping, every entry on replicas
    that do not own the handoff) carry the out-of-pool sentinel and drop
    via ``mode="drop"`` — their clamped source reads are garbage the
    dropped write never lands."""
    def one(d, s):
        return d.at[:, dst_ids].set(s[:, src_ids].astype(d.dtype),
                                    mode="drop")
    return jax.tree_util.tree_map(one, dst_caches, src_caches)


def _serve_logits(h, embed):
    """Tied-embedding readout for the serving step graphs. h: (..., d);
    embed: (V, d), replicated. Returns (..., V) logits — (B, V) for the
    single-token decode step, (B, C, V) when the speculative verifier
    scores every column of a co-batched chunk in one pass.

    Under serve-time tensor parallelism (sharding.get_serve_tp — the
    engine's shard_map region, DESIGN.md §9) each shard computes its
    contiguous padded-vocab column stripe — bitwise equal to the matching
    columns of the replicated readout, since column-splitting a GEMM
    changes no per-element reduction order — and the full logits are
    all-gathered for in-graph sampling: the ONE all-gather of activations
    in the decode step, sized (B, V) per token."""
    if get_serve_tp() is None:
        return lm_logits(h, embed)
    local = serve_tp_slice(embed, 0)
    out = h @ local.T.astype(h.dtype)
    return serve_tp_gather(out, out.ndim - 1)


def paged_step(base, cfg: ModelConfig, spec, broadcast, per_layer, toks,
               caches, block_tables, pos, sel, *, task=None, policy=None,
               all_logits=False):
    """One co-batched decode / chunked-prefill step over a paged cache.

    toks: (B, C) — slot b's tokens at absolute positions pos[b]..pos[b]+C-1
    (decode slots carry 1 real token, prefilling slots up to C prompt
    tokens; trailing columns past a slot's real count are pad whose cache
    writes are overwritten by the step that owns those positions);
    block_tables: (B, P) int32; pos: (B,); sel: (B,) column whose logits
    to return (the slot's last real token). Returns (logits (B, V),
    new caches). ``all_logits`` returns (B, C, V) instead — the
    speculative verifier scores every column (sel is ignored): column c
    attends [0, pos[b]+c], so its logits depend only on tokens <= c
    regardless of what trails in later columns.
    """
    h = embed_tokens(toks, base["embed"]["tok"], cfg.compute_dtype)
    h = maybe_shard(h, BATCH, None, None)
    positions = pos[:, None] + jnp.arange(toks.shape[1])[None, :]
    h, new_caches, _ = run_blocks(
        h, base["blocks"], cfg.block_pattern, spec, broadcast, per_layer,
        cfg, causal=True, positions=positions, caches=caches,
        cache_pos=pos, task=task, policy=policy, block_tables=block_tables)
    h = norm(h, jax.tree_util.tree_map(lambda a: a[0], base["final_norm"]),
             cfg.norm_eps)
    if all_logits:
        return _serve_logits(h, base["embed"]["tok"]), new_caches
    h_sel = h[jnp.arange(h.shape[0]), sel]                  # (B, d)
    logits = _serve_logits(h_sel, base["embed"]["tok"])
    return logits, new_caches


def insert_cache_slot(caches, req_caches, slot):
    """Write a batch-1 cache pytree into batch row ``slot`` of a decode
    cache (leaves stacked (nb, B, ...)): the serving engine's prefill-into-
    slot step. ``slot`` may be a traced scalar."""
    def one(c, c1):
        return jax.lax.dynamic_update_slice(
            c, c1.astype(c.dtype), (0, slot) + (0,) * (c.ndim - 2))
    return jax.tree_util.tree_map(one, caches, req_caches)


def decode_step(base, cfg: ModelConfig, spec, broadcast, per_layer, token,
                caches, cache_pos, *, enc_out=None, task=None, policy=None,
                all_logits=False):
    """One decode step: token (B, T) -> (logits (B, V), new caches).

    cache_pos: scalar, or a (B,) vector of per-row positions (continuous-
    batching slots — see repro/serving/engine.py); token column j lands at
    cache_pos + j (T == 1 everywhere except the speculative verifier's
    multi-token pass — attention handles T > 1 per column, bit-identical
    to T sequential single-token steps). ``all_logits`` returns (B, T, V)
    — one distribution per column — instead of the last column's (B, V).
    ``policy`` routes the adapted matmuls / attention through the fused
    Pallas kernels."""
    h = embed_tokens(token, base["embed"]["tok"], cfg.compute_dtype)
    h = maybe_shard(h, BATCH, None, None)
    t = token.shape[1]
    if jnp.ndim(cache_pos) == 0:
        positions = cache_pos[None] + jnp.arange(t)[None, :]
    elif jnp.ndim(cache_pos) == 1:
        positions = cache_pos[:, None] + jnp.arange(t)[None, :]
    else:
        positions = cache_pos
    layer_offset = cfg.encoder_layers if cfg.is_encdec else 0
    h, new_caches, _ = run_blocks(
        h, base["blocks"], cfg.block_pattern, spec, broadcast, per_layer,
        cfg, causal=True, positions=positions, caches=caches,
        cache_pos=cache_pos, enc_out=enc_out, layer_offset=layer_offset,
        task=task, policy=policy)
    h = norm(h, jax.tree_util.tree_map(lambda a: a[0], base["final_norm"]),
             cfg.norm_eps)
    if all_logits:
        return _serve_logits(h, base["embed"]["tok"]), new_caches
    logits = _serve_logits(h[:, 0], base["embed"]["tok"])
    return logits, new_caches
