"""Model-level API: adapter-spec construction, init, loss.

``build_adapter_spec`` is where the paper meets the model zoo: it enumerates
the adapted matrix types (the TT's M axis) with their per-type dims, choosing
arch-appropriate defaults (paper default q/v for attention archs; mamba /
xlstm projections for the SSM archs — DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig, RunConfig
from repro.core.metatt import MetaTTConfig
from repro.models import moe as moe_lib
from repro.models import transformer
from repro.peft import api as peft_api
from repro.peft.lora import LoRAConfig
from repro.peft.lotr import LoTRConfig
from repro.peft.vera import VeRAConfig


def matrix_dims(cfg: ModelConfig) -> dict:
    """matrix type -> (d_in, d_out) for every adaptable linear map."""
    d, q, kv, ff = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.d_ff
    out = {}
    mixers = {m for m, _ in cfg.block_pattern}
    if "attn" in mixers or cfg.is_encdec:
        out.update({"attn_q": (d, q), "attn_k": (d, kv), "attn_v": (d, kv),
                    "attn_o": (q, d)})
    if "mla" in mixers:
        h, rq, rkv = cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank
        out.update({
            "mla_q_a": (d, rq), "mla_q_b": (rq, h * cfg.qk_head_dim),
            "mla_kv_a": (d, rkv + cfg.qk_rope_head_dim),
            "mla_kv_b": (rkv, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "mla_o": (h * cfg.v_head_dim, d)})
    if cfg.is_encdec:
        out.update({"xattn_q": (d, q), "xattn_k": (d, kv),
                    "xattn_v": (d, kv), "xattn_o": (q, d)})
    if "mamba" in mixers:
        di = cfg.mamba_d_inner
        out.update({"mamba_in": (d, 2 * di), "mamba_out": (di, d)})
    if "mlstm" in mixers:
        out.update({"mlstm_q": (d, d), "mlstm_v": (d, d), "mlstm_o": (d, d)})
    if "slstm" in mixers:
        out.update({"slstm_z": (d, d), "slstm_o": (d, d)})
    if ff:
        out.update({"ffn_gate": (d, ff), "ffn_up": (d, ff),
                    "ffn_down": (ff, d)})
    if any(f == "moe" for _, f in cfg.block_pattern):
        out["moe_down"] = (ff, d)
    return out


def default_matrices(cfg: ModelConfig, variant: str = "4d") -> tuple:
    """Paper default: attention q/v (App. A.2); arch-family extensions for
    blocks that have no attention."""
    mixers = {m for m, _ in cfg.block_pattern}
    out = []
    if "attn" in mixers or cfg.is_encdec:
        out += ["attn_q", "attn_v"]
    if "mla" in mixers:
        # MLA's up-projections to the per-head queries and keys/values
        out += ["mla_q_b", "mla_kv_b"]
    if cfg.is_encdec:
        out += ["xattn_q", "xattn_v"]
    if "mamba" in mixers:
        out += ["mamba_in", "mamba_out"]
    if "mlstm" in mixers:
        out += ["mlstm_q", "mlstm_v"]
    if "slstm" in mixers:
        out += ["slstm_z"]
    if variant == "4+ed":
        out += ["moe_down"]
    return tuple(out)


def build_adapter_spec(run: RunConfig) -> peft_api.AdapterSpec:
    cfg = run.model
    if run.adapter_kind == "none":
        return peft_api.NONE
    types = run.adapter_matrices or default_matrices(cfg, run.adapter_variant)
    dims = matrix_dims(cfg)
    unknown = [t for t in types if t not in dims]
    if unknown:
        raise ValueError(f"{cfg.name}: matrix types {unknown} not present")
    d_in = tuple(dims[t][0] for t in types)
    d_out = tuple(dims[t][1] for t in types)
    common = dict(num_layers=cfg.total_layers, matrix_types=tuple(types),
                  d_in=d_in, d_out=d_out, rank=run.adapter_rank)
    if run.adapter_kind == "metatt":
        extra = {}
        if run.adapter_variant == "5d":
            if max(d_out) > cfg.q_dim:
                raise ValueError(
                    "5d head-factorized output requires all adapted out dims "
                    f"<= H*head_dim={cfg.q_dim}")
            extra = dict(num_heads=cfg.num_heads,
                         head_dim=cfg.resolved_head_dim)
        elif run.adapter_variant == "4+1d":
            extra = dict(num_tasks=max(run.num_tasks, 1))
        elif run.adapter_variant == "4+ed":
            extra = dict(num_experts=cfg.num_experts)
        acfg = MetaTTConfig(**common, variant=run.adapter_variant,
                            alpha=run.adapter_alpha, **extra)
    elif run.adapter_kind == "lora":
        acfg = LoRAConfig(**common, alpha=run.adapter_alpha * run.adapter_rank)
    elif run.adapter_kind == "vera":
        acfg = VeRAConfig(**common)
    elif run.adapter_kind == "lotr":
        acfg = LoTRConfig(**common, alpha=run.adapter_alpha)
    else:
        raise ValueError(run.adapter_kind)
    return peft_api.AdapterSpec(kind=run.adapter_kind, cfg=acfg)


def init_params(cfg: ModelConfig, spec: peft_api.AdapterSpec, key) -> dict:
    k1, k2 = jax.random.split(key)
    base = transformer.init_base_params(cfg, k1)
    adapter, frozen = peft_api.init_adapter(spec, k2)
    return {"base": base, "adapter": adapter, "frozen": frozen}


def count_params(params: dict) -> dict:
    def n(tree):
        return int(sum(x.size for x in jax.tree_util.tree_leaves(tree)))
    return {"base": n(params["base"]), "adapter": n(params["adapter"]),
            "frozen_adapter": n(params["frozen"])}


# ---------------------------------------------------------------------------


def next_token_loss(logits: jnp.ndarray, tokens: jnp.ndarray,
                    mask: Optional[jnp.ndarray] = None,
                    prefix_len: int = 0,
                    vocab_size: int = 0) -> jnp.ndarray:
    """Next-token CE. logits: (B, Tp+T, V) (Tp = vlm prefix), tokens (B, T).

    Deliberately slice-free on the T axis: position p's target comes from a
    ``roll`` (a cheap collective-permute when T is sequence-sharded) and
    invalid positions are masked elementwise. Slicing ``logits[:, :-1]``
    would force XLA to re-replicate a sequence-sharded logits tensor —
    a multi-GB resharding the kimi-k2 dry-run caught (EXPERIMENTS.md §Perf).
    Computed in f32 with a stable logsumexp (vocab- or T-sharded logits both
    fine; XLA inserts the reduction collectives).
    """
    b, t = tokens.shape
    t_full = logits.shape[1]
    if prefix_len:
        full_tokens = jnp.concatenate(
            [jnp.zeros((b, prefix_len), tokens.dtype), tokens], axis=1)
    else:
        full_tokens = tokens
    targets = jnp.roll(full_tokens, -1, axis=1)          # pos p -> token p+1
    pos = jnp.arange(t_full)[None, :]
    valid = jnp.broadcast_to(
        ((pos >= max(prefix_len - 1, 0)) &
         (pos < prefix_len + t - 1)), (b, t_full)).astype(jnp.float32)
    if mask is not None:
        # mask is per-*target* token: mask[j] gates the prediction of
        # token j, which lives at position prefix+j-1 -> roll to align
        m_full = jnp.concatenate(
            [jnp.ones((b, prefix_len), jnp.float32),
             mask.astype(jnp.float32)], axis=1) if prefix_len else \
            mask.astype(jnp.float32)
        valid = valid * jnp.roll(m_full, -1, axis=1)
    lg = logits.astype(jnp.float32)
    if vocab_size and logits.shape[-1] > vocab_size:
        pad_mask = jnp.arange(logits.shape[-1]) < vocab_size
        lg = jnp.where(pad_mask, lg, -1e30)
    lse = jax.nn.logsumexp(lg, axis=-1)
    true = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    nll = (lse - true) * valid
    return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1.0)


def loss_fn(adapter, base, frozen, batch: dict, cfg: ModelConfig,
            spec: peft_api.AdapterSpec, *, remat: bool = False,
            chunk: int = 0, aux_weight: float | None = None,
            policy=None) -> tuple:
    """PEFT objective: CE + MoE aux losses. ``adapter`` first so
    jax.value_and_grad(loss_fn) differentiates only the adapter (the frozen
    base never gets a gradient — the memory story that lets 1T-param models
    fine-tune, DESIGN.md §4). ``policy`` is the resolved kernel-dispatch
    policy — the train hot path runs the fused Pallas kernels (forward AND
    backward, via their custom VJPs) when it routes to Pallas."""
    bc, per_layer = peft_api.adapter_factors(spec, adapter, frozen)
    out = transformer.forward(
        base, cfg, spec, bc, per_layer, batch.get("tokens"),
        embeds=batch.get("embeds"), enc_embeds=batch.get("enc_embeds"),
        task=batch.get("task"), remat=remat, chunk=chunk, policy=policy)
    prefix = 0 if batch.get("embeds") is None else batch["embeds"].shape[1]
    loss = next_token_loss(out.logits, batch["tokens"], batch.get("mask"),
                           prefix, vocab_size=cfg.vocab_size)
    aux_weight = cfg.moe_aux_weight if aux_weight is None else aux_weight
    aux_total = sum(v for k, v in out.aux.items()
                    if k not in moe_lib.COUNTERS and k not in moe_lib.RECORDS)
    metrics = {"ce": loss, **out.aux}
    return loss + aux_weight * aux_total, metrics
