"""The latent-attention MoE decoder as the program under test (``repro``)
runs it: its model configuration, the benchmark's weights laid out as the
program keeps them, and the counters of its last step. The only file of
this architecture that imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib.weights import seed_key

from . import reference
from . import weights as W

#: published keys whose values the program's block is written for
FIXED = {"hidden_act": "silu", "attention_bias": False,
         "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
         "topk_group": 1, "norm_topk_prob": True, "moe_layer_freq": 1,
         "num_nextn_predict_layers": 0}


def check(cfg: dict) -> None:
    """Refuse a configuration whose block differs from the one written
    down in ``reference.py``."""
    other = {k: cfg.get(k) for k, v in FIXED.items() if cfg.get(k, v) != v}
    if cfg.get("rope_scaling", {}).get("type", "yarn") != "yarn":
        other["rope_scaling"] = cfg["rope_scaling"]
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        other["num_key_value_heads"] = cfg["num_key_value_heads"]
    if other:
        raise ValueError(f"{cfg['name']}: the program cannot run {other}")


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.config.base import ModelConfig, YaRN
    check(cfg)
    m = W.dims(cfg)
    rs = cfg["rope_scaling"]
    return ModelConfig(
        name=cfg["name"], family="moe", num_layers=m["L"], d_model=m["d"],
        num_heads=m["h"], num_kv_heads=m["h"], d_ff=m["eff"],
        vocab_size=m["V"], block_pattern=(("mla", "moe"),), mlp="swiglu",
        norm_kind="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        q_lora_rank=m["rq"], kv_lora_rank=m["rkv"],
        qk_nope_head_dim=m["dn"], qk_rope_head_dim=m["dr"],
        v_head_dim=m["dv"],
        rope_scaling=YaRN(
            factor=float(rs["factor"]),
            original_max_position=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        first_dense_layers=m["dense"], dense_d_ff=m["ff"],
        num_experts=m["E"], experts_per_token=m["k"],
        num_shared_experts=cfg["n_shared_experts"],
        moe_scoring=cfg["scoring_func"],
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        moe_dispatch="dropless", moe_record_routing=True,
        experts_held=m["held"],
        expert_offset=m["lo"]).validate()


def _norms(cfg: dict, n: int) -> dict:
    return {"w": jnp.zeros((n, cfg["hidden_size"]), jnp.float32)}


def _block(cfg: dict, w: dict, n: int, dense: bool) -> dict:
    m = W.dims(cfg)
    mixer = {k: w[k] for k in W.MLA_LEAVES}
    mixer["q_norm"] = jnp.zeros((n, m["rq"]), jnp.float32)
    mixer["kv_norm"] = jnp.zeros((n, m["rkv"]), jnp.float32)
    ffn = {k: w[k] for k in (W.DENSE_LEAVES if dense else W.MOE_LEAVES)}
    return {"norm1": _norms(cfg, n), "mixer": mixer, "norm2": _norms(cfg, n),
            "ffn": ffn}


def params(cfg: dict, adapter: dict, seed: int) -> dict:
    """{"base", "adapter", "frozen"} in the program's layout, made on the
    device from ``seed`` in one jitted call. The program's model
    configuration is built first, so a program that cannot run this block
    fails here, before any weight is made."""
    model_config(cfg)
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense

    def make(key):
        w = W.stacked(cfg, adapter, key)
        base = {"embed": {"tok": w["embed"]}, "lm_head": w["head"],
                "dense_blocks": [_block(cfg, w["dense"], n_dense, True)],
                "blocks": [_block(cfg, w["moe"], n_moe, False)],
                "final_norm": _norms(cfg, 1)}
        return {"base": base, "adapter": {"cores": w["cores"]},
                "frozen": {}}

    return jax.jit(make)(seed_key(seed))


def counts(obj) -> dict:
    """The counters of the trainer's last step that ``work.py`` reads: the
    pairs routed to held experts, summed over the layers, and the most
    rows one held expert took in a layer. The program's routing of its
    steps so far (``moe_routing``, which ``model_config`` asks for) goes to
    the reference, which follows it at routing ties
    (``reference.follow``)."""
    reference.follow([m["moe_routing"] for _, m in obj.history
                      if "moe_routing" in m])
    m = obj.history[-1][1]
    return {"moe_pairs_here": float(m["moe_pairs_here"]),
            "moe_max_expert_rows": float(m["moe_max_expert_rows"])}
