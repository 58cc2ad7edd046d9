"""The dense decoder as the program under test (``repro``) runs it: its
model configuration, and the benchmark's weights laid out as the program
keeps them. The only file of this architecture that imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib.weights import seed_key

from . import weights as W


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.config.base import ModelConfig
    m = W.dims(cfg)
    if "layer_norm_eps" in cfg:
        norm_kind, eps = "layernorm", cfg["layer_norm_eps"]
    else:
        norm_kind, eps = "rmsnorm", cfg["rms_norm_eps"]
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{cfg['name']}: hidden_act {cfg['hidden_act']!r}")
    unsupported = {k: cfg[k] for k, ok in (
        ("partial_rotary_factor", 1.0), ("use_qkv_bias", False),
        ("qk_layernorm", False), ("use_parallel_residual", False))
        if cfg.get(k, ok) != ok}
    if unsupported:
        raise ValueError(f"{cfg['name']}: the program cannot run "
                         f"{unsupported}; state what runs under runs_as")
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=m["L"],
        d_model=m["d"], num_heads=m["h"], num_kv_heads=m["kv"],
        d_ff=m["ff"], vocab_size=m["V"], head_dim=m["hd"],
        mlp="swiglu", norm_kind=norm_kind, norm_eps=eps,
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"])).validate()


def _norm(cfg: dict, n: int):
    d = cfg["hidden_size"]
    if "layer_norm_eps" in cfg:                # gain 1, bias 0
        return {"w": jnp.ones((n, d), jnp.float32),
                "b": jnp.zeros((n, d), jnp.float32)}
    return {"w": jnp.zeros((n, d), jnp.float32)}   # the program keeps 1 + w


def params(cfg: dict, adapter: dict, seed: int) -> dict:
    """{"base", "adapter", "frozen"} in the program's layout, made on the
    device from ``seed`` in one jitted call."""
    L = cfg["num_hidden_layers"]

    def make(key):
        w = W.stacked(cfg, adapter, key)
        m = w["layers"]
        block = {"norm1": _norm(cfg, L),
                 "mixer": {k: m[k] for k in ("wq", "wk", "wv", "wo")},
                 "norm2": _norm(cfg, L),
                 "ffn": {k: m[k] for k in ("wg", "wu", "wd")}}
        base = {"embed": {"tok": w["embed"]}, "blocks": [block],
                "final_norm": _norm(cfg, 1)}
        return {"base": base, "adapter": {"cores": w["cores"]},
                "frozen": {}}

    return jax.jit(make)(seed_key(seed))


def counts(obj) -> dict:
    """The counters of the program's last call on ``obj`` (an engine or a
    trainer) that ``work.py`` needs: none, for the dense decoder."""
    return {}
