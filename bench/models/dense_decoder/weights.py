"""Seeded random weights of the dense decoder.

Each leaf of each layer has a key of its own
(``fold_in(fold_in(seed_key, leaf), layer)``), so one layer can be made
alone, bit for bit as the whole stack makes it. The adapter's cores come
from ``bench.lib.weights`` over the shapes that ``adaptable`` gives.

Scales (why each is what it is):

* embedding (tied readout): N(0, initializer_range), the published
  initializer (0.02 for both configurations);
* q, k, v, gate, up: N(0, 1/d_in), unit-variance outputs;
* o, down (the residual projections): N(0, 1/d_in) times 1/sqrt(2 L), as in
  GPT-2, so that the residual stream grows as sqrt of depth and no layer
  drowns the others (L is the depth that runs);
* norms: gain 1, bias 0 (the published initialisation).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.lib.weights import tt_cores

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
EMBED_LEAF = 100
#: the program's name of each adaptable matrix type -> its leaf here
MATRIX_LEAF = {"attn_q": "wq", "attn_k": "wk", "attn_v": "wv",
               "attn_o": "wo", "ffn_gate": "wg", "ffn_up": "wu",
               "ffn_down": "wd"}


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return dict(d=d, h=h, kv=kv, hd=hd, q=h * hd, kvd=kv * hd,
                ff=cfg["intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"])


def leaf_shapes(cfg: dict) -> dict:
    """(d_in, d_out) of each matrix of one layer."""
    m = dims(cfg)
    return {"wq": (m["d"], m["q"]), "wk": (m["d"], m["kvd"]),
            "wv": (m["d"], m["kvd"]), "wo": (m["q"], m["d"]),
            "wg": (m["d"], m["ff"]), "wu": (m["d"], m["ff"]),
            "wd": (m["ff"], m["d"])}


def adaptable(cfg: dict) -> tuple:
    """(adapted layers, {matrix type: (d_in, d_out)}): what the adapter's
    cores are sized from."""
    sh = leaf_shapes(cfg)
    return (cfg["num_hidden_layers"],
            {m: sh[leaf] for m, leaf in MATRIX_LEAF.items()})


def _std(cfg: dict, name: str, d_in: int) -> float:
    s = d_in ** -0.5
    if name in ("wo", "wd"):
        s /= math.sqrt(2 * cfg["num_hidden_layers"])
    return s


def layer(cfg: dict, key, layer_idx):
    """Layer ``layer_idx``'s matrices, bf16, as a dict of (d_in, d_out)."""
    out = {}
    for i, (name, (d_in, d_out)) in enumerate(leaf_shapes(cfg).items()):
        k = jax.random.fold_in(jax.random.fold_in(key, i), layer_idx)
        w = jax.random.normal(k, (d_in, d_out), jnp.float32)
        out[name] = (w * _std(cfg, name, d_in)).astype(jnp.bfloat16)
    return out


def embed(cfg: dict, key):
    k = jax.random.fold_in(key, EMBED_LEAF)
    e = jax.random.normal(k, (cfg["vocab_size"], cfg["hidden_size"]),
                          jnp.float32)
    return (e * cfg.get("initializer_range", 0.02)).astype(jnp.bfloat16)


def cores(cfg: dict, adapter: dict, key) -> list:
    """The adapter's TT cores for this configuration."""
    return tt_cores(cfg, adapter, adaptable(cfg), key)


def stacked(cfg: dict, adapter: dict, key) -> dict:
    """Every weight at once: matrices stacked over layers (L, d_in, d_out)
    in bf16, the embedding, and the TT cores. Meant to run as one jitted
    call on the device; layer ``l`` of each stack equals ``layer(cfg, key,
    l)``."""
    per = [layer(cfg, key, i) for i in range(cfg["num_hidden_layers"])]
    mats = {n: jnp.stack([p[n] for p in per]) for n in LAYER_LEAVES}
    return {"layers": mats, "embed": embed(cfg, key),
            "cores": cores(cfg, adapter, key)}
