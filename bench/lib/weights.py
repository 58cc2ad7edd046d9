"""Seeded random weights for a dense decoder with a MetaTT adapter.

The benchmark makes every weight itself, from ``--seed``, so that the plain
reference can make the same values again without taking anything from the
program. Each leaf of each layer has a key of its own
(``fold_in(fold_in(seed_key, leaf), layer)``), so one layer can be made
alone, bit for bit as the whole stack makes it.

Scales (why each is what it is):

* embedding (tied readout): N(0, initializer_range), the published
  initializer (0.02 for both configurations);
* q, k, v, gate, up: N(0, 1/d_in), unit-variance outputs;
* o, down (the residual projections): N(0, 1/d_in) times 1/sqrt(2 L), as in
  GPT-2, so that the residual stream grows as sqrt of depth and no layer
  drowns the others (L is the depth that runs);
* norms: gain 1, bias 0 (the published initialisation);
* TT cores: every core N(0, s^2), s chosen so that one adapted matrix's
  delta-W has ``delta_ratio`` times the standard deviation of a d_model x
  d_model base matrix. A TT of n cores and rank r gives delta-W entries of
  standard deviation alpha * r^((n-1)/2) * s^n, which fixes s.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
EMBED_LEAF = 100
TT_LEAF = 200
#: the program's name of each adaptable matrix type -> its leaf here
MATRIX_LEAF = {"attn_q": "wq", "attn_k": "wk", "attn_v": "wv",
               "attn_o": "wo", "ffn_gate": "wg", "ffn_up": "wu",
               "ffn_down": "wd"}


def seed_key(seed: int):
    """A PRNG key from any whole number, 64-bit seeds included."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              (seed // 2 ** 32) % 2 ** 31)


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


def adapter_modes(cfg: dict, adapter: dict) -> tuple:
    """Mode sizes of the MetaTT tensor train (core order as in the paper:
    (D_in, L, [T,] M, D_out)); boundary cores are sized to the largest
    adapted input / output width."""
    shapes = leaf_shapes(cfg)
    ins = [shapes[MATRIX_LEAF[m]][0] for m in adapter["matrices"]]
    outs = [shapes[MATRIX_LEAF[m]][1] for m in adapter["matrices"]]
    L, M = cfg["num_hidden_layers"], len(adapter["matrices"])
    if adapter["variant"] == "4d":
        return (max(ins), L, M, max(outs))
    if adapter["variant"] == "4+1d":
        return (max(ins), L, adapter["tasks"], M, max(outs))
    raise ValueError(f"adapter variant {adapter['variant']!r}")


def core_std(cfg: dict, adapter: dict) -> float:
    n = len(adapter_modes(cfg, adapter))
    r = adapter["rank"]
    target = adapter["delta_ratio"] * cfg["hidden_size"] ** -0.5
    return (target / (adapter["alpha"] * r ** ((n - 1) / 2))) ** (1.0 / n)


def tt_cores(cfg: dict, adapter: dict, key) -> list:
    """The adapter's TT cores, f32, shapes (r_{k-1}, n_k, r_k)."""
    modes = adapter_modes(cfg, adapter)
    r = adapter["rank"]
    bonds = [1] + [r] * (len(modes) - 1) + [1]
    s = core_std(cfg, adapter)
    out = []
    for i, n in enumerate(modes):
        k = jax.random.fold_in(jax.random.fold_in(key, TT_LEAF), i)
        out.append(s * jax.random.normal(k, (bonds[i], n, bonds[i + 1]),
                                         jnp.float32))
    return out


def stacked(cfg: dict, adapter: dict, key) -> dict:
    """Every weight at once: matrices stacked over layers (L, d_in, d_out)
    in bf16, the embedding, and the TT cores. Meant to run as one jitted
    call on the device; layer ``l`` of each stack equals ``layer(cfg, key,
    l)``."""
    per = [layer(cfg, key, i) for i in range(cfg["num_hidden_layers"])]
    mats = {n: jnp.stack([p[n] for p in per]) for n in LAYER_LEAVES}
    return {"layers": mats, "embed": embed(cfg, key),
            "cores": tt_cores(cfg, adapter, key)}
