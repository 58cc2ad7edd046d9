"""Seeded random weights of the latent-attention MoE decoder (Kimi K2's
block).

Each leaf of each layer has a key of its own
(``fold_in(fold_in(seed_key, leaf), layer)``), so one layer can be made
alone, bit for bit as the whole stack makes it. The adapter's cores come
from ``bench.lib.weights`` over the shapes that ``adaptable`` gives.

Scales (as the dense decoder's, so that each layer's share of the residual
stream stays even; L is the depth that runs):

* embedding and the untied head: N(0, 0.02) (``assumed`` in the
  configuration file: the published config gives no initializer);
* q_a, kv_a, dense and expert gate/up, shared gate/up: N(0, 1/d_in), unit
  outputs; q_b and kv_b read RMS-normed latents: N(0, 1/d_in) too;
* o and every down projection (the residual projections): N(0, 1/d_in)
  times 1/sqrt(2 L);
* router: N(0, 1/d_model), router logits of unit variance;
* the correction bias b_corr: N(0, 0.01) for every expert, plus a ramp
  from -0.02 to +0.05 over the held experts in an order drawn from the
  seed, so that their loads are uneven (the most loaded about 2-4 times
  an expert's mean load);
* norms: gain 1 (the program keeps 1 + w, so w = 0).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.lib.weights import tt_cores

MLA_LEAVES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
DENSE_LEAVES = ("wg", "wu", "wd")
MOE_LEAVES = ("e_wg", "e_wu", "e_wd", "s_wg", "s_wu", "s_wd", "router",
              "router_bias")
#: leaf -> its key number (one key per leaf name)
LEAF_ID = {n: i for i, n in enumerate(MLA_LEAVES + DENSE_LEAVES
                                      + MOE_LEAVES)}
EMBED_LEAF, HEAD_LEAF = 100, 101
#: the program's name of each adaptable matrix type -> its leaf here
MATRIX_LEAF = {"mla_q_a": "wq_a", "mla_q_b": "wq_b", "mla_kv_a": "wkv_a",
               "mla_kv_b": "wkv_b", "mla_o": "wo"}
RESIDUAL = ("wo", "wd", "e_wd", "s_wd")


def dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return dict(d=cfg["hidden_size"], h=h, dn=dn, dr=dr, qk=dn + dr,
                dv=cfg["v_head_dim"], rq=cfg["q_lora_rank"],
                rkv=cfg["kv_lora_rank"], ff=cfg["intermediate_size"],
                eff=cfg["moe_intermediate_size"],
                held=cfg["n_routed_experts"], E=cfg["experts_total"],
                lo=cfg.get("expert_offset", 0),
                k=cfg["num_experts_per_tok"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"],
                dense=cfg["first_k_dense_replace"])


def is_dense(cfg: dict, layer_idx: int) -> bool:
    return layer_idx < cfg["first_k_dense_replace"]


def mla_shapes(cfg: dict) -> dict:
    """(d_in, d_out) of each latent-attention matrix."""
    m = dims(cfg)
    return {"wq_a": (m["d"], m["rq"]), "wq_b": (m["rq"], m["h"] * m["qk"]),
            "wkv_a": (m["d"], m["rkv"] + m["dr"]),
            "wkv_b": (m["rkv"], m["h"] * (m["dn"] + m["dv"])),
            "wo": (m["h"] * m["dv"], m["d"])}


def leaf_shapes(cfg: dict, dense: bool) -> dict:
    """Shapes of every matrix of a dense or an expert layer."""
    m = dims(cfg)
    d = m["d"]
    out = dict(mla_shapes(cfg))
    if dense:
        out.update(wg=(d, m["ff"]), wu=(d, m["ff"]), wd=(m["ff"], d))
    else:
        out.update(e_wg=(m["held"], d, m["eff"]),
                   e_wu=(m["held"], d, m["eff"]),
                   e_wd=(m["held"], m["eff"], d),
                   s_wg=(d, m["eff"]), s_wu=(d, m["eff"]),
                   s_wd=(m["eff"], d), router=(d, m["E"]))
    return out


def adaptable(cfg: dict) -> tuple:
    """(adapted layers, {matrix type: (d_in, d_out)}): what the adapter's
    cores are sized from."""
    sh = mla_shapes(cfg)
    return (cfg["num_hidden_layers"],
            {t: sh[leaf] for t, leaf in MATRIX_LEAF.items()})


def _key(key, name, layer_idx):
    return jax.random.fold_in(jax.random.fold_in(key, LEAF_ID[name]),
                              layer_idx)


def router_bias(cfg: dict, key, layer_idx):
    """b_corr of one layer (E,), f32: small noise for every expert and an
    uneven ramp over the held ones."""
    m = dims(cfg)
    k = _key(key, "router_bias", layer_idx)
    b = 0.01 * jax.random.normal(jax.random.fold_in(k, 0), (m["E"],))
    ramp = jnp.linspace(-0.02, 0.05, m["held"])
    ramp = jax.random.permutation(jax.random.fold_in(k, 1), ramp)
    return b.at[m["lo"]:m["lo"] + m["held"]].add(ramp)


def layer(cfg: dict, key, layer_idx, dense=None) -> dict:
    """Layer ``layer_idx``'s weights: matrices (d_in, d_out) (experts
    (held, d_in, d_out)) in bf16, the router and its bias in f32.
    ``dense`` says the layer's kind where ``layer_idx`` is traced."""
    L = cfg["num_hidden_layers"]
    dense = is_dense(cfg, layer_idx) if dense is None else dense
    out = {}
    for name, shape in leaf_shapes(cfg, dense).items():
        d_in = shape[-2]
        w = jax.random.normal(_key(key, name, layer_idx), shape, jnp.float32)
        s = d_in ** -0.5
        if name in RESIDUAL:
            s /= math.sqrt(2 * L)
        w = w * s
        out[name] = w if name == "router" else w.astype(jnp.bfloat16)
    if not dense:
        out["router_bias"] = router_bias(cfg, key, layer_idx)
    return out


def embed(cfg: dict, key):
    e = jax.random.normal(jax.random.fold_in(key, EMBED_LEAF),
                          (cfg["vocab_size"], cfg["hidden_size"]))
    return (e * 0.02).astype(jnp.bfloat16)


def head(cfg: dict, key):
    """The untied readout, (V, d) like the embedding."""
    e = jax.random.normal(jax.random.fold_in(key, HEAD_LEAF),
                          (cfg["vocab_size"], cfg["hidden_size"]))
    return (e * 0.02).astype(jnp.bfloat16)


def cores(cfg: dict, adapter: dict, key) -> list:
    return tt_cores(cfg, adapter, adaptable(cfg), key)


def stacked(cfg: dict, adapter: dict, key) -> dict:
    """Every weight at once: the dense layers' and the expert layers'
    leaves each stacked over their layers, the embedding, the head and the
    TT cores. Meant to run as one jitted call on the device; layer l of
    each stack equals ``layer(cfg, key, l)``."""
    n_dense = cfg["first_k_dense_replace"]

    def stack(first, last, dense):
        # made stacked (one draw per layer's key), not made then stacked
        return jax.vmap(lambda l: layer(cfg, key, l, dense))(
            jnp.arange(first, last))

    return {"dense": stack(0, n_dense, True),
            "moe": stack(n_dense, cfg["num_hidden_layers"], False),
            "embed": embed(cfg, key), "head": head(cfg, key),
            "cores": cores(cfg, adapter, key)}
