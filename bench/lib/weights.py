"""The seed's key and the MetaTT adapter's cores, for every architecture.

The benchmark makes every weight itself, from ``--seed``, so that the plain
reference can make the same values again without taking anything from the
program. An architecture's base weights are made by its own ``weights.py``
(``bench/models/<architecture>/``); the adapter is made here, and takes
from the architecture only what its ``weights.adaptable(cfg)`` gives: the
number of adapted layers and the (d_in, d_out) of each adaptable matrix,
by the program's name of the matrix type.

TT cores: every core N(0, s^2), s chosen so that one adapted matrix's
delta-W has ``delta_ratio`` times the standard deviation of a d_model x
d_model base matrix. A TT of n cores and rank r gives delta-W entries of
standard deviation alpha * r^((n-1)/2) * s^n, which fixes s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

TT_LEAF = 200


def seed_key(seed: int):
    """A PRNG key from any whole number, 64-bit seeds included."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              (seed // 2 ** 32) % 2 ** 31)


def adapter_modes(adaptable: tuple, adapter: dict) -> tuple:
    """Mode sizes of the MetaTT tensor train (core order as in the paper:
    (D_in, L, [T,] M, D_out)); boundary cores are sized to the largest
    adapted input / output width. ``adaptable``: (layers, {matrix type:
    (d_in, d_out)}), from the architecture's ``weights.adaptable``."""
    layers, shapes = adaptable
    ins = [shapes[m][0] for m in adapter["matrices"]]
    outs = [shapes[m][1] for m in adapter["matrices"]]
    M = len(adapter["matrices"])
    if adapter["variant"] == "4d":
        return (max(ins), layers, M, max(outs))
    if adapter["variant"] == "4+1d":
        return (max(ins), layers, adapter["tasks"], M, max(outs))
    raise ValueError(f"adapter variant {adapter['variant']!r}")


def core_std(cfg: dict, adapter: dict, adaptable: tuple) -> float:
    n = len(adapter_modes(adaptable, adapter))
    r = adapter["rank"]
    target = adapter["delta_ratio"] * cfg["hidden_size"] ** -0.5
    return (target / (adapter["alpha"] * r ** ((n - 1) / 2))) ** (1.0 / n)


def tt_cores(cfg: dict, adapter: dict, adaptable: tuple, key) -> list:
    """The adapter's TT cores, f32, shapes (r_{k-1}, n_k, r_k)."""
    modes = adapter_modes(adaptable, adapter)
    r = adapter["rank"]
    bonds = [1] + [r] * (len(modes) - 1) + [1]
    s = core_std(cfg, adapter, adaptable)
    out = []
    for i, n in enumerate(modes):
        k = jax.random.fold_in(jax.random.fold_in(key, TT_LEAF), i)
        out.append(s * jax.random.normal(k, (bonds[i], n, bonds[i + 1]),
                                         jnp.float32))
    return out
