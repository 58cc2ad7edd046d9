"""The numbers that decide ``correct`` for a training cell.

Each is a gap between the program's reading and the reference's, taken by
the worst leaf, against the reference's norm of that leaf or of the median
leaf, whichever is larger:

* ``loss_gap``: max over the first steps of |loss - loss_ref| / |loss_ref|;
* ``grad_gap``: the first step's gradient as the optimizer got it (clipped;
  read back from Adam's first moment, g = mu / (1 - beta1));
* ``change_gap``: the parameters' change over the first steps.

A cell's limits file names the numbers it compares.

Leaves whose reference gradient is under a thousandth of the median leaf's
are left out of the gradient and change comparisons (their change under
Adam is round-off alone).
"""
from __future__ import annotations

import numpy as np

SMALL_LEAF = 1e-3


def _norms(leaves):
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in leaves])


def leaf_gaps(got, want, keep) -> np.ndarray:
    """Each kept leaf's gap of norms, against the larger of its own and
    the median kept leaf's reference norm."""
    g, w = _norms(got), _norms(want)
    med = float(np.median(w[keep])) if keep.any() else 0.0
    den = np.maximum(w, med)
    return (np.abs(g - w) / np.where(den > 0, den, 1.0))[keep]


def kept(ref_grads) -> np.ndarray:
    w = _norms(ref_grads)
    return w >= SMALL_LEAF * float(np.median(w))


def train_checks(prog: dict, ref: dict) -> dict:
    """prog / ref: {"losses": [..], "grads": [leaves], "change": [leaves]}."""
    keep = kept(ref["grads"])
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    n = min(len(lp), len(lr))
    loss = float(np.max(np.abs(lp[:n] - lr[:n]) / np.abs(lr[:n])))
    if not np.all(np.isfinite(lp[:n])):
        loss = float("inf")
    grad = leaf_gaps(prog["grads"], ref["grads"], keep)
    change = leaf_gaps(prog["change"], ref["change"], keep)
    return {"loss_gap": loss, "grad_gap": float(grad.max()),
            "change_gap": float(change.max())}


def details(prog: dict, ref: dict) -> dict:
    """The readings behind ``train_checks``: each step's signed relative
    loss gap and each kept leaf's gradient and change gaps."""
    keep = kept(ref["grads"])
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    n = min(len(lp), len(lr))
    return {"loss_steps": ((lp[:n] - lr[:n]) / np.abs(lr[:n])).tolist(),
            "grad_leaves": leaf_gaps(prog["grads"], ref["grads"],
                                     keep).tolist(),
            "change_leaves": leaf_gaps(prog["change"], ref["change"],
                                       keep).tolist()}


def lr_at(opt: dict, step: int) -> float:
    """The learning rate of step ``step`` (0-based): linear warm-up over
    ``warmup_ratio`` of ``total_steps``, then the schedule."""
    total = opt["total_steps"]
    warm = max(int(opt["warmup_ratio"] * total), 1)
    if step < warm:
        return opt["lr"] * (step + 1) / warm
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    if opt["schedule"] == "linear":
        return opt["lr"] * (1.0 - frac)
    if opt["schedule"] == "cosine":
        return opt["lr"] * 0.5 * (1 + np.cos(np.pi * frac))
    return opt["lr"]


def reference_steps(ref, batches, opt: dict, steps: int) -> dict:
    """AdamW (global-norm clipping, bias correction, decoupled weight
    decay) over the reference's loss, ``steps`` steps from the seed's
    cores."""
    import jax.numpy as jnp
    b1, b2 = opt["betas"]
    cores = [jnp.asarray(c) for c in ref.cores()]
    start = [np.asarray(c) for c in cores]
    m = [jnp.zeros_like(c) for c in cores]
    v = [jnp.zeros_like(c) for c in cores]
    losses, first = [], None
    for s in range(steps):
        loss, g = ref.loss_and_grads(batches[s]["tokens"],
                                     batches[s]["mask"], cores)
        losses.append(float(loss))
        gn = float(np.sqrt(sum(float(jnp.sum(x * x)) for x in g)))
        if opt["grad_clip"] > 0:
            scale = min(1.0, opt["grad_clip"] / max(gn, 1e-9))
            g = [x * scale for x in g]
        if first is None:
            first = [np.asarray(x) for x in g]
        t = s + 1
        lr = lr_at(opt, s)
        m = [b1 * a + (1 - b1) * x for a, x in zip(m, g)]
        v = [b2 * a + (1 - b2) * x * x for a, x in zip(v, g)]
        new = []
        for c, a, w in zip(cores, m, v):
            upd = (a / (1 - b1 ** t)) / (jnp.sqrt(w / (1 - b2 ** t))
                                         + opt["eps"])
            if opt["weight_decay"]:
                upd = upd + opt["weight_decay"] * c
            new.append(c - lr * upd)
        cores = new
    return {"losses": losses, "grads": first,
            "change": [np.asarray(c) - s0 for c, s0 in zip(cores, start)]}
