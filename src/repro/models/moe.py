"""Mixture-of-Experts with expert parallelism.

Routing: softmax top-k (renormalized). Dispatch is **capacity-based
(GShard-style)**: token-expert pairs are sorted by expert, each expert
processes up to C = capacity_factor · pairs/E_local slots as a *batched*
GEMM (E, C, d) × (E, d, ff); overflow pairs are dropped (aux load-balance
loss keeps routing near-uniform, and the paper's PEFT setting never trains
the experts anyway). We deliberately chose capacity dispatch over
``jax.lax.ragged_dot`` dropless grouping: the batched-GEMM form is what maps
onto the MXU as dense contractions and is also what the dry-run HLO
faithfully costs (ragged_dot's reference lowering is dense-masked —
E_local× flop inflation in the compiled module). Trade-off recorded in
DESIGN.md §3.

Expert parallelism is explicit ``jax.shard_map`` over the "model" mesh axis:
each shard owns E/|model| experts, dispatches exactly the pairs routed to
its local experts (non-local pairs land in a trash slot), and the per-token
combine is a single psum over "model". Expert weights are additionally
FSDP-sharded over "data" on the d_ff dim and all-gathered per-layer inside
the shard (DESIGN.md §4) — this is what lets kimi-k2's ~1T frozen parameters
fit 512 chips.

MetaTT hook: with the (4+E)D variant (paper §4 "expert partitions"), the
expert down-projection gets a TT delta whose middle r×r core is indexed by
the expert owning each capacity block — one tiny batched einsum, zero extra
large GEMMs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.config.base import ModelConfig
from repro.models.layers import AdapterCtx, adapted_linear, dense_ffn
from repro.sharding import batch_axes, current_mesh


def _router(x, w_router, n_k):
    logits = (x @ w_router.astype(x.dtype)).astype(jnp.float32)   # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, n_k)                      # (N, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return logits, probs, top_p, top_i


def aux_losses(logits, probs, top_i, num_experts: int) -> dict:
    """Standard load-balance + router-z losses (Switch/GShard)."""
    n = probs.shape[0]
    onehot = jax.nn.one_hot(top_i, num_experts, dtype=jnp.float32)  # (N,k,E)
    frac_tokens = onehot.sum((0, 1)) / (n * top_i.shape[-1])
    frac_probs = probs.mean(0)
    lb = num_experts * jnp.sum(frac_tokens * frac_probs)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return {"load_balance": lb, "router_z": z}


def _expert_delta(ctx: AdapterCtx, h: jnp.ndarray, lo, n_local: int,
                  d_out: int):
    """Adapter delta on the expert down-projection. h: (E_local, C, ff).

    MetaTT-(4+E)D indexes the middle core by global expert id (paper §4);
    other adapters apply a uniform (expert-independent) delta.
    """
    spec = ctx.spec
    if not spec.adapts("moe_down"):
        return None
    cfg = spec.cfg
    if spec.kind == "metatt" and getattr(cfg, "variant", "") == "4+ed":
        mi = cfg.m_index("moe_down")
        g1 = ctx.broadcast["g1"][: h.shape[-1]].astype(h.dtype)
        g4 = ctx.broadcast["g4"][:, :d_out].astype(h.dtype)
        c_all = ctx.layer["c"]                          # (E, M, r, r)
        c_loc = jax.lax.dynamic_slice_in_dim(c_all, lo, n_local, axis=0)
        c_loc = c_loc[:, mi].astype(h.dtype)            # (E_local, r, r)
        p = h @ g1                                      # (E_local, C, r)
        return cfg.alpha * (jnp.einsum("ecr,ers->ecs", p, c_loc) @ g4)
    if ctx.task is not None and jnp.ndim(ctx.task) >= 1:
        # h is expert-sorted (E_local, C, ff): its leading axis is experts,
        # so a per-request (B,) task vector cannot be gathered against it
        # (and would silently mis-route whenever E_local == B).
        raise NotImplementedError(
            "per-request task vectors cannot index the expert-sorted "
            "moe_down delta; use a scalar task")
    from repro.peft import api as peft_api
    return peft_api.adapter_delta(spec, ctx.broadcast, ctx.layer, h,
                                  "moe_down", task=ctx.task)


def _moe_block(x, top_p, top_i, lo, n_local, w_g, w_u, w_d, ctx: AdapterCtx,
               cfg: ModelConfig):
    """Capacity-dispatched expert FFN for experts [lo, lo+n_local).

    x: (N, d) tokens (all local tokens); returns (N, d) partial output
    covering exactly the pairs owned by this shard's experts.
    """
    n, k = top_i.shape
    pairs = n * k
    # per-expert capacity sized against the GLOBAL expert count: expected
    # pairs per expert = pairs/num_experts regardless of how many are local
    cap = int(cfg.moe_capacity_factor * pairs / max(cfg.num_experts, 1))
    cap = max(min(cap, pairs), 1)
    d = x.shape[-1]

    flat_e = top_i.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(n), k)
    flat_p = top_p.reshape(-1)
    local_e = flat_e - lo
    is_local = (local_e >= 0) & (local_e < n_local)
    sort_key = jnp.where(is_local, local_e, n_local)     # overflow group last
    order = jnp.argsort(sort_key)
    se, st, sp = sort_key[order], flat_t[order], flat_p[order]

    group_sizes = jnp.bincount(se, length=n_local + 1)[:n_local]
    seg_start = jnp.concatenate(
        [jnp.cumsum(group_sizes) - group_sizes,
         jnp.sum(group_sizes)[None]])                    # (n_local+1,)
    pos = jnp.arange(pairs) - seg_start[se]
    keep = (se < n_local) & (pos < cap)
    trash = n_local * cap
    dest = jnp.where(keep, se * cap + pos, trash)

    xs = jnp.take(x, st, axis=0)                         # (pairs, d)
    # gather-based dispatch: slot (e, c) reads sorted pair seg_start[e]+c.
    # (A scatter into the capacity buffer lowers to giant u32 index
    # broadcasts — (pairs, d)-sized temps the dry-run flagged; the gather
    # form is the TPU-friendly one.)
    src = jnp.clip(seg_start[:n_local, None] + jnp.arange(cap)[None, :],
                   0, pairs - 1)                         # (E_local, cap)
    slot_valid = jnp.arange(cap)[None, :] < group_sizes[:, None]
    disp = jnp.where(slot_valid[..., None],
                     jnp.take(xs, src, axis=0), 0).astype(x.dtype)

    act = jax.nn.silu if cfg.mlp == "swiglu" else (
        lambda v: jax.nn.gelu(v, approximate=True))
    wg = w_g.astype(x.dtype)
    wu = w_u.astype(x.dtype)
    wd = w_d.astype(x.dtype)
    h = act(jnp.einsum("ecd,edf->ecf", disp, wg)) * \
        jnp.einsum("ecd,edf->ecf", disp, wu)
    y = jnp.einsum("ecf,efd->ecd", h, wd)
    delta = _expert_delta(ctx, h, lo, n_local, d)
    if delta is not None:
        y = y + delta.astype(y.dtype)

    y_flat = jnp.concatenate(
        [y.reshape(n_local * cap, d), jnp.zeros((1, d), y.dtype)])
    y_pairs = jnp.take(y_flat, dest, axis=0)             # dropped -> zeros
    y_pairs = y_pairs * sp[:, None].astype(y.dtype)
    inv = jnp.argsort(order)
    y_pairs = jnp.take(y_pairs, inv, axis=0)
    # combine in the compute dtype: the gate weights promoted everything to
    # f32, which doubled the EP-combine psum wire bytes (§Perf iteration K2)
    return y_pairs.reshape(n, k, d).sum(axis=1).astype(x.dtype)


def moe_ffn(x: jnp.ndarray, w: dict, ctx: AdapterCtx, cfg: ModelConfig):
    """x: (B, T, d) -> (y, aux). Dispatches to shard_map EP when a mesh with
    a partitionable "model" axis is active; plain local path otherwise
    (identical math — CPU unit tests exercise the same code)."""
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    n_k, n_e = cfg.experts_per_token, cfg.num_experts
    logits, probs, top_p, top_i = _router(xf, w["router"], n_k)
    aux = (aux_losses(logits, probs, top_i, n_e)
           if cfg.moe_aux_weight > 0 else {})

    mesh = current_mesh()
    ep = (mesh is not None and "model" in mesh.axis_names
          and n_e % mesh.shape["model"] == 0 and mesh.shape["model"] > 1)
    if not ep:
        y = _moe_block(xf, top_p, top_i, 0, n_e, w["e_wg"], w["e_wu"],
                       w["e_wd"], ctx, cfg)
    else:
        n_model = mesh.shape["model"]
        n_local = n_e // n_model
        # token batch spec: keep only the leading batch axes that divide the
        # flat token count (decode with global_batch=1 degrades to fully
        # replicated tokens — every shard computes its local experts)
        bspec = ()
        n_tok = xf.shape[0]
        for ax in batch_axes(mesh):
            prod = int(np.prod([mesh.shape[a] for a in bspec])) if bspec else 1
            if n_tok % (mesh.shape[ax] * prod) == 0:
                bspec = bspec + (ax,)
        bspec = bspec or None
        fsdp = "data" in mesh.axis_names and \
            w["e_wg"].shape[-1] % mesh.shape["data"] == 0 and \
            w["e_wd"].shape[1] % mesh.shape["data"] == 0
        wg_spec = P("model", None, "data" if fsdp else None)
        wd_spec = P("model", "data" if fsdp else None, None)
        # adapter factors + task index ride along fully replicated
        # (shard_map must not close over tracers)
        adapter_in = (ctx.broadcast, ctx.layer, ctx.task)
        adapter_specs = jax.tree_util.tree_map(lambda _: P(), adapter_in)

        def shard_fn(xf_l, top_p_l, top_i_l, wg_l, wu_l, wd_l, adapt):
            bc, ly, task = adapt
            ctx_l = AdapterCtx(ctx.spec, bc, ly, task)
            if fsdp:
                wg_l = jax.lax.all_gather(wg_l, "data", axis=2, tiled=True)
                wu_l = jax.lax.all_gather(wu_l, "data", axis=2, tiled=True)
                wd_l = jax.lax.all_gather(wd_l, "data", axis=1, tiled=True)
            idx = jax.lax.axis_index("model")
            y_l = _moe_block(xf_l, top_p_l, top_i_l, idx * n_local, n_local,
                             wg_l, wu_l, wd_l, ctx_l, cfg)
            return jax.lax.psum(y_l, "model")

        y = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(bspec, None), P(bspec, None), P(bspec, None),
                      wg_spec, wg_spec, wd_spec, adapter_specs),
            out_specs=P(bspec, None),
            check_vma=False,
        )(xf, top_p, top_i, w["e_wg"], w["e_wu"], w["e_wd"], adapter_in)

    if cfg.num_shared_experts:
        y = y + dense_ffn(xf, {"wg": w["s_wg"], "wu": w["s_wu"],
                               "wd": w["s_wd"]}, ctx, cfg.mlp)
    return y.reshape(b, t, d), aux
