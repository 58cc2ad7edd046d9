"""Mixture-of-Experts with expert parallelism.

A layer is a router, a dispatch and an optional shared expert; the router
and the dispatch are chosen apart.

Router (``cfg.moe_scoring``, the published ``scoring_func``):

* "softmax": softmax top-k, renormalised (Switch/GShard), with the
  load-balance and router-z losses when ``moe_aux_weight`` > 0;
* "sigmoid": the DeepSeek-V3 / Kimi K2 router (``noaux_tc``), see
  ``sigmoid_router``.

Dispatch (``cfg.moe_dispatch``):

* "capacity" (GShard): token-expert pairs are sorted by expert, each expert
  processes up to C = capacity_factor · pairs/E slots as a *batched* GEMM
  (E, C, d) × (E, d, ff), and overflow pairs are dropped. It stays because
  its batched-GEMM form is what the dry-run HLO costs faithfully at full
  size on any backend (DESIGN.md §3), and the capacity configurations are
  tested and costed with its drops;
* "dropless": every pair routed to a held expert is computed, through a
  grouped matmul over expert-sorted rows; see ``dropless_block``.

A model may hold a share of the experts (``cfg.experts_held`` from
``cfg.expert_offset``): the router still scores every expert, and pairs
routed to experts held elsewhere are left out.

Expert parallelism is explicit ``jax.shard_map`` over the "model" mesh axis,
for either dispatch: each shard owns E_held/|model| experts, dispatches
exactly the pairs routed to them, and the per-token combine is a single
psum over "model". Expert weights are additionally FSDP-sharded over "data"
on the d_ff dim and all-gathered per-layer inside the shard (DESIGN.md §4).

MetaTT hook: with the (4+E)D variant (paper §4 "expert partitions"), the
expert down-projection gets a TT delta whose middle r×r core is indexed by
the expert owning each block of rows (a capacity block, or a row tile of
the dropless layer) — one tiny batched einsum, zero extra large GEMMs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.config.base import ModelConfig
from repro.kernels import dispatch
from repro.models.layers import AdapterCtx, dense_ffn
from repro.sharding import batch_axes, current_mesh


#: the step's counters that ``moe_ffn`` returns beside its losses (summed
#: over layers, except the maxima); they never enter the loss
COUNTERS = {"moe_pairs_here": jnp.add, "moe_max_expert_rows": jnp.maximum}
#: what ``moe_ffn`` returns per layer when ``cfg.moe_record_routing``: the
#: chosen experts (1, tokens, k), global ids; layers stack on the leading
#: axis, and they never enter the loss
RECORDS = ("moe_routing",)
#: rows of a tile of the dropless layer's grouped matmul: every expert's
#: rows start on a multiple of it
GMM_ROWS = 128
#: tokens the dropless layer dispatches at once
DROPLESS_CHUNK = 4096
#: the dropless layer's usual rows buffer holds this many times a chunk's
#: even share of the pairs (c·k·held / E); a chunk that routes more here
#: takes the buffer that holds every pair it can route
EVEN_SHARES = 4


def softmax_router(x, w_router, n_k: int):
    """Softmax top-k, renormalised. Returns (logits, probs, top weights,
    top experts)."""
    logits = (x @ w_router.astype(x.dtype)).astype(jnp.float32)   # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, n_k)                      # (N, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return logits, probs, top_p, top_i


def sigmoid_router(x, w_router, bias, n_k: int, scale: float):
    """The DeepSeek-V3 / Kimi K2 router (``noaux_tc``), in float32:

        s = sigmoid(x·W_r)                          (N, E), every expert
        chosen = top-k of s + b_corr                (the bias only chooses)
        weight = s[chosen] / (Σ s[chosen] + 1e-20) · routed_scaling_factor

    Returns (top weights (N, k), top experts (N, k))."""
    logits = jnp.matmul(x.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, top_i = jax.lax.top_k(s + bias.astype(jnp.float32), n_k)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    top_w = top_s / (top_s.sum(-1, keepdims=True) + 1e-20) * scale
    return top_w, top_i


def aux_losses(logits, probs, top_i, num_experts: int) -> dict:
    """Standard load-balance + router-z losses (Switch/GShard)."""
    n = probs.shape[0]
    onehot = jax.nn.one_hot(top_i, num_experts, dtype=jnp.float32)  # (N,k,E)
    frac_tokens = onehot.sum((0, 1)) / (n * top_i.shape[-1])
    frac_probs = probs.mean(0)
    lb = num_experts * jnp.sum(frac_tokens * frac_probs)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return {"load_balance": lb, "router_z": z}


def route(xf, w: dict, cfg: ModelConfig):
    """The router ``cfg.moe_scoring`` names: (top weights (N, k), top
    experts (N, k), its losses)."""
    if cfg.moe_scoring == "sigmoid":
        top_w, top_i = sigmoid_router(xf, w["router"], w["router_bias"],
                                      cfg.experts_per_token,
                                      cfg.moe_routed_scale)
        return top_w, top_i, {}
    logits, probs, top_p, top_i = softmax_router(xf, w["router"],
                                                 cfg.experts_per_token)
    aux = (aux_losses(logits, probs, top_i, cfg.num_experts)
           if cfg.moe_aux_weight > 0 else {})
    return top_p * cfg.moe_routed_scale, top_i, aux


def _expert_delta(ctx: AdapterCtx, h: jnp.ndarray, experts, d_out: int):
    """Adapter delta on the expert down-projection. h: (G, R, ff), each of
    its G blocks of rows owned by one expert; experts: (G,) their global
    ids.

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
        c = jnp.take(ctx.layer["c"][:, mi], experts, axis=0)   # (G, r, r)
        p = h @ g1                                      # (G, R, r)
        return cfg.alpha * (jnp.einsum("gcr,grs->gcs", p,
                                       c.astype(h.dtype)) @ g4)
    if ctx.task is not None and jnp.ndim(ctx.task) >= 1:
        # h is expert-sorted (G, R, ff): its leading axis is experts, so a
        # per-request (B,) task vector cannot be gathered against it (and
        # would silently mis-route whenever G == B).
        raise NotImplementedError(
            "per-request task vectors cannot index the expert-sorted "
            "moe_down delta; use a scalar task")
    from repro.peft import api as peft_api
    return peft_api.adapter_delta(spec, ctx.broadcast, ctx.layer, h,
                                  "moe_down", task=ctx.task)


def _capacity_block(x, top_w, top_i, lo, n_local: int, w_g, w_u, w_d,
                    ctx: AdapterCtx, cfg: ModelConfig):
    """Capacity-dispatched expert FFN for experts [lo, lo+n_local).

    x: (N, d) tokens (all local tokens); returns ((N, d) partial output
    covering exactly the kept pairs owned by these experts, the pairs each
    kept (n_local,)).
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
    flat_p = top_w.reshape(-1)
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
    delta = _expert_delta(ctx, h, lo + jnp.arange(n_local), d)
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
    y = y_pairs.reshape(n, k, d).sum(axis=1).astype(x.dtype)
    return y, jnp.minimum(group_sizes, cap)


def _buffer_rows(pairs: int, n_local: int) -> int:
    """Rows of a buffer that holds ``pairs`` expert-sorted pairs with each
    of ``n_local`` groups starting on a tile."""
    tm = GMM_ROWS
    return -(-(pairs + n_local * (tm - 1)) // tm) * tm


def _dropless_rows(x, top_w, top_i, lo, n_local: int, rows: int, w_g, w_u,
                   w_d, ctx: AdapterCtx):
    """The held experts' SwiGLU part of one chunk through a rows buffer of
    ``rows`` rows, which must hold the chunk's pairs routed here with each
    expert's group padded to a tile."""
    n, k = top_i.shape
    tm = GMM_ROWS
    e = top_i.reshape(-1) - lo
    key = jnp.where((e >= 0) & (e < n_local), e, n_local)
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    sizes = jnp.bincount(key, length=n_local + 1)[:n_local]
    padded = -(-sizes // tm) * tm
    start = jnp.cumsum(sizes) - sizes
    pstart = jnp.cumsum(padded) - padded
    ke = jnp.minimum(key_s, n_local - 1)
    # sorted pair j goes to row pstart[e] + (j - start[e]) of its expert's
    # group; pairs routed elsewhere go past the buffer and are dropped
    row_s = jnp.where(key_s < n_local,
                      pstart[ke] + jnp.arange(n * k) - start[ke], rows)
    # each row's token (n, past the tokens, for the rows no pair fills)
    # and gate weight
    row_tok = jnp.full((rows,), n, jnp.int32).at[row_s].set(
        (order // k).astype(jnp.int32), mode="drop")
    row_w = jnp.zeros((rows,), jnp.float32).at[row_s].set(
        top_w.reshape(-1)[order].astype(jnp.float32), mode="drop")
    x_rows = jnp.take(x, row_tok, axis=0, mode="fill", fill_value=0)
    tile_end = jnp.cumsum(padded)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(rows // tm) * tm,
                         side="right"), n_local - 1).astype(jnp.int32)
    n_used = (tile_end[-1] // tm).astype(jnp.int32)[None]
    pol = ctx.policy

    def gmm(a, wt):
        return dispatch.grouped_matmul(a, wt.astype(a.dtype), tile_group,
                                       n_used, tm=tm, policy=pol)

    h = jax.nn.silu(gmm(x_rows, w_g)) * gmm(x_rows, w_u)
    y_rows = gmm(h, w_d)
    delta = _expert_delta(ctx, h.reshape(rows // tm, tm, -1),
                          lo + tile_group, x.shape[-1])
    if delta is not None:
        y_rows = y_rows + delta.reshape(y_rows.shape).astype(y_rows.dtype)
    # y[t] = Σ_rows of t  w_row · y_row: a weighted scatter-add of the rows
    y = jnp.zeros((n, x.shape[-1]), jnp.float32).at[row_tok].add(
        y_rows.astype(jnp.float32) * row_w[:, None], mode="drop")
    return y.astype(x.dtype)


def _dropless_chunk(x, top_w, top_i, lo, n_local: int, num_experts: int,
                    w_g, w_u, w_d, ctx: AdapterCtx):
    """The held experts' SwiGLU part for one chunk of c tokens, and the rows
    each held expert took. x (c, d); top_w, top_i (c, k). The usual buffer
    holds ``EVEN_SHARES`` times the chunk's even share of pairs; a chunk
    whose pairs routed here, padded to tiles, do not fit takes the buffer of
    every pair it can route (c·min(k, n_local) of them)."""
    n, k = top_i.shape
    e = top_i.reshape(-1) - lo
    sizes = jnp.bincount(jnp.where((e >= 0) & (e < n_local), e, n_local),
                         length=n_local + 1)[:n_local]
    full = _buffer_rows(n * min(k, n_local), n_local)
    even = -(-n * k * n_local // num_experts)
    usual = min(_buffer_rows(EVEN_SHARES * even, n_local), full)

    def through(rows):
        # under remat: a branch keeps no residuals of its own, so the
        # backward does not hold the larger branch's buffers either way
        return jax.checkpoint(lambda a: _dropless_rows(
            *a, lo, n_local, rows, w_g, w_u, w_d, ctx))

    args = (x, top_w, top_i)
    if usual == full:
        return through(full)(args), sizes
    need = jnp.sum(-(-sizes // GMM_ROWS)) * GMM_ROWS
    return jax.lax.cond(need <= usual, through(usual), through(full),
                        args), sizes


def dropless_block(x, top_w, top_i, lo, n_local: int, w_g, w_u, w_d,
                   ctx: AdapterCtx, cfg: ModelConfig):
    """The part of the expert layer that experts [lo, lo + n_local) give,
    for every pair routed to them; none is dropped. x (N, d); top_w, top_i
    (N, k) the router's weights and experts over all ``cfg.num_experts``.

        y[t] = Σ_{j: lo <= e_tj < lo + n_local} w_tj · SwiGLU_{e_tj}(x[t])
        SwiGLU_e(x) = (silu(x·Wg_e) ⊙ (x·Wu_e))·Wd_e  (+ the moe_down delta)

    Pairs routed to other experts are left out: their experts live on
    other chips, and what they add is not computed here. Tokens go
    ``DROPLESS_CHUNK`` at a time. A chunk's pairs are sorted by expert into
    a rows buffer whose groups start on a ``GMM_ROWS`` boundary; three
    grouped matmuls (``moe_gmm``) run over the tiles that hold rows, and a
    weighted scatter-add of the rows gives the tokens. The buffer is sized
    by the pairs a chunk of c tokens is expected to route here,
    ``EVEN_SHARES`` times its even share c·k·n_local/E, so its gathers,
    scatters and matmul grid follow that and not c·k; a chunk that routes
    more here runs through a buffer of c·min(k, n_local) rows, which holds
    every pair even when every token routes here. Returns (y (N, d), the
    rows each held expert took (n_local,))."""
    if cfg.mlp != "swiglu":
        raise NotImplementedError("the dropless layer runs SwiGLU experts")
    n, d = x.shape
    c = min(n, DROPLESS_CHUNK)
    if n % c:
        raise ValueError(f"{n} tokens do not split into chunks of {c}")

    def one(args):
        return _dropless_chunk(*args, lo, n_local, cfg.num_experts, w_g, w_u,
                               w_d, ctx)

    if n == c:
        return one((x, top_w, top_i))
    y, sizes = jax.lax.map(jax.checkpoint(one), (
        x.reshape(n // c, c, d), top_w.reshape(n // c, c, -1),
        top_i.reshape(n // c, c, -1)))
    return y.reshape(n, d), sizes.sum(0)


_BLOCKS = {"capacity": _capacity_block, "dropless": dropless_block}


def _expert_parallel(mesh, cfg: ModelConfig, n_tok: int):
    """(experts a shard holds, token batch spec, FSDP over "data") when a
    mesh with a partitionable "model" axis is active, else None."""
    held = cfg.local_experts
    if not (mesh is not None and "model" in mesh.axis_names
            and mesh.shape["model"] > 1 and held % mesh.shape["model"] == 0):
        return None
    # token batch spec: keep only the leading batch axes that divide the
    # flat token count (decode with global_batch=1 degrades to fully
    # replicated tokens — every shard computes its local experts)
    bspec = ()
    for ax in batch_axes(mesh):
        prod = int(np.prod([mesh.shape[a] for a in bspec])) if bspec else 1
        if n_tok % (mesh.shape[ax] * prod) == 0:
            bspec = bspec + (ax,)
    return held // mesh.shape["model"], bspec or None


def moe_ffn(x: jnp.ndarray, w: dict, ctx: AdapterCtx, cfg: ModelConfig):
    """x: (B, T, d) -> (y, aux): the router, the held experts' part through
    the dispatch, and the shared expert, each under its named scope. aux:
    the router's losses, the ``COUNTERS`` and, with
    ``cfg.moe_record_routing``, the ``RECORDS``. Dispatches to shard_map EP
    when a mesh with a partitionable "model" axis is active; plain local
    path otherwise (identical math — CPU unit tests exercise the same
    code)."""
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    with jax.named_scope("moe.router"):
        top_w, top_i, aux = route(xf, w, cfg)
    block = _BLOCKS[cfg.moe_dispatch]
    mesh = current_mesh()
    ep = _expert_parallel(mesh, cfg, xf.shape[0])
    with jax.named_scope("moe.experts"):
        if ep is None:
            y, sizes = block(xf, top_w, top_i, cfg.expert_offset,
                             cfg.local_experts, w["e_wg"], w["e_wu"],
                             w["e_wd"], ctx, cfg)
            pairs, most = jnp.sum(sizes), jnp.max(sizes)
        else:
            y, pairs, most = _ep_experts(xf, top_w, top_i, w, ctx, cfg, mesh,
                                         block, *ep)
    aux = dict(aux, moe_pairs_here=pairs.astype(jnp.float32),
               moe_max_expert_rows=most.astype(jnp.float32))
    if cfg.moe_record_routing:
        aux["moe_routing"] = top_i.astype(jnp.int32)[None]
    if cfg.num_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + dense_ffn(xf, {"wg": w["s_wg"], "wu": w["s_wu"],
                                   "wd": w["s_wd"]}, ctx, cfg.mlp)
    return y.reshape(b, t, d), aux


def _ep_experts(xf, top_w, top_i, w, ctx: AdapterCtx, cfg: ModelConfig,
                mesh, block, n_local: int, bspec):
    """The experts' part under shard_map: each "model" shard runs ``block``
    over its n_local experts and the shards' parts are summed."""
    fsdp = "data" in mesh.axis_names and \
        w["e_wg"].shape[-1] % mesh.shape["data"] == 0 and \
        w["e_wd"].shape[1] % mesh.shape["data"] == 0
    wg_spec = P("model", None, "data" if fsdp else None)
    wd_spec = P("model", "data" if fsdp else None, None)
    # adapter factors + task index ride along fully replicated
    # (shard_map must not close over tracers)
    adapter_in = (ctx.broadcast, ctx.layer, ctx.task)
    adapter_specs = jax.tree_util.tree_map(lambda _: P(), adapter_in)

    def shard_fn(xf_l, top_w_l, top_i_l, wg_l, wu_l, wd_l, adapt):
        bc, ly, task = adapt
        ctx_l = AdapterCtx(ctx.spec, bc, ly, task, ctx.policy)
        if fsdp:
            wg_l = jax.lax.all_gather(wg_l, "data", axis=2, tiled=True)
            wu_l = jax.lax.all_gather(wu_l, "data", axis=2, tiled=True)
            wd_l = jax.lax.all_gather(wd_l, "data", axis=1, tiled=True)
        lo = cfg.expert_offset + jax.lax.axis_index("model") * n_local
        y_l, sizes = block(xf_l, top_w_l, top_i_l, lo, n_local, wg_l, wu_l,
                           wd_l, ctx_l, cfg)
        if bspec:                   # an expert's rows from every token shard
            sizes = jax.lax.psum(sizes, bspec)
        return (jax.lax.psum(y_l, "model"),
                jax.lax.psum(jnp.sum(sizes), "model"),
                jax.lax.pmax(jnp.max(sizes), "model"))

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(bspec, None), P(bspec, None), P(bspec, None),
                  wg_spec, wg_spec, wd_spec, adapter_specs),
        out_specs=(P(bspec, None), P(), P()),
        check_vma=False,
    )(xf, top_w, top_i, w["e_wg"], w["e_wu"], w["e_wd"], adapter_in)
