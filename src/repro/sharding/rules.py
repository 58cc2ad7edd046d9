"""Logical→physical sharding rules.

Parallelism map (DESIGN.md §4):
  * batch            -> ("pod", "data")     DP across pods, DP/FSDP within
  * weight d_model / d_ff "other" dim -> "data"   (FSDP storage sharding)
  * heads / d_ff compute dim          -> "model"  (TP)
  * MoE expert dim                    -> "model"  (EP, via shard_map)
  * KV-cache sequence dim             -> "model"  (sequence-sharded decode
                                         attention — GQA kv-heads are often
                                         < |model|, so we shard S instead)
  * long-context activations          -> sequence over "data" when
                                         global_batch < |data|

``maybe_shard`` degrades gracefully: axes missing from the ambient mesh are
dropped, and any dim not divisible by its axis-size product falls back to
replicated — so e.g. paligemma's 8 heads on a 16-way model axis simply stay
replicated instead of erroring (documented trade-off; the dry-run output
shows the real placement).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis in Auto (GSPMD) mode: the rules
    here place arrays by sharding constraints, not by explicit axis types
    (the installed JAX defaults ``make_mesh`` to Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def current_mesh():
    """The ambient mesh entered with ``jax.set_mesh`` (an ``AbstractMesh``,
    readable inside ``jit``), or None without one. Inside a ``shard_map``
    region every axis is manual and there is no mesh left to constrain
    against, so that counts as None too."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.are_all_axes_manual:
        return None
    return mesh


# Sequence-parallel sentinel: ``SEQ`` in a spec resolves to the configured
# sequence axis (default: none -> replicated). The dry-run / launcher enables
# SP for train/prefill shapes via ``set_seq_axis("model")`` — activations'
# T dim is then sharded on the residual stream and gathered inside
# attention/FFN (Korthikanti-style SP, expressed purely as constraints).
SEQ = "__seq__"
_seq_axis: list = [None]


def set_seq_axis(axis: str | None) -> None:
    _seq_axis[0] = axis


def get_seq_axis() -> str | None:
    return _seq_axis[0]


# --------------------------------------------------------------------------
# Serve-time tensor parallelism (DESIGN.md §9).
#
# The serving engine wraps its jitted step graphs in ``shard_map`` over a
# ("data", "model") mesh. Inside that manual-mesh region the ambient-mesh
# machinery above is inert (``current_mesh()`` is None, so ``maybe_shard``
# no-ops) and the per-shard call sites — attention head slicing, the
# vocab-striped readout — consult this TRACE-TIME context instead: it is
# set by the engine around tracing a sharded step and cleared after, the
# same pattern as the SEQ sentinel. ``None`` means "no serve TP" (the
# default for training, dry-runs and the single-device engine).
# --------------------------------------------------------------------------
_serve_tp: list = [None]


def set_serve_tp(axis: str | None, size: int = 0) -> None:
    """Install (or clear, with ``axis=None``) the serve-TP trace context:
    ``axis`` is the shard_map mesh axis name, ``size`` its length."""
    _serve_tp[0] = (axis, size) if axis is not None else None


def get_serve_tp() -> tuple | None:
    """Current serve-TP context as ``(axis_name, size)``, or None when no
    sharded serving step is being traced."""
    return _serve_tp[0]


def serve_tp_slice(x, axis: int):
    """This shard's contiguous chunk of dim ``axis`` under serve TP.

    x: any array whose dim ``axis`` divides the TP size (the engine
    validates heads / kv-heads / padded vocab up front). Returns the
    ``x.shape[axis] // tp``-wide slice owned by this shard — identity
    when no serve-TP context is active, so call sites can be
    unconditional. Slicing a dim that is NOT a contraction input is
    bitwise-safe: every output element's reduction order is unchanged.
    """
    tp = get_serve_tp()
    if tp is None:
        return x
    name, size = tp
    assert x.shape[axis] % size == 0, \
        f"dim {axis} of {x.shape} does not split {size} ways"
    n = x.shape[axis] // size
    return jax.lax.dynamic_slice_in_dim(
        x, jax.lax.axis_index(name) * n, n, axis)


def serve_tp_gather(x, axis: int):
    """All-gather shard chunks back into the full dim ``axis`` (tiled),
    inverse of ``serve_tp_slice``. Identity when no serve-TP context is
    active."""
    tp = get_serve_tp()
    if tp is None:
        return x
    return jax.lax.all_gather(x, tp[0], axis=axis, tiled=True)


# Row-parallel serve TP (DESIGN.md §11): when on, the second matmul of
# each attention / FFN pair keeps its input SHARDED (local head group /
# local d_ff stripe), row-slices the weight, and all-reduces the partial
# outputs — one psum of (B, d) instead of an all-gather of the (B, h·hd)
# activations. Partial sums change the reduction order, so this mode is
# near-parity (~1e-3), not bit-exact; the column-only default stays the
# parity oracle. Same trace-time lifecycle as the serve-TP context.
_serve_rp: list = [False]


def set_serve_rp(on: bool) -> None:
    """Enable/disable the row-parallel serve-TP variant for the step
    graph currently being traced (engine sets it alongside serve_tp)."""
    _serve_rp[0] = bool(on)


def get_serve_rp() -> bool:
    """True when the row-parallel serve-TP variant is being traced (only
    meaningful while a serve-TP context is installed)."""
    return _serve_rp[0] and _serve_tp[0] is not None


def serve_psum(x):
    """All-reduce partial outputs over the serve-TP axis (row-parallel
    epilogue). Identity when no serve-TP context is active."""
    tp = get_serve_tp()
    if tp is None:
        return x
    return jax.lax.psum(x, tp[0])


# --------------------------------------------------------------------------
# Serve-time data parallelism (DESIGN.md §11): the engine stripes decode
# SLOTS and paged-pool BLOCKS across the "data" mesh axis — each data
# shard owns max_batch/|data| slots and num_blocks/|data| pool blocks
# with LOCAL ids, so the whole per-replica step body runs unchanged on
# local shapes. The context mirrors the serve-TP one: installed around
# tracing a dp-sharded step, cleared after.
# --------------------------------------------------------------------------
_serve_dp: list = [None]


def set_serve_dp(axis: str | None, size: int = 0) -> None:
    """Install (or clear, with ``axis=None``) the serve-DP trace context:
    ``axis`` is the shard_map data-axis name, ``size`` its length."""
    _serve_dp[0] = (axis, size) if axis is not None else None


def get_serve_dp() -> tuple | None:
    """Current serve-DP context as ``(axis_name, size)``, or None when no
    data-striped serving step is being traced."""
    return _serve_dp[0]


def serve_dp_index():
    """This replica's index on the serve-DP axis (0 without a context) —
    the host addresses per-replica work by global slot/replica id and the
    step graphs gate on this to act only on their own stripe."""
    dp = get_serve_dp()
    if dp is None:
        return jnp.int32(0)
    return jax.lax.axis_index(dp[0])


def serve_mesh(shape, axes: tuple = ("data", "model")) -> Mesh:
    """Serving mesh over the local devices: ``shape`` is (data, model) —
    "model" is the tensor-parallel axis the engine shards kv-heads /
    vocab on, "data" is reserved for replica DP (state is replicated
    across it today). Raises with the XLA_FLAGS hint when the host does
    not expose enough devices (CPU tests force fake devices via
    ``--xla_force_host_platform_device_count=N``)."""
    shape = tuple(int(s) for s in shape)
    if len(shape) != len(axes):
        raise ValueError(
            f"serve mesh shape {shape} must have one entry per axis "
            f"{axes}")
    need = int(np.prod(shape))
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"serve mesh {dict(zip(axes, shape))} needs {need} devices "
            f"but only {have} are visible (on CPU force fake devices "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need})")
    return auto_mesh(shape, axes)


def serve_cache_pspec(caches, axis: str = "model",
                      dp_axis: str | None = None):
    """PartitionSpec pytree sharding serving KV caches on the KV-HEAD
    axis, axis 2 of every leaf (all of them head-major):

      paged pools   (nb, num_blocks, KV, page, hd)  k / v
      scale pools   (nb, num_blocks, KV, 1, page)   k_s / v_s (int8 KV)
      dense caches  (nb, B, KV, S, hd)              k / v

    Page/block/sequence dims stay whole, so one host-side block id
    indexes every shard's pool identically (the BlockManager never needs
    to know about the mesh). ``dp_axis`` additionally stripes the BLOCKS
    axis (axis 1, paged pools only) across data replicas (DESIGN.md
    §11): each replica then owns a private num_blocks/|data| pool whose
    LOCAL block ids its per-replica BlockManager hands out."""
    def one(leaf):
        spec = [None] * leaf.ndim
        spec[2] = axis
        if dp_axis is not None:
            spec[1] = dp_axis
        return P(*spec)
    return jax.tree_util.tree_map(one, caches)


def serve_cache_sharding(caches, mesh: Mesh, axis: str = "model",
                         dp_axis: str | None = None):
    """NamedSharding pytree for ``device_put``-placing serving KV caches
    kv-head-sharded on ``axis`` (and block-striped on ``dp_axis``, see
    serve_cache_pspec)."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        serve_cache_pspec(caches, axis, dp_axis))


def _resolve(entry):
    if entry == SEQ:
        return _seq_axis[0]
    if isinstance(entry, tuple):
        resolved = tuple(_seq_axis[0] if e == SEQ else e for e in entry)
        return tuple(e for e in resolved if e is not None) or None
    return entry


def _filter_spec(mesh: Mesh, spec: P, shape) -> P:
    """Drop mesh axes that don't exist / don't divide the dim."""
    out = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        entry = _resolve(entry)
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        names = tuple(n for n in names if n in mesh.axis_names)
        size = int(np.prod([mesh.shape[n] for n in names])) if names else 1
        if not names or size == 1 or dim % size != 0:
            out.append(None)
        else:
            out.append(names if len(names) > 1 else names[0])
    return P(*out)


def maybe_shard(x, *spec_entries) -> jax.Array:
    """with_sharding_constraint that no-ops without an ambient mesh and
    auto-filters invalid axes. Usable identically in CPU unit tests and in
    the 512-device dry-run."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = _filter_spec(mesh, P(*spec_entries), x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_axes(mesh: Mesh | None = None) -> tuple:
    mesh = mesh or current_mesh()
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


BATCH = ("pod", "data")  # logical batch axes, filtered per-mesh by maybe_shard


# --------------------------------------------------------------------------
# parameter placement: pytree of PartitionSpec mirroring the params pytree.
# Conventions (leaf shapes, nb = stacked super-block dim first where present):
#   embed        (V, d)            -> P("model", "data")
#   in-proj      (nb, d_in, d_out) -> P(None, "data", "model")
#   out-proj     (nb, d_in, d_out) -> P(None, "model", "data")
#   experts      (nb, E, d, ff)    -> P(None, "model", None, "data")
#   vectors      (..., d)          -> replicated
# --------------------------------------------------------------------------

_IN_PROJ = {"wq", "wk", "wv", "wg", "wu", "w_in", "w_qkv", "w_up",
            "s_wg", "s_wu"}
_OUT_PROJ = {"wo", "wd", "w_out", "w_down", "s_wd"}
_EXPERT_IN = {"e_wg", "e_wu"}
_EXPERT_OUT = {"e_wd"}


def spec_for_param(path: str, shape) -> P:
    """Sharding spec from the parameter's name + rank (see conventions)."""
    leaf = path.split("/")[-1]
    nd = len(shape)
    if leaf in ("tok", "embed", "lm_head"):
        return P("model", "data") if nd == 2 else P()
    if leaf in _EXPERT_IN:
        return P(None, "model", None, "data") if nd == 4 else P("model", None, "data")
    if leaf in _EXPERT_OUT:
        return P(None, "model", "data", None) if nd == 4 else P("model", "data", None)
    if leaf in _IN_PROJ:
        return P(*( [None] * (nd - 2) + ["data", "model"] ))
    if leaf in _OUT_PROJ:
        return P(*( [None] * (nd - 2) + ["model", "data"] ))
    # norms, biases, conv kernels, gates, adapter cores: replicated
    return P()


def params_pspec(params) -> dict:
    """PartitionSpec pytree for a params pytree (path-based rules)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def name(kp):
        parts = []
        for k in kp:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
        return "/".join(parts)

    specs = {name(kp): spec_for_param(name(kp), leaf.shape)
             for kp, leaf in flat}
    # rebuild as pytree
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params),
        [spec_for_param(name(kp), leaf.shape) for kp, leaf in flat])


def cache_spec_for(path: str, shape) -> P:
    """Decode-cache placement: KV caches are sequence-sharded over "model"
    (kv-heads are often < |model|) and batch-sharded over ("pod","data");
    mamba state shards d_inner over "model"; recurrent xlstm scalars are
    tiny and replicate (see DESIGN.md §4).

    Cache leaves are stacked over super-blocks: shapes carry a leading nb
    dim (transformer.init_caches), hence the leading None below.
    """
    leaf = path.split("/")[-1]
    nd = len(shape)
    if leaf in ("k", "v") and nd == 5:       # (nb, B, KV, S, hd)
        return P(None, BATCH, None, "model", None)
    if leaf == "h" and nd == 4:              # (nb, B, di, ds) mamba state
        return P(None, BATCH, "model", None)
    if leaf == "conv" and nd == 4:           # (nb, B, K-1, di)
        return P(None, BATCH, None, "model")
    if nd >= 2:
        return P(None, BATCH)
    return P()


def _paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for kp, leaf in flat:
        parts = []
        for k in kp:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        out.append(("/".join(parts), leaf))
    return out


def tree_sharding(tree, mesh: Mesh, spec_fn):
    """NamedSharding pytree from a (path, shape) -> PartitionSpec rule."""
    leaves = [NamedSharding(mesh, _filter_spec(mesh, spec_fn(p, leaf.shape),
                                               leaf.shape))
              for p, leaf in _paths(tree)]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(tree), leaves)


def params_sharding(params, mesh: Mesh):
    """NamedSharding pytree (filtered for divisibility) for device_put /
    in_shardings."""
    def one(path_spec, leaf):
        return NamedSharding(mesh, _filter_spec(mesh, path_spec, leaf.shape))
    return jax.tree_util.tree_map(one, params_pspec(params), params)


def reshard_after_reshape(tree, mesh: Mesh | None = None):
    """device_put a host-reshaped pytree back onto the ambient GSPMD mesh.

    Built for the mid-training DMRG sweep: the sweep runs host-side and
    returns cores / transported moments with NEW bond shapes, so their old
    shardings are stale. This re-places every leaf under the standard
    parameter rules (``spec_for_param`` — adapter cores and moments
    replicate), ensuring each device holds the rank-changed arrays before
    the next jitted train step retraces against them. No-op without an
    ambient mesh (single-device training and unit tests)."""
    if mesh is None and current_mesh() is not None:
        mesh = jax.sharding.get_mesh()       # host side: the concrete mesh
    if mesh is None:
        return tree
    return jax.device_put(tree, tree_sharding(tree, mesh, spec_for_param))
