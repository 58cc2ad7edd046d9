"""Public jit'd wrappers for the Pallas kernels.

These handle the gap between model-land and kernel-land: leading batch dims,
tile padding on EVERY dim (M, N, K, r for the linear kernels; T, S for the
attention kernels — GeGLU d_ff, odd vocab slices and non-128-multiple
sequence lengths all pad up and slice back down), GQA head broadcast, dtype
policy, and backend dispatch — ``backend="auto"`` uses the Pallas kernel on
TPU and the pure-jnp oracle elsewhere (the CPU container runs kernels only
under interpret=True, which is for correctness tests, not speed).

Model code should not call this module directly: ``kernels/dispatch.py``
wraps these entry points behind a ``KernelPolicy`` (DESIGN.md §5) and is the
single seam the model/serving stack routes through.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import decode_attention as _decode_attn
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_attention import flash_attention_bwd as _flash_bwd
from repro.kernels.flash_attention import flash_attention_fwd as _flash_fwd
from repro.kernels.paged_attention import (
    paged_decode_attention as _paged_attn)
from repro.kernels.tt_linear import tt_linear as _tt_linear
from repro.kernels.tt_linear import tt_linear_batched_a as _tt_linear_ba
from repro.kernels.tt_linear import tt_linear_batched_a_w8 as _tt_ba_w8
from repro.kernels.tt_linear import tt_linear_w8 as _tt_linear_w8


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_ref(backend: str) -> bool:
    return backend == "ref" or (backend == "auto" and not _on_tpu())


def _interp(interpret: bool | None) -> bool:
    return (not _on_tpu()) if interpret is None else interpret


def _pick_tile(size: int, override: int, prefer: tuple) -> int:
    """Largest preferred tile that divides ``size``; otherwise the smallest
    preferred tile (the caller pads up to a multiple of it)."""
    if override:
        return override
    for t in prefer:
        if size % t == 0:
            return t
    return prefer[-1]


def _pad_to(x, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def tt_linear(x, w, a, b, *, alpha: float = 1.0, backend: str = "auto",
              interpret: bool | None = None, bm: int = 0, bn: int = 0,
              bk: int = 0):
    """Adapted linear layer y = x·W + α·(x·A)·B with arbitrary leading dims.

    x: (..., K); w: (K, N); a: (K, r); b: (r, N). No dim needs to be a tile
    multiple: M/N/K pad with zeros (exact — zero rows/cols contribute
    nothing) and the output slices back to (..., N).
    """
    if _use_ref(backend):
        return _ref.tt_linear_ref(x, w, a, b, alpha)
    lead = x.shape[:-1]
    k_dim = x.shape[-1]
    n_dim = w.shape[1]
    xf = x.reshape(-1, k_dim)
    bm = _pick_tile(xf.shape[0], bm, (256, 128))
    bn = _pick_tile(n_dim, bn, (256, 128))
    bk = _pick_tile(k_dim, bk, (512, 256, 128))
    xf, m0 = _pad_to(xf, 0, bm)
    xf, _ = _pad_to(xf, 1, bk)
    w, _ = _pad_to(w, 0, bk)
    w, n0 = _pad_to(w, 1, bn)
    a, _ = _pad_to(a, 0, bk)
    a, _ = _pad_to(a, 1, 128)            # r is kept whole per tile
    b, _ = _pad_to(b, 0, 128)
    b, _ = _pad_to(b, 1, bn)
    y = _tt_linear(xf, w, a, b, alpha=alpha, bm=bm, bn=bn, bk=bk,
                   interpret=_interp(interpret))
    return y[:m0, :n0].reshape(*lead, n0)


def _quant_tiles(k_dim: int, n_dim: int, scale, bn: int, bk: int):
    """Resolve (bn, bk, per_channel) for a w8 call: group-wise scales pin
    bk to the group size (one scale row per K tile; quantize_base
    guarantees the group divides K)."""
    groups = scale.shape[0]
    per_channel = groups == 1
    bn = _pick_tile(n_dim, bn, (256, 128))
    if per_channel:
        bk = _pick_tile(k_dim, bk, (512, 256, 128))
    else:
        bk = k_dim // groups
    return bn, bk, per_channel


def tt_linear_q(x, wq, scale, a, b, *, alpha: float = 1.0,
                backend: str = "auto", interpret: bool | None = None,
                bm: int = 0, bn: int = 0, bk: int = 0):
    """w8a16 adapted linear: int8 base W + f32 scales (kernels/quant.py),
    fp adapter factors. Same padding contract as ``tt_linear`` (padded K
    rows of the int8 W are zero, so they contribute nothing under any
    scale; padded scale columns are sliced off with the output).
    """
    if _use_ref(backend):
        return _ref.tt_linear_q_ref(x, wq, scale, a, b, alpha)
    lead = x.shape[:-1]
    k_dim = x.shape[-1]
    n_dim = wq.shape[1]
    xf = x.reshape(-1, k_dim)
    bm = _pick_tile(xf.shape[0], bm, (256, 128))
    bn, bk, _ = _quant_tiles(k_dim, n_dim, scale, bn, bk)
    xf, m0 = _pad_to(xf, 0, bm)
    xf, _ = _pad_to(xf, 1, bk)
    wq, _ = _pad_to(wq, 0, bk)
    wq, n0 = _pad_to(wq, 1, bn)
    scale, _ = _pad_to(scale, 1, bn)
    a, _ = _pad_to(a, 0, bk)
    a, _ = _pad_to(a, 1, 128)            # r is kept whole per tile
    b, _ = _pad_to(b, 0, 128)
    b, _ = _pad_to(b, 1, bn)
    y = _tt_linear_w8(xf, wq, scale, a, b, alpha=alpha, bm=bm, bn=bn,
                      bk=bk, interpret=_interp(interpret))
    return y[:m0, :n0].reshape(*lead, n0)


def tt_linear_batched_a_q(x, wq, scale, a, b, *, alpha: float = 1.0,
                          backend: str = "auto",
                          interpret: bool | None = None, bm: int = 0,
                          bn: int = 0, bk: int = 0):
    """w8a16 per-row-A adapted linear (the decode-slot task-routing form
    of ``tt_linear_batched_a`` over an int8 base)."""
    squeeze = x.ndim == 3
    if squeeze:
        assert x.shape[1] == 1, ("batched-A fusion is decode-shaped "
                                 "(one token per slot)", x.shape)
        x = x[:, 0]
    if _use_ref(backend):
        y = _ref.tt_linear_batched_a_q_ref(x, wq, scale, a, b, alpha)
        return y[:, None] if squeeze else y
    k_dim, n_dim = wq.shape
    bm = _pick_tile(x.shape[0], bm, (8,))
    bn, bk, _ = _quant_tiles(k_dim, n_dim, scale, bn, bk)
    x, m0 = _pad_to(x, 0, bm)
    x, _ = _pad_to(x, 1, bk)
    wq, _ = _pad_to(wq, 0, bk)
    wq, n0 = _pad_to(wq, 1, bn)
    scale, _ = _pad_to(scale, 1, bn)
    a, _ = _pad_to(a, 0, bm)
    a, _ = _pad_to(a, 1, bk)
    a, _ = _pad_to(a, 2, 128)
    b, _ = _pad_to(b, 0, 128)
    b, _ = _pad_to(b, 1, bn)
    y = _tt_ba_w8(x, wq, scale, a, b, alpha=alpha, bm=bm, bn=bn, bk=bk,
                  interpret=_interp(interpret))
    y = y[:m0, :n0]
    return y[:, None] if squeeze else y


def tt_linear_batched_a(x, w, a, b, *, alpha: float = 1.0,
                        backend: str = "auto",
                        interpret: bool | None = None, bm: int = 0,
                        bn: int = 0, bk: int = 0):
    """Per-row-A adapted linear: y[s] = x[s]·W + α·(x[s]·A[s])·B.

    x: (S, K) or (S, 1, K); w: (K, N); a: (S, K, r); b: (r, N). The leading
    S axis is the serving engine's slot axis — A[s] was gathered from the
    (4+1)d task axis by slot s's task id, so a mixed-task decode batch runs
    as ONE fused kernel call.
    """
    squeeze = x.ndim == 3
    if squeeze:
        assert x.shape[1] == 1, ("batched-A fusion is decode-shaped "
                                 "(one token per slot)", x.shape)
        x = x[:, 0]
    if _use_ref(backend):
        p = jnp.einsum("sk,skr->sr", x, a.astype(x.dtype),
                       preferred_element_type=jnp.float32)
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        y = (y + alpha * jnp.dot(p, b.astype(p.dtype),
                                 preferred_element_type=jnp.float32)
             ).astype(x.dtype)
        return y[:, None] if squeeze else y
    k_dim, n_dim = w.shape
    bm = _pick_tile(x.shape[0], bm, (8,))
    bn = _pick_tile(n_dim, bn, (256, 128))
    bk = _pick_tile(k_dim, bk, (512, 256, 128))
    x, m0 = _pad_to(x, 0, bm)
    x, _ = _pad_to(x, 1, bk)
    w, _ = _pad_to(w, 0, bk)
    w, n0 = _pad_to(w, 1, bn)
    a, _ = _pad_to(a, 0, bm)
    a, _ = _pad_to(a, 1, bk)
    a, _ = _pad_to(a, 2, 128)
    b, _ = _pad_to(b, 0, 128)
    b, _ = _pad_to(b, 1, bn)
    y = _tt_linear_ba(x, w, a, b, alpha=alpha, bm=bm, bn=bn, bk=bk,
                      interpret=_interp(interpret))
    y = y[:m0, :n0]
    return y[:, None] if squeeze else y


def flash_attention(q, k, v, *, causal: bool = True, backend: str = "auto",
                    interpret: bool | None = None, bq: int = 0,
                    bkv: int = 0):
    """GQA flash attention. q: (B, T, H, d); k, v: (B, S, KV, d).

    KV heads are broadcast to the query-head count before the per-head
    kernel call (zero-copy under XLA when G == 1). T and S need not be tile
    multiples: both pad up and the padded keys are masked inside the kernel
    (``kv_len``), padded query rows are sliced off.
    """
    if _use_ref(backend):
        g = q.shape[2] // k.shape[2]
        kk = jnp.repeat(k, g, axis=2) if g > 1 else k
        vv = jnp.repeat(v, g, axis=2) if g > 1 else v
        out = _ref.flash_attention_ref(
            q.transpose(0, 2, 1, 3), kk.transpose(0, 2, 1, 3),
            vv.transpose(0, 2, 1, 3), causal=causal)
        return out.transpose(0, 2, 1, 3)
    b, t, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, s, d)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, s, d)
    bq = _pick_tile(t, bq, (256, 128))
    bkv = _pick_tile(s, bkv, (256, 128))
    qh, t0 = _pad_to(qh, 1, bq)
    kh, s0 = _pad_to(kh, 1, bkv)
    vh, _ = _pad_to(vh, 1, bkv)
    out = _flash(qh, kh, vh, causal=causal, bq=bq, bkv=bkv,
                 interpret=_interp(interpret), kv_len=s0)
    return out[:, :t0].reshape(b, h, t0, d).transpose(0, 2, 1, 3)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        backend: str = "auto", interpret: bool | None = None,
                        bq: int = 0, bkv: int = 0):
    """Stats-emitting GQA flash forward for training.

    Same layout contract as ``flash_attention`` — q: (B, T, H, d); k, v:
    (B, S, KV, d) — but also returns the per-row log-sum-exp residual
    ``lse`` with shape (B, H, T) f32, which ``flash_attention_bwd`` needs
    to rebuild probability tiles without ever materializing (T, S).
    """
    if _use_ref(backend):
        g = q.shape[2] // k.shape[2]
        kk = jnp.repeat(k, g, axis=2) if g > 1 else k
        vv = jnp.repeat(v, g, axis=2) if g > 1 else v
        qh = q.transpose(0, 2, 1, 3)
        kh = kk.transpose(0, 2, 1, 3)
        vh = vv.transpose(0, 2, 1, 3)
        scale = q.shape[-1] ** -0.5
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            t, s_len = q.shape[1], k.shape[1]
            mask = jnp.arange(t)[:, None] >= jnp.arange(s_len)[None, :]
            s = jnp.where(mask[None, None], s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None])
        out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vh.dtype), vh)
        return out.transpose(0, 2, 1, 3).astype(q.dtype), lse
    b, t, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, s, d)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, s, d)
    bq = _pick_tile(t, bq, (256, 128))
    bkv = _pick_tile(s, bkv, (256, 128))
    qh, t0 = _pad_to(qh, 1, bq)
    kh, s0 = _pad_to(kh, 1, bkv)
    vh, _ = _pad_to(vh, 1, bkv)
    out, lse = _flash_fwd(qh, kh, vh, causal=causal, bq=bq, bkv=bkv,
                          interpret=_interp(interpret), kv_len=s0)
    out = out[:, :t0].reshape(b, h, t0, d).transpose(0, 2, 1, 3)
    return out, lse[:, 0, :t0].reshape(b, h, t0)


def _group_sum_kv(dx, b: int, kv: int, grp: int, s: int, d: int, dtype):
    """(B·H, S, d) query-head grads -> (B, S, KV, d): sum each GQA group
    of ``grp`` query heads back onto its shared KV head (the adjoint of
    the jnp.repeat broadcast), accumulated in f32."""
    dx = dx.astype(jnp.float32).reshape(b, kv, grp, s, d).sum(axis=2)
    return dx.transpose(0, 2, 1, 3).astype(dtype)          # (B, S, KV, d)


def flash_attention_bwd(q, k, v, o, lse, g, *, causal: bool = True,
                        backend: str = "auto", interpret: bool | None = None,
                        bq: int = 0, bkv: int = 0):
    """Blockwise GQA flash backward: (dq, dk, dv) from stashed residuals.

    q, o, g: (B, T, H, d); k, v: (B, S, KV, d); lse: (B, H, T) f32 from
    ``flash_attention_fwd``. dk/dv come back in KV-head layout — the GQA
    broadcast's adjoint sums each group of query heads in f32. Padded
    query rows carry a +1e30 lse sentinel so their recomputed probability
    tiles are exactly zero (no inf·0 NaNs); padded keys are masked by
    ``kv_len`` inside the kernels.
    """
    b, t, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    grp = h // kv
    if _use_ref(backend):
        kk = jnp.repeat(k, grp, axis=2) if grp > 1 else k
        vv = jnp.repeat(v, grp, axis=2) if grp > 1 else v
        dq, dk, dv = _ref.flash_attention_bwd_ref(
            q.transpose(0, 2, 1, 3), kk.transpose(0, 2, 1, 3),
            vv.transpose(0, 2, 1, 3), o.transpose(0, 2, 1, 3), lse,
            g.transpose(0, 2, 1, 3), causal=causal)
        dq = dq.transpose(0, 2, 1, 3)
        dk = _group_sum_kv(dk.reshape(b * h, s, d), b, kv, grp, s, d,
                           k.dtype)
        dv = _group_sum_kv(dv.reshape(b * h, s, d), b, kv, grp, s, d,
                           v.dtype)
        return dq, dk, dv
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), grp, axis=1).reshape(b * h, s, d)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), grp, axis=1).reshape(b * h, s, d)
    oh = o.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    gh = g.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    lseh = lse.reshape(b * h, t)
    bq = _pick_tile(t, bq, (256, 128))
    bkv = _pick_tile(s, bkv, (256, 128))
    qh, t0 = _pad_to(qh, 1, bq)
    oh, _ = _pad_to(oh, 1, bq)
    gh, _ = _pad_to(gh, 1, bq)
    pad = (-t) % bq
    if pad:
        # sentinel, not zero: exp(s - 1e30) == 0 keeps padded rows inert
        lseh = jnp.pad(lseh, ((0, 0), (0, pad)), constant_values=1e30)
    kh, s0 = _pad_to(kh, 1, bkv)
    vh, _ = _pad_to(vh, 1, bkv)
    dq, dk, dv = _flash_bwd(qh, kh, vh, oh, lseh, gh, causal=causal,
                            bq=bq, bkv=bkv, interpret=_interp(interpret),
                            kv_len=s0)
    dq = dq[:, :t0].reshape(b, h, t0, d).transpose(0, 2, 1, 3)
    dk = _group_sum_kv(dk[:, :s0], b, kv, grp, s0, d, k.dtype)
    dv = _group_sum_kv(dv[:, :s0], b, kv, grp, s0, d, v.dtype)
    return dq, dk, dv


def decode_attention(q, k, v, pos, *, backend: str = "auto",
                     interpret: bool | None = None, bkv: int = 0):
    """Cached single-token decode attention with per-row positions.

    q: (B, 1, H, d); k, v: (B, KV, S, d) head-major full-width caches
    (models/attention.py::init_cache); pos: (B,) — row b attends cache
    cells [0, pos[b]]. Returns (B, 1, H, d).
    """
    b, t, h, d = q.shape
    assert t == 1, "decode attention expects a single query token"
    kv, s = k.shape[1], k.shape[2]
    g = h // kv
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    if _use_ref(backend):
        qh = q[:, 0].reshape(b * h, d)
        kh = jnp.repeat(k, g, axis=1).reshape(b * h, s, d)
        vh = jnp.repeat(v, g, axis=1).reshape(b * h, s, d)
        out = _ref.decode_attention_ref(qh, kh, vh,
                                        jnp.repeat(pos, h))
        return out.reshape(b, 1, h, d)
    bkv = _pick_tile(s, bkv, (256, 128))
    # the GQA broadcast happens in the kernel's index map; padded tail
    # cells sit beyond every row's position -> masked by pos
    kp, _ = _pad_to(k, 2, bkv)
    vp, _ = _pad_to(v, 2, bkv)
    out = _decode_attn(q[:, 0], kp, vp, pos, bkv=bkv,
                       interpret=_interp(interpret))
    return out[:, None]


def paged_decode_attention(q, k_cache, v_cache, tables, pos, *,
                           k_scale=None, v_scale=None,
                           backend: str = "auto",
                           interpret: bool | None = None):
    """Block-table attention over a paged KV cache (serving engine decode
    + in-loop chunked prefill).

    q: (B, C, H, d) — C query tokens per slot, query c of slot b at
    absolute position pos[b] + c; k_cache, v_cache: (N, KV, page, d) flat
    head-major block pools; tables: (B, P) int32 logical-page ->
    physical-block map (sentinel >= N marks unallocated pages); pos: (B,).
    Returns (B, C, H, d): query c attends cache cells [0, pos[b] + c]. The
    Pallas kernel gathers blocks in its index map (scalar-prefetched
    table) so the gathered cache never materializes; the reference path
    gathers explicitly — same valid set, same logical order.

    k_scale/v_scale: optional (N, KV, 1, page) per-cell scale pools for the
    int8 KV mode — the kernel dequantizes pages in-register; the
    reference path dequantizes the pool up front (same math).
    """
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (q.shape[0],))
    if _use_ref(backend):
        if k_scale is not None:
            k_cache = (k_cache.astype(jnp.float32)
                       * jnp.swapaxes(k_scale, -1, -2))
            v_cache = (v_cache.astype(jnp.float32)
                       * jnp.swapaxes(v_scale, -1, -2))
            return _ref.paged_decode_attention_ref(
                q, k_cache, v_cache, tables, pos).astype(q.dtype)
        return _ref.paged_decode_attention_ref(q, k_cache, v_cache,
                                               tables, pos)
    # the kernel takes queries head-major, (B, H, C, d), like the pools
    out = _paged_attn(q.transpose(0, 2, 1, 3), k_cache, v_cache, tables,
                      pos, k_scale, v_scale, interpret=_interp(interpret))
    return out.transpose(0, 2, 1, 3)
