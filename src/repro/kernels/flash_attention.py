"""Flash attention (forward AND backward) as Pallas TPU kernels.

Blockwise online-softmax attention: the (T, S) score matrix never
materializes in HBM — each (bq, bkv) tile lives in VMEM with running
(row-max m, row-sum l, output acc) scratch carried across the innermost
(sequential) KV grid dimension. This is the TPU-native replacement for the
pure-XLA chunked path in models/attention.py (same math; the XLA path is
what the CPU dry-run lowers, this kernel is the TPU fast path).

The backward is the standard FlashAttention two-pass recompute: the
forward stashes one per-row statistic (the log-sum-exp ``lse = m +
log(l)``), and two kernels rebuild each (bq, bkv) probability tile from it
on the fly — ``p = exp(s − lse)`` — so the backward never holds more than
one tile of scores either. ``_bwd_dq_kernel`` accumulates dq over KV
blocks; ``_bwd_dkv_kernel`` accumulates dk/dv over query blocks, with the
per-row correction term ``D = rowsum(dO ⊙ O)`` precomputed outside (an
O(T·d) contraction). Gradient tiles strictly above the causal diagonal are
skipped in both, mirroring the forward.

Strictly-above-diagonal tiles are skipped under causal masking (the
``pl.when`` guard), halving work for training/prefill.

Layout: (B·H, T, d) per head — GQA callers broadcast kv heads before the
call and reduce dk/dv over the head group after it (ops.py). d is kept
whole per tile (d ≤ 256 across the zoo). Validated in interpret mode
against kernels/ref.py::flash_attention_ref / flash_attention_bwd_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float, causal: bool,
            bq: int, bkv: int, kv_steps: int, kv_len: int,
            with_stats: bool = False):
    if with_stats:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        lse_ref, (m_ref, l_ref, acc_ref) = None, rest
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (j * bkv <= i * bq + bq - 1) if causal else True

    @pl.when(run)
    def _block():
        q = q_ref[0]                                   # (bq, d)
        k = k_ref[0]                                   # (bkv, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bkv)
        ki = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        valid = ki < kv_len                            # kv tile padding
        if causal:
            qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            valid &= qi >= ki
        s = jnp.where(valid, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == kv_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        if with_stats:
            # per-row log-sum-exp: the one statistic the blockwise backward
            # needs to rebuild probability tiles as p = exp(s - lse)
            lse_ref[0] = (m_ref[...] + jnp.log(l))[None, :]


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bkv",
                                             "interpret", "kv_len"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, bq: int = 256, bkv: int = 256,
                    interpret: bool = True, kv_len: int = 0) -> jnp.ndarray:
    """q: (BH, T, d); k, v: (BH, S, d) -> (BH, T, d).

    kv_len: number of *real* key/value rows (0 -> S). Callers that pad S up
    to a bkv multiple pass the unpadded length so the tail keys are masked
    out of the softmax (zero-padded keys would otherwise contribute
    exp(0) mass under non-causal attention).
    """
    bh, t, d = q.shape
    s_len = k.shape[1]
    assert t % bq == 0 and s_len % bkv == 0, (t, s_len, bq, bkv)
    grid = (bh, t // bq, s_len // bkv)
    kernel = functools.partial(
        _kernel, scale=d ** -0.5, causal=causal, bq=bq, bkv=bkv,
        kv_steps=grid[2], kv_len=kv_len or s_len)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bkv",
                                             "interpret", "kv_len"))
def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, bq: int = 256, bkv: int = 256,
                        interpret: bool = True, kv_len: int = 0):
    """Stats-emitting forward for training: same kernel as
    ``flash_attention`` plus a second output carrying the per-row
    log-sum-exp — the residual the blockwise backward rebuilds probability
    tiles from. Returns ``(out (BH, T, d), lse (BH, 1, T) f32)`` — the
    row statistic lies along lanes, so its (1, 1, bq) blocks meet the
    TPU's block rule (last two block dims whole or (8, 128)-aligned)."""
    bh, t, d = q.shape
    s_len = k.shape[1]
    assert t % bq == 0 and s_len % bkv == 0, (t, s_len, bq, bkv)
    grid = (bh, t // bq, s_len // bkv)
    kernel = functools.partial(
        _kernel, scale=d ** -0.5, causal=causal, bq=bq, bkv=bkv,
        kv_steps=grid[2], kv_len=kv_len or s_len, with_stats=True)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# blockwise backward (standard FlashAttention two-pass recompute): each
# kernel rebuilds its (bq, bkv) probability tile from the stashed lse —
#   p  = exp(q·kᵀ·scale − lse)
#   dv = Σ_i pᵀ·dO            dp = dO·vᵀ
#   ds = p ⊙ (dp − D)·scale   with D = rowsum(dO ⊙ O)  (precomputed)
#   dq = Σ_j ds·k             dk = Σ_i dsᵀ·q
# so no (T, S) tensor ever exists: dq accumulates across KV blocks
# (innermost grid dim), dk/dv accumulate across query blocks.
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale: float, causal: bool, bq: int,
                   bkv: int, kv_steps: int, kv_len: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (j * bkv <= i * bq + bq - 1) if causal else True

    @pl.when(run)
    def _block():
        q = q_ref[0]                                   # (bq, d)
        k = k_ref[0]                                   # (bkv, d)
        g = g_ref[0]                                   # (bq, d) = dO tile
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bkv)
        ki = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        valid = ki < kv_len
        if causal:
            qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            valid &= qi >= ki
        p = jnp.where(valid, jnp.exp(s - lse_ref[0, 0][:, None]), 0.0)
        dp = jax.lax.dot_general(
            g, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bkv)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        acc_ref[...] += jax.lax.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(j == kv_steps - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref,
                    dv_ref, dk_acc, dv_acc, *, scale: float, causal: bool,
                    bq: int, bkv: int, q_steps: int, kv_len: int):
    j, i = pl.program_id(1), pl.program_id(2)   # j: kv tile, i: q tile

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (i * bq + bq - 1 >= j * bkv) if causal else True

    @pl.when(run)
    def _block():
        q = q_ref[0]                                   # (bq, d)
        k = k_ref[0]                                   # (bkv, d)
        g = g_ref[0]                                   # (bq, d) = dO tile
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bkv)
        ki = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        valid = ki < kv_len
        if causal:
            qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            valid &= qi >= ki
        p = jnp.where(valid, jnp.exp(s - lse_ref[0, 0][:, None]), 0.0)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bkv, d)
        dp = jax.lax.dot_general(
            g, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bkv)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bkv, d)

    @pl.when(i == q_steps - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bkv",
                                             "interpret", "kv_len"))
def flash_attention_bwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        o: jnp.ndarray, lse: jnp.ndarray, g: jnp.ndarray, *,
                        causal: bool = True, bq: int = 256, bkv: int = 256,
                        interpret: bool = True, kv_len: int = 0):
    """Blockwise dq/dk/dv. q, k, v as in ``flash_attention``; o/lse are the
    stashed forward output + per-row log-sum-exp (BH, T); g is the output
    cotangent (BH, T, d). Returns (dq, dk, dv) in the input dtypes. Both
    row statistics (lse and D) reach the kernels as (BH, 1, T) lane rows
    in (1, 1, bq) blocks, the layout the TPU's block rule admits.

    Zero-padded query rows (callers pad T up to a bq multiple) carry zero
    cotangents, so they contribute nothing to dk/dv; keys at ``ki >=
    kv_len`` are masked out of every probability tile, so their dk/dv rows
    come out exactly zero.
    """
    bh, t, d = q.shape
    s_len = k.shape[1]
    assert t % bq == 0 and s_len % bkv == 0, (t, s_len, bq, bkv)
    kv_len = kv_len or s_len
    # per-row correction D = rowsum(dO ⊙ O): O(T·d), stays out of kernels
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None]
    lse = lse.astype(jnp.float32)[:, None]
    common = dict(scale=d ** -0.5, causal=causal, bq=bq, bkv=bkv,
                  kv_len=kv_len)
    qspec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0))
    rowspec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, kv_steps=s_len // bkv, **common),
        grid=(bh, t // bq, s_len // bkv),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    # dk/dv: kv tiles on the parallel dim, q tiles innermost (sequential)
    qspec2 = pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0))
    kspec2 = pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0))
    rowspec2 = pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, i))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, q_steps=t // bq, **common),
        grid=(bh, s_len // bkv, t // bq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[jax.ShapeDtypeStruct((bh, s_len, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s_len, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bkv, d), jnp.float32),
                        pltpu.VMEM((bkv, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# decode-shaped variant: one query token per (batch·head) row against a
# fixed-width KV cache, masked by a per-row position (the serving engine's
# continuous-batching slots each sit at their own cache position).
# ---------------------------------------------------------------------------


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, bkv: int, kv_steps: int):
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]                                   # this slot's position
    run = j * bkv <= pos                               # skip future kv tiles

    @pl.when(run)
    def _block():
        q = q_ref[0, 0]                                # (1, d)
        k = k_ref[0, 0]                                # (bkv, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (1, bkv)
        ki = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
        valid = ki <= pos                              # cache cells written
        s = jnp.where(valid, s, NEG)
        m_prev = m_ref[...]                            # (1, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0, 0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == kv_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bkv", "interpret"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     pos: jnp.ndarray, *, bkv: int = 256,
                     interpret: bool = True) -> jnp.ndarray:
    """q: (B, H, d); k, v: (B, KV, S, d) head-major caches; pos: (B,)
    int32 -> (B, H, d).

    Row b attends key/value cells [0, pos[b]] of its cache (pos is the cell
    the current token was just written to). ``pos`` is a scalar-prefetch
    operand, so the index map can clamp the KV tile index at the slot's
    position: tiles strictly beyond it repeat the last live tile (no DMA)
    and skip their compute. The GQA broadcast happens in the index map
    (query head h reads kv head h // G), so no head-repeated cache copy
    exists; head-major caches keep every block's last two dims whole —
    (1, d) queries against (bkv, d) cache tiles.
    """
    b, h, d = q.shape
    kv, s_len = k.shape[1], k.shape[2]
    g = h // kv
    assert s_len % bkv == 0, (s_len, bkv)
    grid = (b, h, s_len // bkv)
    kernel = functools.partial(_decode_kernel, scale=d ** -0.5, bkv=bkv,
                               kv_steps=grid[2])

    def q_map(bi, hi, j, pos_):
        return (bi, hi, 0, 0)

    def kv_map(bi, hi, j, pos_):
        return (bi, hi // g, jnp.clip(pos_[bi] // bkv, 0, j), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), q_map),
            pl.BlockSpec((1, 1, bkv, d), kv_map),
            pl.BlockSpec((1, 1, bkv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos.astype(jnp.int32), q[:, :, None], k, v)
    return out[:, :, 0]
