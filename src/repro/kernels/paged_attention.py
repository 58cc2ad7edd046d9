"""Paged decode/chunked-prefill attention as a Pallas TPU kernel.

vLLM-style paged attention for the serving engine's block/paged KV cache:
k/v live in one flat pool of ``(kv_heads, page, d)`` blocks and each slot
owns a **block table** mapping its logical pages to physical blocks. The
kernel never materializes the gathered cache — the table is a
scalar-prefetch operand and the *index map* does the gather, DMA-ing each
physical block straight into VMEM (``pltpu.PrefetchScalarGridSpec``; see
the guide's scalar-prefetch section). The GQA broadcast also happens in
the index map (query head h reads kv head h // G), like the dense decode
kernel.

Queries are a (C,)-token chunk per slot — C = 1 is plain decode; C > 1 is
the engine's in-loop chunked prefill, where prefill chunks and decode
tokens co-batch in one fixed-shape graph. Query c of slot b sits at
absolute position ``pos[b] + c`` and attends cache cells ``[0, pos[b]+c]``
(per-slot, per-query masking); pages strictly beyond a slot's window are
skipped entirely (their index map repeats the last live block, so no DMA
is issued either), and sentinel table entries (>= num_blocks: unallocated
logical pages) are clamped by the index map and hidden by the same mask.

int8 KV mode (DESIGN.md §8): when per-cell scale pools ``(N, KV, 1, page)``
ride along, the k/v page tiles arrive int8 and the scales apply
in-register — the k scale to the score columns, the v scale to the
probability columns, since both index the page's token axis — so the fp
cache never exists in HBM, halving KV read traffic per decoded token.
Scales follow the SAME block gather as the cells (one (1, page) row per
page).

Layout: the pools are head-major, (N, KV, page, d), and the kernel sees
queries as (B, H, C, d), so every block's last two dims are whole array
dims — (C, d) query tiles against (page, d) cache tiles, as the TPU's
(8, 128) block rule needs. Online-softmax scratch (m, l, acc) is carried
across the sequential page grid axis, exactly like flash_attention.py.
Validated in interpret mode against kernels/ref.py::
paged_decode_attention_ref (its quantized leg dequantizes explicitly).

Under tensor-parallel serving (DESIGN.md §9) the kernel is invoked once
PER SHARD inside the engine's shard_map region, with the shard's local
head group and local kv-head-striped pools — H and KV below are then
H/tp and KV/tp; the grid/indexing logic is unchanged because every
(slot, head) program is independent and the block table (replicated) and
positions are shard-agnostic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, *rest, scale: float,
            page: int, chunk: int, kv_steps: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]                       # slot base position (first query)
    # pages beyond the last query's position hold nothing attendable —
    # skip them (their table entries may be sentinels)
    run = j * page <= pos + chunk - 1

    @pl.when(run)
    def _block():
        q = q_ref[0, 0]                                    # (C, d)
        k = k_ref[0, 0]                                    # (page, d)
        if quantized:
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (C, page)
        if quantized:
            # per-cell k scale: one factor per score column (token)
            s = s * ks_ref[0, 0]
        ki = j * page + jax.lax.broadcasted_iota(jnp.int32, (chunk, page), 1)
        qi = pos + jax.lax.broadcasted_iota(jnp.int32, (chunk, page), 0)
        valid = ki <= qi                  # query c attends cells <= pos + c
        s = jnp.where(valid, s, NEG)
        m_prev = m_ref[...]                                # (C, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0]
        if quantized:
            # per-cell v scale folds into the probability columns
            p = p * vs_ref[0, 0]
            v = v.astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == kv_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, tables: jnp.ndarray,
                           pos: jnp.ndarray, k_scale=None, v_scale=None, *,
                           interpret: bool = True) -> jnp.ndarray:
    """q: (B, H, C, d); k_cache, v_cache: (N, KV, page, d) flat block
    pools; tables: (B, P) int32 block table (sentinel >= N for
    unallocated pages); pos: (B,) base positions -> (B, H, C, d).
    k_scale/v_scale: optional (N, KV, 1, page) per-cell scale pools (one
    (1, page) row per block, so the last two block dims are whole) — when
    given the cache pools are int8 and dequantize in-register.

    Grid (B, H, P): the page axis is sequential (online softmax); the
    block table is scalar-prefetched so each page's physical block is
    chosen in the index map — the gathered cache never exists in HBM.
    """
    b, h, c, d = q.shape
    n, kv, page, _ = k_cache.shape
    g = h // kv
    p_tab = tables.shape[1]
    quantized = k_scale is not None
    grid = (b, h, p_tab)
    kernel = functools.partial(_kernel, scale=d ** -0.5, page=page,
                               chunk=c, kv_steps=p_tab, quantized=quantized)

    def kv_map(bi, hi, j, tbl, pos_):
        # past the slot's last live page, repeat that page: an unchanged
        # block index issues no DMA for the skipped grid steps
        j = jnp.clip((pos_[bi] + c - 1) // page, 0, j)
        return (jnp.minimum(tbl[bi, j], n - 1), hi // g, 0, 0)

    def q_map(bi, hi, j, tbl, pos_):
        return (bi, hi, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, c, d), q_map),
        pl.BlockSpec((1, 1, page, d), kv_map),
        pl.BlockSpec((1, 1, page, d), kv_map),
    ]
    operands = [q, k_cache, v_cache]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, 1, page), kv_map),
                     pl.BlockSpec((1, 1, 1, page), kv_map)]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, c, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((c, 1), jnp.float32),
            pltpu.VMEM((c, 1), jnp.float32),
            pltpu.VMEM((c, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, c, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), *operands)
