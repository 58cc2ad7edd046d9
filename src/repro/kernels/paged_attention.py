"""Paged decode/chunked-prefill attention as a Pallas TPU kernel.

vLLM-style paged attention for the serving engine's block/paged KV cache:
k/v live in one flat pool of ``(kv_heads, page, d)`` blocks and each slot
owns a **block table** mapping its logical pages to physical blocks. The
kernel never materializes the gathered cache: the table is a
scalar-prefetch operand, the pools stay in HBM (``memory_space=pl.ANY``),
and the kernel copies the pages it needs into VMEM itself, one async copy
per page.

Queries are a (C,)-token chunk per slot — C = 1 is plain decode; C > 1 is
the engine's in-loop chunked prefill (or the speculative verifier's
``spec_k + 1`` columns), where prefill chunks and decode tokens co-batch
in one fixed-shape graph. Query c of slot b sits at absolute position
``pos[b] + c`` and attends cache cells ``[0, pos[b]+c]`` (per-slot,
per-query masking); sentinel table entries (>= num_blocks: unallocated
logical pages) are clamped to a real block and hidden by the same mask.

Work decomposition. The grid is ``(B, KV // heads, ceil(P / pps))``: one
step attends ``heads`` kv heads of one slot against ``pps``
(pages_per_step) consecutive logical pages, every head group through one
batched ``dot_general`` over the kv-head axis. Both sizes follow from
shapes (:func:`step_shape`, called by the ``kernels/ops.py`` wrapper):

- ``pps`` is the fewest pages that give one step at least 128 keys (the
  lane width), capped at P; P need not be a multiple of it;
- ``heads`` is every kv head while both halves of the K and V page
  buffers (and the int8 scale blocks) fit ``_KV_VMEM_BUDGET``, else the
  largest divisor of KV that fits.

Lane-dense pages. A copy must move whole 128-lane rows (Mosaic refuses a
copy out of a slice of a lane-padded operand), so at d < 128 the wrapper
reads the pools as ``(N, KV, page/r, r*d)`` with r = 128/d (r = 2 at
d = 64): row t of a page holds keys t*r .. t*r + r - 1 side by side
(r = 1 where d is a multiple of 128; rows narrower than 128 lanes, where
d neither divides nor is a multiple of 128 or a page is too short, run
only in interpret mode: Mosaic refuses their copies). The queries come
block-diagonal to match, ``(B, KV, r*GCp, r*d)``: row
u*GCp + i holds query row i (of G*C, padded to GCp, a multiple of 8) in
lanes [u*d, (u+1)*d), so score (u*GCp + i, col) is query i against key
col*r + u. The online softmax runs over all u of a query; at the end the
output of query i is the sum over u of accumulator row u*GCp + i, lanes
[u*d, (u+1)*d).

Copies. Each live page is one async copy of ``k[blk, h0:h0+heads]`` (one
contiguous piece of the pool when ``heads == KV``) into slot i of a
``(2, heads, pps*page/r, r*d)`` VMEM buffer, and V likewise, all
signalling the half's semaphore. The buffer is double-buffered across
grid steps: a live step starts the copies of the NEXT live step (this
slot's next block, else the next head group's or slot's first block) into
the other half before it waits for its own, so the next slot's first
pages are in flight while this slot's last block computes. A step past a
slot's last live page issues no copy and no compute, and a partly live
block copies only its live pages; the position mask hides the stale rows
(the buffers are zeroed once per call, so they only ever hold zeros or
pool data). Every grid axis is walked in order ("arbitrary"), which the
look-ahead needs. Online-softmax scratch (m, l, acc) is carried across
the page-block axis, exactly like flash_attention.py.

int8 KV mode (DESIGN.md §8): when per-cell scale pools ``(N, KV, 1, page)``
ride along, the k/v pages arrive int8 and the scales apply in-register —
the k scale to the score columns, the v scale to the probability
columns, since both index the page's token axis — so the fp cache never
exists in HBM, halving KV read traffic per decoded token. The scales of
each step's pages are gathered by the same table into the score
columns' order, ``(B, steps, KV, r, pps*page/r)`` (1/16 of the int8
cells' bytes at d = 64), and read as one block a step.

Validated in interpret mode against kernels/ref.py::
paged_decode_attention_ref (its quantized leg dequantizes explicitly).

Under tensor-parallel serving (DESIGN.md §9) the kernel is invoked once
PER SHARD inside the engine's shard_map region, with the shard's local
head group and local kv-head-striped pools — H and KV above are then
H/tp and KV/tp; nothing else changes because the block table
(replicated) and positions are shard-agnostic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
LANES = 128
#: VMEM that one call's double-buffered K and V page buffers (and int8
#: scale blocks) may take, counted at their padded (sublane, lane) tiles
_KV_VMEM_BUDGET = 4 * 2 ** 20


def _padded_bytes(rows: int, cols: int, dtype) -> int:
    """VMEM bytes of a (rows, cols) tile of ``dtype`` once padded to the
    TPU's tiling: sublanes of 32 bits in groups of 8, 128 lanes."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item
    return -(-rows // sub) * sub * -(-cols // LANES) * LANES * item


def packing(page: int, d: int) -> int:
    """Keys per lane-dense row: the pools are read as (N, KV, page/r, r*d)
    so that each DMA moves whole 128-lane rows (r = 128/d for d < 128)."""
    return math.gcd(LANES // d, page) if LANES % d == 0 else 1


def step_shape(p_tab: int, kv: int, page: int, d: int, dtype,
               quantized: bool) -> tuple[int, int]:
    """(pages_per_step, heads): the pages and kv heads one grid step
    attends. Pages: the fewest that give at least 128 keys, capped at the
    table width. Heads: all kv heads while both halves of the K and V page
    buffers (and the int8 scale blocks) fit ``_KV_VMEM_BUDGET``, else the
    largest divisor of KV that fits (at least one)."""
    pps = min(p_tab, -(-LANES // page))
    r = packing(page, d)
    rows = pps * page // r
    per_head = 2 * 2 * _padded_bytes(rows, r * d, dtype)      # K, V; halves
    if quantized:
        per_head += 2 * 2 * _padded_bytes(r, rows, jnp.float32)
    fits = [h for h in range(1, kv + 1)
            if kv % h == 0 and h * per_head <= _KV_VMEM_BUDGET]
    return pps, max(fits, default=1)


def _kernel(tbl_ref, pos_ref, q_ref, k_hbm, v_hbm, *rest, scale: float,
            page: int, chunk: int, pps: int, heads: int, pack: int,
            d: int, p_tab: int, n_blocks: int, quantized: bool):
    if quantized:
        (ks_ref, vs_ref, o_ref, kbuf, vbuf, sem, half_ref, m_ref, l_ref,
         acc_ref) = rest
    else:
        o_ref, kbuf, vbuf, sem, half_ref, m_ref, l_ref, acc_ref = rest
    b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_slots, n_groups = pl.num_programs(0), pl.num_programs(1)
    rows = page // pack                        # lane-dense rows per page

    def copies(bi, hi, ji, half):
        """(live, copies) per page of block ji: the K and V copies of one
        page, both signalling the half's semaphore."""
        npg = _live_pages(pos_ref[bi], chunk, page, p_tab)
        out = []
        for i in range(pps):
            pg = ji * pps + i

            def make(i=i, pg=pg):
                blk = jnp.clip(tbl_ref[bi, jnp.minimum(pg, p_tab - 1)], 0,
                               n_blocks - 1)
                return [pltpu.make_async_copy(
                    src.at[blk, pl.ds(hi * heads, heads)],
                    dst.at[half, :, pl.ds(i * rows, rows)], sem.at[half])
                    for src, dst in ((k_hbm, kbuf), (v_hbm, vbuf))]
            out.append((pg < npg, make))
        return out

    def start(bi, hi, ji, half):
        for live, make in copies(bi, hi, ji, half):
            @pl.when(live)
            def _():
                for cp in make():
                    cp.start()

    def wait(bi, hi, ji, half):
        for live, make in copies(bi, hi, ji, half):
            @pl.when(live)
            def _():
                for cp in make():
                    cp.wait()

    last = _live_blocks(pos_ref[b], chunk, page, p_tab, pps) - 1

    @pl.when((b == 0) & (h == 0) & (j == 0))
    def _first():
        # stale rows of a partly live block are masked, but 0 * NaN is not
        # 0: let the buffers only ever hold zeros or pool data
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        half_ref[0] = 0
        start(b, h, j, 0)

    @pl.when(j <= last)
    def _step():
        half = half_ref[0]
        # start the next live step's copies before waiting for this one's:
        # this slot's next block, else the next head group's first block,
        # else the next slot's first block
        more_j, more_h = j < last, h + 1 < n_groups
        nb = jnp.where(more_j | more_h, b, b + 1)
        nh = jnp.where(more_j, h, jnp.where(more_h, h + 1, 0))
        nj = jnp.where(more_j, j + 1, 0)

        @pl.when(nb < n_slots)
        def _prefetch():
            start(nb, nh, nj, 1 - half)
            half_ref[0] = 1 - half

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        wait(b, h, j, half)
        q = q_ref[0]                         # (heads, pack * GCp, pack * d)
        k = kbuf[half]                       # (heads, pps * rows, pack * d)
        v = vbuf[half]
        if quantized:
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        # row u*GCp + i of q holds query row i in lanes [u*d, (u+1)*d), so
        # score (u*GCp + i, col) is query i against key col*pack + u
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        gcp, cols = s.shape[1] // pack, s.shape[2]
        s = s.reshape(heads, pack, gcp, cols)
        if quantized:
            # per-cell k scale: one factor per score column (key)
            s = s * ks_ref[0, 0][:, :, None, :]
        key = (j * pps * rows
               + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)) * pack \
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = pos_ref[b] + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2) % chunk
        # query c attends cells <= pos + c, and nothing past the table
        valid = (key <= qpos) & (key < p_tab * page)
        s = jnp.where(valid, s, NEG)
        m_prev = m_ref[...]                              # (heads, GCp, 1)
        m_new = jnp.maximum(m_prev, jnp.max(
            jnp.max(s, axis=3, keepdims=True), axis=1))
        p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(
            jnp.sum(p, axis=3, keepdims=True), axis=1)
        if quantized:
            # per-cell v scale folds into the probability columns
            p = p * vs_ref[0, 0][:, :, None, :]
        pv = jax.lax.dot_general(
            p.reshape(heads, pack * gcp, cols).astype(v.dtype), v,
            (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)
        corr = jnp.broadcast_to(corr[:, None], (heads, pack, gcp, 1))
        acc_ref[...] = acc_ref[...] * corr.reshape(heads, pack * gcp, 1) + pv
        m_ref[...] = m_new

        @pl.when(j == last)
        def _finish():
            # query i's output: the sum over u of rows u*GCp + i, lanes
            # [u*d, (u+1)*d) (keys col*pack + u against their own values)
            acc = acc_ref[...].reshape(heads, pack, gcp, pack * d)
            out = acc[:, 0, :, :d]
            for u in range(1, pack):
                out = out + acc[:, u, :, u * d:(u + 1) * d]
            o_ref[0] = (out / jnp.maximum(l_ref[...], 1e-30)).astype(
                o_ref.dtype)


def _live_pages(pos, chunk: int, page: int, p_tab: int):
    """Pages up to the slot's last query position; at least one, so every
    slot has a live first block (an all-masked one outputs zeros)."""
    return jnp.clip((pos + chunk - 1) // page + 1, 1, p_tab)


def _live_blocks(pos, chunk: int, page: int, p_tab: int, pps: int):
    return (_live_pages(pos, chunk, page, p_tab) + pps - 1) // pps


@functools.partial(jax.jit, static_argnames=("pages_per_step", "heads",
                                             "interpret"))
def paged_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, tables: jnp.ndarray,
                           pos: jnp.ndarray, k_scale=None, v_scale=None, *,
                           pages_per_step: int, heads: int,
                           interpret: bool = True) -> jnp.ndarray:
    """q: (B, KV, G, C, d) — query c of the g-th head on kv head k;
    k_cache, v_cache: (N, KV, page, d) flat block pools; tables: (B, P)
    int32 block table (sentinel >= N for unallocated pages); pos: (B,)
    base positions -> (B, KV, G, C, d). k_scale/v_scale: optional
    (N, KV, 1, page) per-cell scale pools — when given the cache pools are
    int8 and dequantize in-register. ``pages_per_step`` and ``heads``
    come from :func:`step_shape`.

    Grid (B, KV // heads, ceil(P / pages_per_step)): the page-block axis
    is sequential (online softmax), and every axis is walked in order so
    that each live step can start the next live step's page copies.
    """
    b, kv, g, c, d = q.shape
    n, _, page, _ = k_cache.shape
    p_tab = tables.shape[1]
    quantized = k_scale is not None
    pps = pages_per_step
    n_steps = -(-p_tab // pps)
    r = packing(page, d)
    rows = page // r
    gc = g * c
    gcp = -(-gc // 8) * 8               # whole f32 sublane groups per pack
    # queries, block-diagonal over the packing: row u*GCp + i carries
    # query row i in lanes [u*d, (u+1)*d), zeros elsewhere
    qf = jnp.pad(q.reshape(b, kv, gc, d), ((0, 0), (0, 0), (0, gcp - gc),
                                           (0, 0)))
    q2 = jnp.concatenate(
        [jnp.pad(qf, ((0, 0), (0, 0), (0, 0), (u * d, (r - 1 - u) * d)))
         for u in range(r)], axis=2)
    kernel = functools.partial(
        _kernel, scale=d ** -0.5, page=page, chunk=c, pps=pps, heads=heads,
        pack=r, d=d, p_tab=p_tab, n_blocks=n, quantized=quantized)

    def q_map(bi, hi, j, tbl, pos_):
        return (bi, hi, 0, 0)

    in_specs = [pl.BlockSpec((1, heads, r * gcp, r * d), q_map),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    # the pools as whole 128-lane rows: (N, KV, page/r, r*d)
    operands = [q2, k_cache.reshape(n, kv, rows, r * d),
                v_cache.reshape(n, kv, rows, r * d)]
    if quantized:
        # scales of every table page, laid out like the score columns:
        # (B, step, KV, u, col) holds key col*r + u of the step's pages
        tbl = jnp.pad(tables, ((0, 0), (0, n_steps * pps - p_tab)),
                      constant_values=n)
        idx = jnp.clip(tbl, 0, n - 1)

        def by_column(sc):
            sg = sc[idx].reshape(b, n_steps, pps, kv, rows, r)
            return sg.transpose(0, 1, 3, 5, 2, 4).reshape(
                b, n_steps, kv, r, pps * rows)

        def s_map(bi, hi, j, tbl_, pos_):
            # past the slot's last live block, keep the last block's
            # index: an unchanged block index issues no copy
            last = _live_blocks(pos_[bi], c, page, p_tab, pps) - 1
            return (bi, jnp.minimum(j, last), hi, 0, 0)

        in_specs += [pl.BlockSpec((1, 1, heads, r, pps * rows), s_map)] * 2
        operands += [by_column(k_scale), by_column(v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kv // heads, n_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, heads, gcp, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, heads, pps * rows, r * d), k_cache.dtype),
            pltpu.VMEM((2, heads, pps * rows, r * d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((heads, gcp, 1), jnp.float32),
            pltpu.VMEM((heads, gcp, 1), jnp.float32),
            pltpu.VMEM((heads, r * gcp, r * d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, gcp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), *operands)
    return out[:, :, :gc].reshape(b, kv, g, c, d)
