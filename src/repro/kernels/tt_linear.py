"""Fused TT-adapted linear layer: Y = X·W + α·(X·A)·B  (one Pallas kernel).

This is the paper's serving/training hot spot (Eq. (5)) with the middle TT
cores pre-merged (A = G1·G2[l]·G3[m] ∈ R^{K×r}, B = G4 ∈ R^{r×N},
DESIGN.md §3). The unfused XLA path writes Y_base to HBM, reads it back,
adds the rank-r delta — 3 extra HBM round-trips of the (M, N) output.
Here the rank-r epilogue is applied while the output tile is still in VMEM:

  grid (M/bm, N/bn, K/bk), K innermost ("arbitrary" = sequential):
    acc   (bm, bn) f32 VMEM scratch — base matmul accumulator
    acc_p (bm, r)  f32 VMEM scratch — P = X·A accumulator (r ≤ 256)
    k-step:  acc += X_tile @ W_tile ;  acc_p += X_tile @ A_tile
    last k:  OUT = acc + α · acc_p @ B_tile     (epilogue, in VMEM)

Tile choices: bm/bn/bk multiples of the MXU native (128×128; 8-sublane f32
scratch). VMEM footprint = bm·bk + bk·bn + bm·bn·4 + (bm+bn)·r·4 + bk·r
≈ 1.3 MB at (256, 256, 512, r=64) — comfortably inside the ~16 MB/core VMEM
budget, leaving room for double buffering.

Validated in interpret mode on CPU against kernels/ref.py::tt_linear_ref
(tests/test_kernels.py sweeps shapes/dtypes/ranks).

w8a16 variants (``tt_linear_w8`` / ``tt_linear_batched_a_w8``, DESIGN.md
§8): the frozen base W arrives int8 with f32 per-output-channel (or
K-group-wise) scales from kernels/quant.py — half the weight HBM traffic
on the bandwidth-bound decode path — while the rank-r TT epilogue stays
full precision (the trained adapter never quantizes). Oracles:
kernels/ref.py::tt_linear_q_ref / tt_linear_batched_a_q_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _base_dot(x, w_ref, s_ref, per_channel):
    """One K-step of the base matmul. fp (s_ref None): dot in the operand
    dtype. w8a16 per-channel (scale constant over K): dot the raw int8
    values cast to the activation dtype (|q| <= 127 is exact in bf16) —
    the scale is applied once to the f32 accumulator in the epilogue.
    w8a16 group-wise (scale row indexed by the K tile; ops.py pins
    bk == group_size): dequantize the tile in-register to f32 first."""
    if s_ref is None:
        return jax.lax.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    if per_channel:
        return jax.lax.dot(x, w_ref[...].astype(x.dtype),
                           preferred_element_type=jnp.float32)
    wf = w_ref[...].astype(jnp.float32) * s_ref[...]
    return jax.lax.dot(x.astype(jnp.float32), wf,
                       preferred_element_type=jnp.float32)


def _epilogue_out(acc_ref, accp_ref, b_ref, s_ref, out_ref, alpha,
                  per_channel):
    """Shared epilogue: the f32 P = X·A accumulator feeds the delta GEMM
    in f32 — casting it down to b's storage dtype first (bf16) would
    throw away the accumulated precision right before the last matmul.
    The w8a16 per-channel scale multiplies the f32 base accumulator here,
    so the int8 MXU passes never see it; the rank-r TT epilogue is full
    fp either way — the adapter delta never loses precision to the
    quantization."""
    delta = jax.lax.dot(accp_ref[...], b_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    acc = acc_ref[...]
    if s_ref is not None and per_channel:
        acc = acc * s_ref[...]
    out_ref[...] = (acc + alpha * delta).astype(out_ref.dtype)


def _kernel(x_ref, w_ref, *rest, alpha: float, k_steps: int,
            per_channel: bool | None = None):
    """Fused adapted linear. ``per_channel=None`` is the fp form (no
    scale operand); True/False is the w8a16 form with a (1, bn) scale
    block riding after W (per-output-channel / group-wise)."""
    if per_channel is None:
        s_ref, (a_ref, b_ref, out_ref, acc_ref, accp_ref) = None, rest
    else:
        s_ref, a_ref, b_ref, out_ref, acc_ref, accp_ref = rest
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        accp_ref[...] = jnp.zeros_like(accp_ref)

    x = x_ref[...]
    acc_ref[...] += _base_dot(x, w_ref, s_ref, per_channel)
    accp_ref[...] += jax.lax.dot(
        x, a_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _epilogue():
        _epilogue_out(acc_ref, accp_ref, b_ref, s_ref, out_ref, alpha,
                      per_channel)


def _batched_a_kernel(x_ref, w_ref, *rest, alpha: float, k_steps: int,
                      per_channel: bool | None = None):
    """Per-slot-A variant (the slot-gathered 4+1d task routing); same
    fp / w8a16 operand convention as ``_kernel``."""
    if per_channel is None:
        s_ref, (a_ref, b_ref, out_ref, acc_ref, accp_ref) = None, rest
    else:
        s_ref, a_ref, b_ref, out_ref, acc_ref, accp_ref = rest
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        accp_ref[...] = jnp.zeros_like(accp_ref)

    x = x_ref[...]
    acc_ref[...] += _base_dot(x, w_ref, s_ref, per_channel)
    # per-row A: row m of the tile contracts against its own (bk, r)
    # slice — unrolled 2-D dots over the bm (= 8) slot rows, since Mosaic
    # does not lower a batched dot_general
    for m in range(x.shape[0]):
        accp_ref[m:m + 1, :] += jax.lax.dot(
            x[m:m + 1], a_ref[m], preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _epilogue():
        _epilogue_out(acc_ref, accp_ref, b_ref, s_ref, out_ref, alpha,
                      per_channel)


@functools.partial(jax.jit, static_argnames=("alpha", "bm", "bn", "bk",
                                             "interpret"))
def tt_linear_batched_a(x: jnp.ndarray, w: jnp.ndarray, a: jnp.ndarray,
                        b: jnp.ndarray, *, alpha: float = 1.0, bm: int = 8,
                        bn: int = 256, bk: int = 512,
                        interpret: bool = True) -> jnp.ndarray:
    """x: (M, K); w: (K, N); a: (M, K, r); b: (r, N) -> (M, N).

    Same fusion as ``tt_linear`` but the A operand carries a leading slot
    axis — one low-rank factor per output row. This is the serving engine's
    decode shape: M is the continuous-batching slot axis and A[m] was
    gathered from the (4+1)d task axis by the slot's task id, so per-request
    task routing stays inside the one fused kernel. bm defaults to the f32
    sublane (8): decode Ms are slot counts, not token counts.
    """
    m, k_dim = x.shape
    _, n = w.shape
    r = a.shape[2]
    assert a.shape[:2] == (m, k_dim), (a.shape, x.shape)
    assert m % bm == 0 and n % bn == 0 and k_dim % bk == 0, \
        (m, n, k_dim, bm, bn, bk)
    grid = (m // bm, n // bn, k_dim // bk)

    kernel = functools.partial(_batched_a_kernel, alpha=alpha,
                               k_steps=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, bk, r), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, a, b)


@functools.partial(jax.jit, static_argnames=("alpha", "bm", "bn", "bk",
                                             "interpret"))
def tt_linear_w8(x: jnp.ndarray, wq: jnp.ndarray, scale: jnp.ndarray,
                 a: jnp.ndarray, b: jnp.ndarray, *, alpha: float = 1.0,
                 bm: int = 256, bn: int = 256, bk: int = 512,
                 interpret: bool = True) -> jnp.ndarray:
    """w8a16 fused adapted linear. x: (M, K); wq: (K, N) int8; scale:
    (G, N) f32 (G == 1: per-output-channel, applied at the epilogue;
    G > 1: group-wise with bk == K // G, dequantized in-register); a, b:
    fp adapter factors as in ``tt_linear``.
    """
    m, k_dim = x.shape
    _, n = wq.shape
    r = a.shape[1]
    g = scale.shape[0]
    per_channel = g == 1
    assert m % bm == 0 and n % bn == 0 and k_dim % bk == 0, \
        (m, n, k_dim, bm, bn, bk)
    assert per_channel or k_dim // g == bk, (k_dim, g, bk)
    grid = (m // bm, n // bn, k_dim // bk)

    def s_map(i, j, k):
        return (0 if per_channel else k, j)

    kernel = functools.partial(_kernel, alpha=alpha, k_steps=grid[2],
                               per_channel=per_channel)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), s_map),
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, wq, scale, a, b)


@functools.partial(jax.jit, static_argnames=("alpha", "bm", "bn", "bk",
                                             "interpret"))
def tt_linear_batched_a_w8(x: jnp.ndarray, wq: jnp.ndarray,
                           scale: jnp.ndarray, a: jnp.ndarray,
                           b: jnp.ndarray, *, alpha: float = 1.0,
                           bm: int = 8, bn: int = 256, bk: int = 512,
                           interpret: bool = True) -> jnp.ndarray:
    """w8a16 twin of ``tt_linear_batched_a`` (decode-slot per-row A).
    wq: (K, N) int8; scale: (G, N) f32 as in ``tt_linear_w8``."""
    m, k_dim = x.shape
    _, n = wq.shape
    r = a.shape[2]
    g = scale.shape[0]
    per_channel = g == 1
    assert a.shape[:2] == (m, k_dim), (a.shape, x.shape)
    assert m % bm == 0 and n % bn == 0 and k_dim % bk == 0, \
        (m, n, k_dim, bm, bn, bk)
    assert per_channel or k_dim // g == bk, (k_dim, g, bk)
    grid = (m // bm, n // bn, k_dim // bk)

    def s_map(i, j, k):
        return (0 if per_channel else k, j)

    kernel = functools.partial(_batched_a_kernel, alpha=alpha,
                               k_steps=grid[2], per_channel=per_channel)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), s_map),
            pl.BlockSpec((bm, bk, r), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, wq, scale, a, b)


@functools.partial(jax.jit, static_argnames=("alpha", "bm", "bn", "bk",
                                             "interpret"))
def tt_linear(x: jnp.ndarray, w: jnp.ndarray, a: jnp.ndarray,
              b: jnp.ndarray, *, alpha: float = 1.0, bm: int = 256,
              bn: int = 256, bk: int = 512,
              interpret: bool = True) -> jnp.ndarray:
    """x: (M, K); w: (K, N); a: (K, r); b: (r, N) -> (M, N).

    Dims must be multiples of the tile sizes (ops.py pads otherwise); r is
    kept whole per tile (r ≤ 256 in every paper configuration).
    """
    m, k_dim = x.shape
    _, n = w.shape
    r = a.shape[1]
    assert m % bm == 0 and n % bn == 0 and k_dim % bk == 0, \
        (m, n, k_dim, bm, bn, bk)
    grid = (m // bm, n // bn, k_dim // bk)

    kernel = functools.partial(_kernel, alpha=alpha, k_steps=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, a, b)
