"""Grouped matmul of expert-sorted rows as a Pallas TPU kernel.

The dropless expert layer (models/moe.py::dropless_block) sorts its
(token, expert) pairs by expert into a rows buffer in which every expert's
group starts on a ``tm`` boundary, so each ``tm``-row tile belongs to one
expert. The kernel multiplies tile i by its expert's weights:

    out[tile i] = x[tile i] @ w[tile_group[i]]          (transpose_w=False)
    out[tile i] = x[tile i] @ w[tile_group[i]]ᵀ         (transpose_w=True)

``tile_group`` and ``n_used`` (the tiles that hold rows) are scalar-prefetch
operands. Tiles at or past ``n_used`` compute nothing and fetch nothing:
their index maps repeat the last used tile's blocks, so the pipeline starts
no DMA, and they store zeros. The operations and copies therefore follow
the rows routed here, not the buffer's size; each dead tile still costs a
grid step. Grid: (output tiles, row tiles, contraction tiles), the
contraction innermost with an f32 accumulator.

The instruction carries the ``pallas_call``'s name, ``moe_gmm``, in a
device trace (the benchmark's ``experts_roofline`` reads it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(group_ref, used_ref, x_ref, w_ref, o_ref, acc_ref, *,
            k_steps: int, transpose_w: bool):
    i, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < used_ref[0])
    def _accumulate():
        dims = (((1,), (1,)), ((), ())) if transpose_w \
            else (((1,), (0,)), ((), ()))
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[0], dims, preferred_element_type=jnp.float32)

    @pl.when(kk == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tk", "tn",
                                             "transpose_w", "interpret"))
def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, tile_group: jnp.ndarray,
                   n_used: jnp.ndarray, *, tm: int = 128, tk: int = 512,
                   tn: int = 512, transpose_w: bool = False,
                   interpret: bool = True) -> jnp.ndarray:
    """x: (R, K) rows, R a multiple of ``tm``; w: (E, K, N), or (E, N, K)
    with ``transpose_w``; tile_group: (R / tm,) int32, the expert of each
    row tile; n_used: (1,) int32, the tiles that hold rows (a prefix).
    Returns (R, N) in x's dtype, zero in the unused tiles. K must be a
    multiple of ``tk`` and N of ``tn``."""
    r, k_dim = x.shape
    n_dim = w.shape[1] if transpose_w else w.shape[2]
    assert r % tm == 0 and k_dim % tk == 0 and n_dim % tn == 0, \
        (x.shape, w.shape, tm, tk, tn)
    k_steps = k_dim // tk
    grid = (n_dim // tn, r // tm, k_steps)

    def _live(i, kk, used):
        """The row tile and contraction step whose blocks tile i reads:
        its own while it holds rows, else the last used tile's last step
        (already resident, so no copy)."""
        last = jnp.maximum(used[0] - 1, 0)
        on = i < used[0]
        return jnp.where(on, i, last), jnp.where(on, kk, k_steps - 1)

    def x_map(j, i, kk, group, used):
        ii, kc = _live(i, kk, used)
        return ii, kc

    def w_map(j, i, kk, group, used):
        ii, kc = _live(i, kk, used)
        return (group[ii], j, kc) if transpose_w else (group[ii], kc, j)

    w_block = (1, tn, tk) if transpose_w else (1, tk, tn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[pl.BlockSpec((tm, tk), x_map),
                  pl.BlockSpec(w_block, w_map)],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, kk, g, u: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, transpose_w=transpose_w),
        name="moe_gmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n_dim), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(tile_group.astype(jnp.int32), n_used.astype(jnp.int32), x, w)
