"""Kernel-dispatch layer: the single seam between model code and kernels.

Every hot-path call site (``models/layers.py::adapted_linear``, the
attention paths in ``models/attention.py``, the serving engine's decode
loop) routes through this module instead of picking a backend ad hoc
(DESIGN.md §5). The flow is:

  KernelConfig (config/base.py, user-facing knobs on RunConfig / Engine)
      -> resolve() -> KernelPolicy (hashable, fully resolved: backend
         chosen, interpret decided, tile overrides pinned)
      -> AdapterCtx.policy -> layers / attention / engine call the
         dispatch functions below.

With ``use_pallas`` the fused Pallas kernels run (on TPU natively; on CPU
only under ``interpret=True`` — the correctness path the parity tests and
CI exercise). Otherwise the pure-XLA reference math runs from the SAME
entry points, so fused-vs-ref comparisons (tests, benchmarks) exercise
exactly the code the model executes — no benchmark-only kernel calls.

The fused linear is differentiable: a custom VJP whose dx GEMM is itself
the fused kernel with transposed operands (dx = g·Wᵀ + α·(g·Bᵀ)·Aᵀ has the
same base-matmul + rank-r-epilogue shape as the forward), so the *training*
hot path stays on the kernel in both directions. Flash attention is also
differentiable end-to-end on the blockwise path: the forward stashes the
per-row log-sum-exp and the backward runs the two-pass recompute kernels
(``kernels/flash_attention.py::flash_attention_bwd``), so neither direction
ever materializes the (T, S) score matrix — the flash memory win holds for
training as well as inference (DESIGN.md §14). Future backends (GPU Triton,
new TPU generations) plug in here: add a branch to resolve() and the whole
stack follows.

Sharded serving (DESIGN.md §9): these entry points are shard_map-safe —
under the engine's tensor-parallel mesh each shard calls them with its
LOCAL head group and LOCAL KV-pool shard (q (B, C, H/tp, d) against
(N, KV/tp, page, d) pools), which is just a smaller instance of the
single-device shapes documented below; no kernel knows about the mesh.
The ambient-GSPMD guard lives one level up (models/attention.py::
_flash_ok): kernels stand down under an ambient >1-chip mesh, but run
per-shard inside shard_map where no ambient mesh exists.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.config.base import KernelConfig
from repro.kernels import ops
from repro.kernels import quant as quant_lib
from repro.kernels import ref as _ref


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Resolved dispatch decision. Hashable and static: it is closed over
    by jitted functions and passed through ``jax.custom_vjp`` nondiff args,
    so it must never carry tracers."""
    use_pallas: bool = False
    interpret: bool = True
    fuse_linear: bool = True
    flash: bool = True
    bm: int = 0
    bn: int = 0
    bk: int = 0
    bq: int = 0
    bkv: int = 0

    @property
    def fused_linear(self) -> bool:
        """adapted_linear routes through the fused TT-linear kernel."""
        return self.use_pallas and self.fuse_linear

    @property
    def flash_attn(self) -> bool:
        """attention routes through the Pallas flash/decode kernels."""
        return self.use_pallas and self.flash


#: Force-reference policy (dispatch entry points, XLA math) — the "ref" leg
#: of every fused-vs-ref parity comparison.
REF = KernelPolicy(use_pallas=False)

#: Interpret-mode Pallas policy — the CPU correctness path.
PALLAS_INTERPRET = KernelPolicy(use_pallas=True, interpret=True)


def resolve(cfg: Union[KernelConfig, KernelPolicy, None]
            ) -> Optional[KernelPolicy]:
    """KernelConfig -> KernelPolicy (None passes through: "no policy" keeps
    the legacy unfused path, bit-identical to the pre-dispatch stack)."""
    if cfg is None or isinstance(cfg, KernelPolicy):
        return cfg
    cfg = cfg.validate()
    if cfg.backend == "pallas":
        use = True
    elif cfg.backend == "ref":
        use = False
    else:                                   # auto: Pallas iff on TPU
        use = jax.default_backend() == "tpu"
    interp = ((jax.default_backend() != "tpu") if cfg.interpret is None
              else cfg.interpret)
    return KernelPolicy(use_pallas=use, interpret=interp,
                        fuse_linear=cfg.fuse_linear, flash=cfg.flash,
                        bm=cfg.bm, bn=cfg.bn, bk=cfg.bk, bq=cfg.bq,
                        bkv=cfg.bkv)


# ---------------------------------------------------------------------------
# fused adapted linear (differentiable)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_tt_linear(pol: KernelPolicy, alpha: float, x, w, a, b):
    return ops.tt_linear(x, w, a, b, alpha=alpha, backend="pallas",
                         interpret=pol.interpret, bm=pol.bm, bn=pol.bn,
                         bk=pol.bk)


def _fused_tt_linear_fwd(pol, alpha, x, w, a, b):
    return _fused_tt_linear(pol, alpha, x, w, a, b), (x, w, a, b)


def _fused_tt_linear_bwd(pol, alpha, res, g):
    x, w, a, b = res
    # dx = g·Wᵀ + α·(g·Bᵀ)·Aᵀ — the SAME fused base-matmul + rank-r
    # epilogue, so the backward's big GEMM stays on the kernel. The N/K
    # roles swap under the transpose, so the tile overrides swap with them.
    dx = ops.tt_linear(g, w.T, b.T, a.T, alpha=alpha, backend="pallas",
                       interpret=pol.interpret, bm=pol.bm, bn=pol.bk,
                       bk=pol.bn)
    xf = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    gf = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
    # dW = Xᵀ·G is dead code under PEFT (W frozen, cotangent dropped) and
    # XLA eliminates it; computed for custom_vjp completeness.
    dw = xf.T @ gf
    gb = gf @ b.astype(jnp.float32).T
    da = alpha * (xf.T @ gb)
    db = alpha * ((xf @ a.astype(jnp.float32)).T @ gf)
    return (dx.astype(x.dtype), dw.astype(w.dtype), da.astype(a.dtype),
            db.astype(b.dtype))


_fused_tt_linear.defvjp(_fused_tt_linear_fwd, _fused_tt_linear_bwd)


def tt_linear(x, w, a, b, *, alpha: float = 1.0,
              policy: Optional[KernelPolicy] = None):
    """y = x·W + α·(x·A)·B. x: (..., K); w: (K, N); a: (K, r); b: (r, N)."""
    if policy is not None and policy.fused_linear:
        return _fused_tt_linear(policy, float(alpha), x, w, a, b)
    return _ref.tt_linear_ref(x, w, a, b, float(alpha))


def tt_linear_q(x, wq, a, b, *, alpha: float = 1.0,
                policy: Optional[KernelPolicy] = None):
    """w8a16 adapted linear over a packed int8 base leaf (DESIGN.md §8).

    wq: ``{"q8": int8 (K, N), "scale": f32 (G, N)}`` (kernels/quant.py);
    x/a/b as in ``tt_linear``. Inference-only — the int8 base is frozen by
    construction, so no custom VJP is defined; differentiate the ref path
    (plain XLA dequant + matmul) if a gradient is ever needed.
    """
    if policy is not None and policy.fused_linear:
        return ops.tt_linear_q(x, wq["q8"], wq["scale"], a, b,
                               alpha=float(alpha), backend="pallas",
                               interpret=policy.interpret, bm=policy.bm,
                               bn=policy.bn, bk=policy.bk)
    return _ref.tt_linear_q_ref(x, wq["q8"], wq["scale"], a, b,
                                float(alpha))


def tt_linear_batched_a_q(x, wq, a, b, *, alpha: float = 1.0,
                          policy: Optional[KernelPolicy] = None):
    """w8a16 per-row-A adapted linear (slot-task routing over an int8
    base). Decode shapes run the fused w8 kernel; the (B, T>1, K) chunked-
    prefill generalization dequantizes once and runs the batched-einsum
    reference from the same seam (mirrors ``tt_linear_batched_a``)."""
    decode_shaped = x.ndim == 2 or (x.ndim == 3 and x.shape[1] == 1)
    if decode_shaped:
        fused = policy is not None and policy.fused_linear
        kw = dict(interpret=policy.interpret, bm=policy.bm, bn=policy.bn,
                  bk=policy.bk) if fused else {}
        return ops.tt_linear_batched_a_q(
            x, wq["q8"], wq["scale"], a, b, alpha=float(alpha),
            backend="pallas" if fused else "ref", **kw)
    w = quant_lib.dequantize(wq, x.dtype)
    p = jnp.einsum("b...k,bkr->b...r", x, a.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    y = y + float(alpha) * jnp.dot(p, b.astype(p.dtype),
                                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_tt_linear_ba(pol: KernelPolicy, alpha: float, x, w, a, b):
    return ops.tt_linear_batched_a(x, w, a, b, alpha=alpha,
                                   backend="pallas", interpret=pol.interpret,
                                   bm=pol.bm, bn=pol.bn, bk=pol.bk)


def _fused_tt_linear_ba_fwd(pol, alpha, x, w, a, b):
    return _fused_tt_linear_ba(pol, alpha, x, w, a, b), (x, w, a, b)


def _fused_tt_linear_ba_bwd(pol, alpha, res, g):
    x, w, a, b = res
    # decode-shaped (one token per slot row): the backward contractions
    # are per-row rank-r epilogues, so plain XLA einsums in f32 suffice
    squeeze = x.ndim == 3
    xf = (x[:, 0] if squeeze else x).astype(jnp.float32)
    gf = (g[:, 0] if squeeze else g).astype(jnp.float32)
    af = a.astype(jnp.float32)
    gb = gf @ b.astype(jnp.float32).T                       # (S, r)
    dx = (gf @ w.astype(jnp.float32).T
          + alpha * jnp.einsum("sr,skr->sk", gb, af))
    dw = xf.T @ gf
    da = alpha * jnp.einsum("sk,sr->skr", xf, gb)
    p = jnp.einsum("sk,skr->sr", xf, af)
    db = alpha * (p.T @ gf)
    if squeeze:
        dx = dx[:, None]
    return (dx.astype(x.dtype), dw.astype(w.dtype), da.astype(a.dtype),
            db.astype(b.dtype))


_fused_tt_linear_ba.defvjp(_fused_tt_linear_ba_fwd, _fused_tt_linear_ba_bwd)


def tt_linear_batched_a(x, w, a, b, *, alpha: float = 1.0,
                        policy: Optional[KernelPolicy] = None):
    """Per-row-A adapted linear (the (4+1)d slot-task routing form).

    x: (S, [1,] K); w: (K, N); a: (S, K, r); b: (r, N). The Pallas kernel
    handles the decode shape (one token per slot row) through a custom VJP
    (differentiable like the plain fused linear); other shapes (e.g. a
    per-example task vector during training) run the batched-einsum
    reference from the same seam.
    """
    decode_shaped = x.ndim == 2 or (x.ndim == 3 and x.shape[1] == 1)
    if decode_shaped:
        fused = policy is not None and policy.fused_linear
        if fused:
            return _fused_tt_linear_ba(policy, float(alpha), x, w, a, b)
        return ops.tt_linear_batched_a(x, w, a, b, alpha=float(alpha),
                                       backend="ref")
    # (B, T>1, K) generalization (per-example task vectors during
    # training) — no kernel for this shape yet; batched-einsum reference
    p = jnp.einsum("b...k,bkr->b...r", x, a.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    y = y + float(alpha) * jnp.dot(p, b.astype(p.dtype),
                                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (flash forward, blockwise flash backward)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_flash(pol: KernelPolicy, causal: bool, q, k, v):
    return ops.flash_attention(q, k, v, causal=causal, backend="pallas",
                               interpret=pol.interpret, bq=pol.bq,
                               bkv=pol.bkv)


def _fused_flash_fwd(pol, causal, q, k, v):
    # the stats-emitting forward: one extra (B, H, T) f32 residual (lse)
    # buys a backward that never builds (T, S)
    out, lse = ops.flash_attention_fwd(q, k, v, causal=causal,
                                       backend="pallas",
                                       interpret=pol.interpret, bq=pol.bq,
                                       bkv=pol.bkv)
    return out, (q, k, v, out, lse)


def _fused_flash_bwd(pol, causal, res, g):
    q, k, v, out, lse = res
    return ops.flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                                   backend="pallas",
                                   interpret=pol.interpret, bq=pol.bq,
                                   bkv=pol.bkv)


_fused_flash.defvjp(_fused_flash_fwd, _fused_flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    policy: Optional[KernelPolicy] = None):
    """GQA attention. q: (B, T, H, d); k, v: (B, S, KV, d) -> (B, T, H, d)."""
    if policy is not None and policy.flash_attn:
        return _fused_flash(policy, causal, q, k, v)
    return ops.flash_attention(q, k, v, causal=causal, backend="ref")


def decode_attention(q, k, v, pos, *,
                     policy: Optional[KernelPolicy] = None):
    """Cached single-token decode. q: (B, 1, H, d); k, v: (B, KV, S, d)
    head-major caches; pos: scalar or (B,) per-slot positions ->
    (B, 1, H, d)."""
    if policy is not None and policy.flash_attn:
        return ops.decode_attention(q, k, v, pos, backend="pallas",
                                    interpret=policy.interpret,
                                    bkv=policy.bkv)
    return ops.decode_attention(q, k, v, pos, backend="ref")


def paged_decode_attention(q, k_cache, v_cache, tables, pos, *,
                           k_scale=None, v_scale=None,
                           policy: Optional[KernelPolicy] = None):
    """Paged-cache attention (decode and in-loop chunked prefill).
    q: (B, C, H, d); k_cache, v_cache: (N, KV, page, d); tables: (B, P)
    int32 block table; pos: (B,) base positions -> (B, C, H, d).
    k_scale/v_scale: (N, KV, 1, page) per-cell scale pools when the cache is
    int8 (the kernel dequantizes pages in-register)."""
    if policy is not None and policy.flash_attn:
        return ops.paged_decode_attention(q, k_cache, v_cache, tables, pos,
                                          k_scale=k_scale, v_scale=v_scale,
                                          backend="pallas",
                                          interpret=policy.interpret)
    return ops.paged_decode_attention(q, k_cache, v_cache, tables, pos,
                                      k_scale=k_scale, v_scale=v_scale,
                                      backend="ref")
