"""Kernel benches through the SAME dispatch seam the model uses.

Every row calls ``repro.kernels.dispatch`` (or the model forward with a
KernelPolicy) — no benchmark-only kernel entry points — so fused-vs-unfused
numbers measure exactly what training/serving executes. On CPU the Pallas
rows run interpret mode (a correctness emulator, orders of magnitude slower
than the compiled kernel; TPU is the performance target) — the ref rows are
the meaningful CPU timings, the derived column carries the fusion's HBM
arithmetic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_call
from repro import configs as registry
from repro.config.base import RunConfig, SHAPES
from repro.core import tt as ttlib
from repro.kernels import dispatch, quant
from repro.models import model as M
from repro.models import transformer as T
from repro.peft import api as peft_api

POLICIES = (("ref", dispatch.REF),
            ("pallas_interpret", dispatch.PALLAS_INTERPRET))


def _linear_rows(rows) -> None:
    key = jax.random.PRNGKey(0)
    m_, k, n, r = 256, 512, 512, 16
    x = jax.random.normal(key, (m_, k), jnp.float32)
    w = jax.random.normal(key, (k, n), jnp.float32) / 32
    a = jax.random.normal(key, (k, r), jnp.float32) / 32
    b = jax.random.normal(key, (r, n), jnp.float32) / 4
    # HBM savings of the fusion (the TPU story): unfused writes+reads the
    # (M, N) base output one extra time -> 2*M*N*2B saved per call
    saved = 2 * m_ * n * 2
    for name, pol in POLICIES:
        us = time_call(jax.jit(lambda *t, p=pol: dispatch.tt_linear(
            *t, alpha=1.0, policy=p)), x, w, a, b, iters=3, warmup=1)
        rows.append(emit(f"kernels/tt_linear_{name}", us,
                         f"M={m_},K={k},N={n},r={r},"
                         f"hbm_roundtrip_saved_bytes={saved}"))

    # w8a16: int8 base + f32 per-channel scales through the same seam —
    # the TPU story is the weight HBM read dropping from 4B (f32) / 2B
    # (bf16) to 1B per element (+ one f32 scale per output channel)
    wq = quant.quantize_linear(w)
    w_bytes_fp = k * n * 4
    w_bytes_q = k * n * 1 + n * 4
    for name, pol in POLICIES:
        us = time_call(jax.jit(lambda x_, a_, b_, p=pol: dispatch.tt_linear_q(
            x_, wq, a_, b_, alpha=1.0, policy=p)), x, a, b,
            iters=3, warmup=1)
        rows.append(emit(f"kernels/tt_linear_w8a16_{name}", us,
                         f"M={m_},K={k},N={n},r={r},"
                         f"w_bytes={w_bytes_q}vs{w_bytes_fp}"))

    s = 8                                 # decode slots
    xa = jax.random.normal(key, (s, k), jnp.float32)
    ab = jax.random.normal(key, (s, k, r), jnp.float32) / 32
    for name, pol in POLICIES:
        us = time_call(jax.jit(lambda *t, p=pol: dispatch.tt_linear_batched_a(
            *t, alpha=1.0, policy=p)), xa, w, ab, b, iters=3, warmup=1)
        rows.append(emit(f"kernels/tt_linear_batched_a_{name}", us,
                         f"slots={s},K={k},N={n},r={r}"))
    for name, pol in POLICIES:
        us = time_call(jax.jit(
            lambda x_, a_, b_, p=pol: dispatch.tt_linear_batched_a_q(
                x_, wq, a_, b_, alpha=1.0, policy=p)), xa, ab, b,
            iters=3, warmup=1)
        rows.append(emit(f"kernels/tt_linear_batched_a_w8a16_{name}", us,
                         f"slots={s},K={k},N={n},r={r}"))


def _attention_rows(rows) -> None:
    key = jax.random.PRNGKey(1)
    b, t, h, kv, d = 2, 256, 8, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, kv, d), jnp.float32)
    for name, pol in POLICIES:
        us = time_call(jax.jit(lambda *x_, p=pol: dispatch.flash_attention(
            *x_, causal=True, policy=p)), q, k, v, iters=3, warmup=1)
        rows.append(emit(f"kernels/flash_attention_{name}", us,
                         f"B={b},T={t},H={h},KV={kv},d={d}"))

    s_len = 128
    qd = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    kd = jax.random.normal(ks[1], (b, kv, s_len, d), jnp.float32)
    vd = jax.random.normal(ks[2], (b, kv, s_len, d), jnp.float32)
    pos = jnp.array([17, 103])
    for name, pol in POLICIES:
        us = time_call(jax.jit(lambda *x_, p=pol: dispatch.decode_attention(
            *x_, policy=p)), qd, kd, vd, pos, iters=3, warmup=1)
        rows.append(emit(f"kernels/decode_attention_{name}", us,
                         f"B={b},S={s_len},H={h},KV={kv},d={d}"))


def _model_rows(rows) -> None:
    """End-to-end: the full smoke-model forward, fused vs unfused, from the
    same AdapterCtx.policy seam the trainer/engine thread."""
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    adapter_kind="metatt", adapter_rank=8)
    spec = M.build_adapter_spec(run)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, spec, key)
    params["adapter"] = {"cores": ttlib.random_tt(key, spec.cfg.mode_sizes,
                                                  8, scale=0.1)}
    bc, pl = peft_api.adapter_factors(spec, params["adapter"],
                                      params["frozen"])
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab_size)
    for name, pol in POLICIES:
        fn = jax.jit(lambda tok, p=pol: T.forward(
            params["base"], cfg, spec, bc, pl, tok, policy=p).logits)
        us = time_call(fn, tokens, iters=3, warmup=1)
        rows.append(emit(f"model/forward_{name}", us,
                         f"arch={cfg.name},adapter=metatt-r8"))


def run(*, smoke: bool = False) -> list:
    del smoke                       # shapes are already CI-sized
    rows = []
    _linear_rows(rows)
    _attention_rows(rows)
    _model_rows(rows)
    return rows


if __name__ == "__main__":
    run()
