"""On-chip smoke test: MetaTT serving and training at stablelm-1.6b width.

    python chip_smoke.py [--seed 0]          # one TPU chip
    python chip_smoke.py --four-chips        # the four-chip phase only

Phases, in order (any failure exits nonzero; no phase catches an error and
carries on):

1. device  — JAX must find a TPU; platform, device kind and count are
   printed first. There is no CPU fallback.
2. kernels — every Pallas kernel of the main path runs natively at
   stablelm-1.6b widths and is compared with its reference
   (``backend="ref"``) on the chip, each under a stated error bound.
3. serve   — ``Engine`` with a live MetaTT 4+1d adapter (rank 8, 4 tasks)
   over the paged KV cache: 16 mixed-task requests with a shared system
   prefix, ``generate`` twice. The compiled decode step must hold Pallas
   kernels (``tpu_custom_call``). On a 2-layer cut of the model, every
   request's first-token logits through the serving step function must
   match an f32 reference.
4. train   — ``Trainer`` with a MetaTT 4d adapter, 5 steps on batch 2 x
   2048 tokens; the loss under the native kernels must match the
   reference kernels on one batch, and every loss must be finite.

``--four-chips`` runs only the tensor-parallel serving engine
(mesh (1, 4)) against the single-chip engine, and the train step on a
(data=2, model=2) mesh against the single-device loss, at the full
stablelm-1.6b configuration.

Weights and data are random, made from ``--seed``. Timings printed are
smoke timings of this run, not benchmark results. The last line of stdout
is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# serving workload (one chip)
N_REQUESTS, PREFIX_LEN, NEW_TOKENS = 16, 128, 32
PROMPT_MIN, PROMPT_MAX = 64, 512
N_TASKS, RANK = 4, 8
#: per-core std of the random adapter TT. The 4+1d TT has five cores and
#: four rank-8 bonds, so its delta-W entries have std TT_SCALE^5 * 8^2:
#: 0.2 gives 0.020, the size of the base weights (1/sqrt(2048) = 0.022),
#: so each task's delta moves the argmax. 0.5 (the d_model-64 examples'
#: value) gives 2.0, 90x the base, and the bf16 model is then
#: ill-conditioned: bf16 rounding alone, in the XLA path as much as in
#: the kernels, moves the logits by a quarter of their size (CPU, 2
#: layers).
TT_SCALE = 0.2
# training workload (one chip)
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 5, 2, 2048

# Error bounds, as max|native - ref| / max|ref| per output. Both sides take
# bf16 inputs and accumulate in f32; what differs is summation order and
# where values are rounded to bf16 (a relative step of 2^-8 = 3.9e-3).
# A layout or indexing fault gives errors of order 1, far above these.
#: adapted linears: bf16 output rounding (one step each side) plus the
#: rank-r epilogue, which the reference runs at the TPU's default f32 dot
#: precision (bf16 passes).
BOUND_LINEAR = 2e-2
#: attention outputs: f32 scores and softmax statistics, probabilities
#: rounded to bf16 before the PV matmul in both paths, rescaled by the
#: online softmax in another order.
BOUND_ATTN = 2e-2
#: the per-row log-sum-exp: f32 from exact bf16 x bf16 products, so only
#: f32 summation order differs.
BOUND_LSE = 1e-3
#: flash gradients: ds = p * (dp - D) is rounded to bf16 before the dq/dk
#: matmuls, and the cancellation in dp - D magnifies relative rounding.
BOUND_GRAD = 5e-2
#: mean next-token loss over 2 x 2047 tokens: per-token logits differ by a
#: few bf16 steps after 24 layers, and the mean averages them out.
BOUND_LOSS = 1e-2
#: first-token logits of the served model, bf16 serving path vs an f32
#: reference, on a LOGIT_LAYERS-deep cut: two layers of bf16 activations
#: and KV (2^-8 relative steps) move the logits of these 16 requests by
#: 1.3-5.5% of their largest value in the XLA path (CPU); a layout or
#: indexing fault moves them by the order of their size.
BOUND_SERVE_LOGITS = 1.5e-1
#: depth of the logit check. Random adapter layers amplify rounding from
#: layer to layer: the bf16 XLA path itself is about 3% off f32 after 2
#: layers and up to 22% after 6 (1.4% after 6 with no adapter), so at
#: full depth a bf16 path cannot be held to an f32 reference. Every layer runs the same code, so two
#: layers cover the serving path (pool writer, paged attention, chunked
#: prefill, adapter epilogue).
LOGIT_LAYERS = 2


def _check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def require_tpu():
    """Print what JAX sees; raise unless it is a TPU (no CPU fallback)."""
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found {jax.default_backend()!r} "
            "devices. It does not fall back to the CPU.")
    return d


def _rel_err(got, want) -> float:
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    _check(bool(jnp.all(jnp.isfinite(got))), "non-finite kernel output")
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def kernel_parity(cfg, seed: int) -> None:
    """Each main-path kernel, native vs reference, at ``cfg``'s widths."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, quant

    d, f, h = cfg.d_model, cfg.d_ff, cfg.num_heads
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def rnd(shape, scale=1.0, dtype=bf):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    slots, t_len, chunk, page, cache = 8, 2048, 8, 16, 1024
    x_tok = rnd((t_len, d))
    x_slot = rnd((slots, d))
    w = rnd((d, f), d ** -0.5)
    a, b = rnd((d, RANK), d ** -0.5), rnd((RANK, f), RANK ** -0.5)
    a_slot = rnd((slots, d, RANK), d ** -0.5)
    wq = quant.quantize_linear(w)
    q, k, v = (rnd((1, t_len, h, hd)) for _ in range(3))
    g_out = rnd((1, t_len, h, hd))
    pages = cache // page
    n_blocks = slots * pages
    tables = jax.random.permutation(next(keys), n_blocks).reshape(
        slots, pages).astype(jnp.int32)
    pos = jax.random.randint(next(keys), (slots,), 0, cache - chunk)
    qp = rnd((slots, chunk, h, hd))
    kc, vc = rnd((n_blocks, kv, page, hd)), rnd((n_blocks, kv, page, hd))
    kq, ks = quant.quantize_kv(kc)
    vq, vs = quant.quantize_kv(vc)
    ks, vs = ks[:, :, None], vs[:, :, None]     # (N, KV, 1, page) pools
    qd = rnd((slots, 1, h, hd))
    kd, vd = rnd((slots, kv, cache, hd)), rnd((slots, kv, cache, hd))

    nat = dict(backend="pallas", interpret=False)
    ref = dict(backend="ref")
    o_ref, lse_ref = ops.flash_attention_fwd(q, k, v, **ref)
    cases = [
        ("tt_linear M=2048", lambda kw: ops.tt_linear(
            x_tok, w, a, b, **kw), BOUND_LINEAR),
        ("tt_linear M=8", lambda kw: ops.tt_linear(
            x_slot, w, a, b, **kw), BOUND_LINEAR),
        ("tt_linear_q (w8a16)", lambda kw: ops.tt_linear_q(
            x_tok, wq["q8"], wq["scale"], a, b, **kw), BOUND_LINEAR),
        ("tt_linear_batched_a", lambda kw: ops.tt_linear_batched_a(
            x_slot, w, a_slot, b, **kw), BOUND_LINEAR),
        ("tt_linear_batched_a_q", lambda kw: ops.tt_linear_batched_a_q(
            x_slot, wq["q8"], wq["scale"], a_slot, b, **kw), BOUND_LINEAR),
        ("flash_attention", lambda kw: ops.flash_attention(
            q, k, v, **kw), BOUND_ATTN),
        ("flash_attention_fwd out", lambda kw: ops.flash_attention_fwd(
            q, k, v, **kw)[0], BOUND_ATTN),
        ("flash_attention_fwd lse", lambda kw: ops.flash_attention_fwd(
            q, k, v, **kw)[1], BOUND_LSE),
        ("decode_attention", lambda kw: ops.decode_attention(
            qd, kd, vd, pos, **kw), BOUND_ATTN),
        ("paged_decode_attention", lambda kw: ops.paged_decode_attention(
            qp, kc, vc, tables, pos, **kw), BOUND_ATTN),
        ("paged_decode_attention int8", lambda kw:
            ops.paged_decode_attention(qp, kq, vq, tables, pos,
                                       k_scale=ks, v_scale=vs, **kw),
         BOUND_ATTN),
    ]
    bwd = [ops.flash_attention_bwd(q, k, v, o_ref, lse_ref, g_out, **kw)
           for kw in (nat, ref)]
    for name, fn, bound in cases:
        t0 = time.perf_counter()
        got = jax.block_until_ready(fn(nat))
        err = _rel_err(got, fn(ref))
        print(f"kernel {name:<30} rel_err={err:.3e} bound={bound:.0e} "
              f"(smoke timing: {time.perf_counter() - t0:.2f}s incl. "
              "compile)", flush=True)
        _check(err <= bound, f"{name}: rel_err {err} > {bound}")
    for i, name in enumerate(("dq", "dk", "dv")):
        err = _rel_err(bwd[0][i], bwd[1][i])
        print(f"kernel flash_attention_bwd {name:<14} rel_err={err:.3e} "
              f"bound={BOUND_GRAD:.0e}", flush=True)
        _check(err <= BOUND_GRAD, f"flash bwd {name}: {err} > {BOUND_GRAD}")


def _requests(cfg, seed: int):
    """16 mixed-task requests: prompts of 64-512 tokens that open with one
    shared 128-token system prefix, 32 new tokens each."""
    import numpy as np
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, PREFIX_LEN)
    lens = np.linspace(PROMPT_MIN, PROMPT_MAX, N_REQUESTS).astype(int)
    reqs = []
    for i, n in enumerate(lens):
        tail = rng.integers(0, cfg.vocab_size, max(n - PREFIX_LEN, 0))
        prompt = np.concatenate([prefix, tail])[:n].astype(np.int32)
        reqs.append(Request(prompt, NEW_TOKENS, task=i % N_TASKS,
                            request_id=f"r{i}"))
    return reqs


def _serving_params(cfg, seed: int):
    import jax
    from repro.config.base import RunConfig, SHAPES
    from repro.core import tt as ttlib
    from repro.models import model as M
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    adapter_kind="metatt", adapter_variant="4+1d",
                    num_tasks=N_TASKS, adapter_rank=RANK)
    spec = M.build_adapter_spec(run)
    key = jax.random.PRNGKey(seed)
    params = M.init_params(cfg, spec, key)
    params["adapter"] = {"cores": ttlib.random_tt(
        key, spec.cfg.mode_sizes, RANK, scale=TT_SCALE)}
    return spec, params


def _decode_step_text(eng) -> str:
    """Compiled text of the engine's paged decode step, lowered from
    abstract shapes (the engine's persistent pools are left alone)."""
    import jax
    import jax.numpy as jnp
    state = jax.eval_shape(lambda: eng._blank_paged_state(
        jax.random.PRNGKey(0), eng._paged_caches, eng._draft_pools))
    nan_at = jnp.full((eng._slots,), -1, jnp.int32)
    return eng._pdecode.lower(*eng._step_weights, state,
                              jnp.asarray(eng._tables),
                              nan_at).compile().as_text()


def _first_logits(cfg, rt, sv, reqs, policy):
    """Each request's first-token logits through the serving step function
    (``transformer.paged_step``: the co-batched (slots, chunk) block, the
    paged pool writer and the paged attention), prompts fed ``sv``'s
    prefill chunk at a time, ``max_batch`` requests per pass, as the
    engine feeds them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer
    c, page, slots = sv.prefill_chunk, sv.page_size, sv.max_batch
    width = -(-max(len(r.prompt) for r in reqs) // c) * c
    pages = -(-width // page)
    # one sentinel column: pad-column writes past a prompt's pages drop
    tables = jnp.asarray(np.concatenate(
        [np.arange(slots * pages).reshape(slots, pages),
         np.full((slots, 1), slots * pages)], 1), jnp.int32)

    @jax.jit
    def step(base, bc, pl, caches, toks, pos, sel, task):
        return transformer.paged_step(
            base, cfg, rt.spec, bc, pl, toks, caches, tables, pos, sel,
            task=task, policy=policy)

    out = []
    for g in range(0, len(reqs), slots):
        group = reqs[g:g + slots]
        toks = np.zeros((slots, width), np.int32)
        plen = np.ones(slots, np.int32)
        task = np.zeros(slots, np.int32)
        for i, r in enumerate(group):
            toks[i, :len(r.prompt)] = r.prompt
            plen[i], task[i] = len(r.prompt), r.task
        caches = transformer.init_paged_caches(
            cfg, slots * pages, page, cfg.compute_dtype)
        first = [None] * len(group)
        for s in range(0, width, c):
            sel = np.clip(plen - 1 - s, 0, c - 1).astype(np.int32)
            lg, caches = step(rt.base, rt.broadcast, rt.per_layer, caches,
                              jnp.asarray(toks[:, s:s + c]),
                              jnp.full((slots,), s, jnp.int32),
                              jnp.asarray(sel), jnp.asarray(task))
            lg = np.asarray(lg.astype(jnp.float32))
            for i in range(len(group)):
                if s <= plen[i] - 1 < s + c:
                    first[i] = lg[i]
        out += first
    return np.stack(out)


def serve_logit_parity(cfg, kernels, seed: int) -> None:
    """The served requests' first-token logits on a ``LOGIT_LAYERS``-deep
    cut of ``cfg`` (full widths, weights from ``seed``): the serving path
    under ``kernels`` (bf16) and the XLA reference path (bf16) each
    against an f32 reference (f32 activations and KV, ``highest`` matmul
    precision, same bf16 weights). Then the greedy tokens of an ``Engine``
    under ``kernels`` against one under ``backend="ref"`` on the same cut,
    for information."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.config.base import KernelConfig
    from repro.kernels import dispatch
    from repro.serving import AdapterRuntime, Engine
    cfg = dataclasses.replace(cfg, num_layers=LOGIT_LAYERS).validate()
    spec, params = _serving_params(cfg, seed)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    reqs = _requests(cfg, seed)
    sv = _serve_config()
    nat = _first_logits(cfg, rt, sv, reqs, dispatch.resolve(kernels))
    ref = _first_logits(cfg, rt, sv, reqs, dispatch.REF)
    with jax.default_matmul_precision("highest"):
        f32 = _first_logits(dataclasses.replace(
            cfg, compute_dtype=jnp.float32), rt, sv, reqs, dispatch.REF)
    scale = np.abs(f32).max(axis=1)
    err_nat = float((np.abs(nat - f32).max(axis=1) / scale).max())
    err_ref = float((np.abs(ref - f32).max(axis=1) / scale).max())
    agree = lambda a: float(np.mean(a.argmax(1) == f32.argmax(1)))
    print(f"serve logits, {LOGIT_LAYERS}-layer cut, vs f32 reference (max "
          f"over requests of max|err|/max|f32|): native {err_nat:.3e}, "
          f"XLA ref {err_ref:.3e}, bound {BOUND_SERVE_LOGITS:.0e}; "
          f"first-token argmax agreement with f32: native "
          f"{agree(nat):.4f}, XLA ref {agree(ref):.4f}", flush=True)
    _check(err_nat <= BOUND_SERVE_LOGITS,
           f"serve logits native vs f32: {err_nat} > {BOUND_SERVE_LOGITS}")
    outs = [Engine(cfg, rt, kernels=k, serve=sv).generate(reqs)
            for k in (kernels, KernelConfig(backend="ref"))]
    print(f"serve, {LOGIT_LAYERS}-layer cut: greedy-token match of the "
          f"engine vs the KernelConfig(backend='ref') engine "
          f"{_match(*outs):.4f} (information only: a near-tie flipped by "
          "rounding changes every later token)", flush=True)


def _serve_config(**kw):
    from repro.config.base import ServeConfig
    return ServeConfig(max_batch=8, cache_len=1024, out_cap=64, **kw)


def _check_served(eng, outs, label: str) -> None:
    from repro.serving import FINISHED
    st = eng.last_stats
    statuses = [r.status for r in eng.last_results]
    _check(all(s == FINISHED for s in statuses),
           f"{label}: not all requests FINISHED: {statuses}")
    _check(st.numerics_faults == 0 and st.failed_requests == 0,
           f"{label}: NaN logits in {st.numerics_faults} steps")
    _check(all(len(o) == NEW_TOKENS for o in outs),
           f"{label}: short outputs {[len(o) for o in outs]}")


def _match(a, b) -> float:
    import numpy as np
    same = sum(int(np.sum(np.asarray(x) == np.asarray(y)))
               for x, y in zip(a, b))
    return same / sum(len(x) for x in a)


def serve_phase(cfg, kernels, seed: int) -> None:
    from repro.serving import AdapterRuntime, Engine
    spec, params = _serving_params(cfg, seed)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    reqs = _requests(cfg, seed)
    eng = Engine(cfg, rt, kernels=kernels, serve=_serve_config())
    t0 = time.perf_counter()
    outs = eng.generate(reqs)
    t1 = time.perf_counter()
    _check_served(eng, outs, "serve call 1")
    outs = eng.generate(reqs)
    t2 = time.perf_counter()
    _check_served(eng, outs, "serve call 2")
    st = eng.last_stats
    print(f"serve stats (call 2): {st.summary()}", flush=True)
    _check(st.decode_traces == 1,
           f"decode graph traced {st.decode_traces} times")
    _check(st.prefix_hit_rate > 0, "no prefix-cache hits on call 2")
    toks = sum(len(o) for o in outs)
    print(f"serve smoke timing (not a benchmark): call 1 (compile + run) "
          f"{t1 - t0:.1f}s; call 2 {t2 - t1:.2f}s for {toks} tokens, "
          f"{toks / (t2 - t1):.1f} tok/s", flush=True)
    _check("tpu_custom_call" in _decode_step_text(eng),
           "paged decode step holds no Pallas kernel")
    print("serve: paged decode step contains tpu_custom_call", flush=True)
    del eng, rt, params
    gc.collect()
    serve_logit_parity(cfg, kernels, seed)


def train_phase(cfg, kernels, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.config.base import RunConfig, SHAPES, TrainConfig
    from repro.data import LMStream
    from repro.kernels import dispatch
    from repro.models import model as M
    from repro.train.trainer import Trainer
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    adapter_kind="metatt", adapter_variant="4d",
                    adapter_rank=RANK, kernels=kernels,
                    train=TrainConfig(remat="block", seed=seed))

    def stream():
        return LMStream(vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
                        batch=TRAIN_BATCH, seed=seed)

    tr = Trainer(run=run, data=stream(), total_steps=TRAIN_STEPS)
    probe = {k: jnp.asarray(v) for k, v in next(stream()).items()}

    def loss_under(policy):
        fn = jax.jit(lambda ad, base, fr, bt: M.loss_fn(
            ad, base, fr, bt, cfg, tr.spec, policy=policy)[0])
        return float(fn(tr.state.adapter, tr.base, tr.frozen, probe))

    l_nat = loss_under(dispatch.resolve(kernels))
    l_ref = loss_under(dispatch.REF)
    rel = abs(l_nat - l_ref) / abs(l_ref)
    print(f"train: loss native={l_nat:.6f} ref={l_ref:.6f} rel={rel:.2e} "
          f"bound={BOUND_LOSS:.0e}", flush=True)
    _check(rel <= BOUND_LOSS, f"train loss native vs ref: {rel}")
    t0 = time.perf_counter()
    compiled = tr.step_fn.lower(tr.state, tr.base, tr.frozen,
                                probe).compile()
    print(f"train smoke timing (not a benchmark): step compile "
          f"{time.perf_counter() - t0:.1f}s; memory "
          f"{compiled.memory_analysis()}", flush=True)
    _check("tpu_custom_call" in compiled.as_text(),
           "train step holds no Pallas kernel")
    print("train: compiled train step contains tpu_custom_call", flush=True)
    hist = tr.train()
    losses = [m["loss"] for _, m in hist]
    times = [m["step_time_s"] for _, m in hist]
    _check(len(losses) == TRAIN_STEPS and all(np.isfinite(losses)),
           f"train losses {losses}")
    print(f"train: losses {losses}", flush=True)
    print(f"train smoke timing (not a benchmark): step 0 {times[0]:.2f}s, "
          f"steady steps {[round(t, 3) for t in times[1:]]}s for "
          f"{TRAIN_BATCH}x{TRAIN_SEQ} tokens", flush=True)


def four_chip_phase(cfg, seed: int) -> None:
    """TP serving on a (1, 4) mesh vs one chip; the train step on a
    (data=2, model=2) mesh vs the single-device loss."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.config.base import RunConfig, SHAPES, TrainConfig
    from repro.distributed import GradCompressor
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.serving import AdapterRuntime, Engine
    from repro.sharding import params_sharding
    from repro.train import train_step as ts
    _check(len(jax.devices()) == 4, f"need 4 devices, {jax.devices()}")

    spec, params = _serving_params(cfg, seed)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    reqs = _requests(cfg, seed)
    one = Engine(cfg, rt, serve=_serve_config())
    out1 = one.generate(reqs)
    _check_served(one, out1, "one-chip engine")
    del one
    tp = Engine(cfg, rt, serve=_serve_config(mesh_shape=(1, 4)))
    t0 = time.perf_counter()
    out4 = tp.generate(reqs)
    print(f"four-chip serve smoke timing (not a benchmark): "
          f"{time.perf_counter() - t0:.1f}s incl. compile", flush=True)
    _check_served(tp, out4, "tp=4 engine")
    pools = jax.tree_util.tree_leaves(tp._paged_caches)
    spans = {len(x.sharding.device_set) for x in pools}
    _check(spans == {4}, f"TP pools span {spans} devices, want 4")
    print(f"four-chip serve stats: {tp.last_stats.summary()}", flush=True)
    same = _match(out4, out1)
    print(f"four-chip serve: KV pools span 4 devices; greedy-token match "
          f"tp=4 vs one chip: {same:.4f} "
          f"({'identical' if same == 1.0 else 'MISMATCH'})", flush=True)
    del tp, rt, params
    gc.collect()

    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    adapter_kind="metatt", adapter_rank=RANK,
                    train=TrainConfig(remat="block", seed=seed))
    spec = M.build_adapter_spec(run)
    key = jax.random.PRNGKey(seed)
    params = M.init_params(cfg, spec, key)
    toks = jax.random.randint(key, (4, 512), 0, cfg.vocab_size)
    _, m_ref = jax.jit(lambda ad, base, fr, t: M.loss_fn(
        ad, base, fr, {"tokens": t}, cfg, spec))(
        params["adapter"], params["base"], params["frozen"], toks)
    l_ref = float(m_ref["ce"])
    mesh = make_host_mesh(2, 2)
    with jax.set_mesh(mesh):
        base = jax.device_put(params["base"],
                              params_sharding(params["base"], mesh))
        spans = {len(x.sharding.device_set)
                 for x in jax.tree_util.tree_leaves(base)}
        _check(spans == {4}, f"sharded base spans {spans} devices")
        state = ts.init_train_state(params["adapter"],
                                    GradCompressor("none"))
        step = ts.make_train_step(cfg, spec, run.optimizer, run.train, 100)
        batch = {"tokens": jax.device_put(
            toks, NamedSharding(mesh, P("data", None)))}
        state, mets = step(state, base, params["frozen"], batch)
    l_sh = float(mets["ce"])
    rel = abs(l_sh - l_ref) / abs(l_ref)
    print(f"four-chip train: loss (data=2, model=2) {l_sh:.6f} vs one "
          f"device {l_ref:.6f}, rel={rel:.2e} bound={BOUND_LOSS:.0e}",
          flush=True)
    _check(rel <= BOUND_LOSS and bool(jnp.isfinite(l_sh)),
           f"sharded train loss {l_sh} vs {l_ref}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (needs 4 chips)")
    args = ap.parse_args(argv)
    dev = require_tpu()
    import jax
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro import configs
    from repro.config.base import KernelConfig
    from repro.kernels import dispatch
    from repro.launch import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    cfg = configs.get_config("stablelm-1.6b")
    t_start = time.perf_counter()
    if args.four_chips:
        four_chip_phase(cfg, args.seed)
    else:
        kernels = KernelConfig()
        pol = dispatch.resolve(kernels)
        _check(pol.use_pallas and not pol.interpret,
               f"KernelConfig() resolved to {pol} on TPU")
        kernel_parity(cfg, args.seed)
        serve_phase(cfg, kernels, args.seed)
        train_phase(cfg, kernels, args.seed)
    print(f"smoke wall time (not a benchmark): "
          f"{time.perf_counter() - t_start:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
