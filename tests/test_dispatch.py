"""Fused-dispatch vs reference parity (interpret mode on CPU).

Pins the kernel-dispatch refactor's acceptance criteria:
  * full-model forward/loss agreement between
    ``KernelConfig(backend="pallas", interpret=True)`` and the ref path
    across adapter kinds (metatt 4d / 4+1d, lora, vera, lotr), dtypes and
    deliberately non-tile-multiple shapes,
  * serving-engine decode parity with the fused batched-A kernel
    (per-slot task routing stays inside one kernel),
  * ops-level tile padding on every dim (N/K for tt_linear, odd sequence
    lengths for flash/decode attention),
  * gradients through the fused custom VJPs (the *train* hot path),
  * the two-site DMRG sweep's exact resplit + per-bond gradient count.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as registry
from repro.config.base import KernelConfig, RunConfig, SHAPES
from repro.core import dmrg as dmrg_lib
from repro.core import tt as ttlib
from repro.kernels import dispatch, ops
from repro.models import model as M
from repro.models import transformer as T
from repro.peft import api as peft_api
from repro.serving import AdapterRuntime, Engine, Request

KEY = jax.random.PRNGKey(0)
PALLAS = dispatch.resolve(KernelConfig(backend="pallas", interpret=True))
REF = dispatch.resolve(KernelConfig(backend="ref"))


def _setup(kind="metatt", variant="4d", num_tasks=0, model_cfg=None,
           matrices=(), rank=4, scale=0.5):
    cfg = model_cfg or registry.get_smoke_config("stablelm-1.6b")
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"], adapter_kind=kind,
                    adapter_variant=variant, num_tasks=num_tasks,
                    adapter_rank=rank, adapter_matrices=matrices)
    spec = M.build_adapter_spec(run)
    params = M.init_params(cfg, spec, KEY)
    if kind == "metatt":
        params["adapter"] = {"cores": ttlib.random_tt(
            KEY, spec.cfg.mode_sizes, rank, scale=scale)}
    else:   # zero-init B/g/S factors would make the fused route vacuous
        params["adapter"] = jax.tree_util.tree_map(
            lambda a: scale * jax.random.normal(KEY, a.shape, a.dtype),
            params["adapter"])
    return cfg, spec, params


def _forward(cfg, spec, params, tokens, policy, task=None):
    bc, pl = peft_api.adapter_factors(spec, params["adapter"],
                                      params["frozen"])
    return T.forward(params["base"], cfg, spec, bc, pl, tokens, task=task,
                     policy=policy)


# ---------------------------------------------------------------------------
# full-model forward / loss parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,variant,num_tasks", [
    ("metatt", "4d", 0),
    ("metatt", "4+1d", 2),
    ("lora", "4d", 0),
    ("vera", "4d", 0),
    ("lotr", "4d", 0),
])
def test_forward_loss_parity_across_adapter_kinds(kind, variant, num_tasks):
    cfg, spec, params = _setup(kind, variant, num_tasks)
    tokens = jax.random.randint(KEY, (2, 9), 0, cfg.vocab_size)
    task = jnp.int32(1) if variant == "4+1d" else None
    out_p = _forward(cfg, spec, params, tokens, PALLAS, task)
    out_r = _forward(cfg, spec, params, tokens, REF, task)
    out_legacy = _forward(cfg, spec, params, tokens, None, task)
    np.testing.assert_allclose(out_p.logits, out_r.logits,
                               atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(out_p.logits, out_legacy.logits,
                               atol=5e-5, rtol=5e-5)
    batch = {"tokens": tokens, "task": task}
    loss_p = M.loss_fn(params["adapter"], params["base"], params["frozen"],
                       batch, cfg, spec, policy=PALLAS)[0]
    loss_r = M.loss_fn(params["adapter"], params["base"], params["frozen"],
                       batch, cfg, spec, policy=None)[0]
    np.testing.assert_allclose(loss_p, loss_r, atol=1e-5, rtol=1e-5)


def test_forward_parity_batched_task_vector():
    """Per-example (B,) task ids (4+1d) hit the batched-A seam in train
    shape (T > 1) — the dispatch falls back to the batched-einsum leg of
    the SAME entry point."""
    cfg, spec, params = _setup("metatt", "4+1d", num_tasks=3)
    tokens = jax.random.randint(KEY, (3, 6), 0, cfg.vocab_size)
    tv = jnp.array([0, 2, 1], jnp.int32)
    out_p = _forward(cfg, spec, params, tokens, PALLAS, tv)
    out_l = _forward(cfg, spec, params, tokens, None, tv)
    np.testing.assert_allclose(out_p.logits, out_l.logits,
                               atol=5e-5, rtol=5e-5)


def test_forward_parity_bf16():
    cfg = dataclasses.replace(registry.get_smoke_config("stablelm-1.6b"),
                              param_dtype=jnp.bfloat16,
                              compute_dtype=jnp.bfloat16)
    cfg2, spec, params = _setup(model_cfg=cfg)
    tokens = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
    out_p = _forward(cfg, spec, params, tokens, PALLAS)
    out_r = _forward(cfg, spec, params, tokens, REF)
    np.testing.assert_allclose(np.asarray(out_p.logits, np.float32),
                               np.asarray(out_r.logits, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_forward_parity_non_tile_multiple_shapes():
    """GeGLU d_ff, odd d_model/vocab/seq — nothing is a 128 multiple, so
    every kernel call exercises the pad-and-slice path, including the
    ffn_* adapted matrices."""
    cfg = dataclasses.replace(
        registry.get_smoke_config("stablelm-1.6b"), name="odd-smoke",
        d_model=40, num_heads=4, num_kv_heads=2, d_ff=72, vocab_size=77,
        mlp="geglu")
    cfg2, spec, params = _setup(
        model_cfg=cfg,
        matrices=("attn_q", "attn_v", "ffn_up", "ffn_down", "ffn_gate"))
    tokens = jax.random.randint(KEY, (2, 9), 0, cfg.vocab_size)
    out_p = _forward(cfg, spec, params, tokens, PALLAS)
    out_r = _forward(cfg, spec, params, tokens, REF)
    np.testing.assert_allclose(out_p.logits, out_r.logits,
                               atol=5e-5, rtol=5e-5)


def test_grad_parity_through_fused_vjp():
    """The TRAIN hot path: value_and_grad through the fused kernels (the
    custom VJP whose dx GEMM is the fused kernel itself) must match the
    reference autodiff."""
    cfg, spec, params = _setup()
    batch = {"tokens": jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)}

    def run(policy):
        def f(adapter):
            return M.loss_fn(adapter, params["base"], params["frozen"],
                             batch, cfg, spec, policy=policy)[0]
        return jax.value_and_grad(f)(params["adapter"])

    (loss_p, grads_p) = run(PALLAS)
    (loss_r, grads_r) = run(None)
    np.testing.assert_allclose(loss_p, loss_r, atol=1e-5, rtol=1e-5)
    for gp, gr in zip(jax.tree_util.tree_leaves(grads_p),
                      jax.tree_util.tree_leaves(grads_r)):
        np.testing.assert_allclose(gp, gr, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# serving engine: fused batched-A decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["live", "lora"])
def test_engine_decode_fused_batched_a_matches_ref(mode):
    """Mixed-task continuous batching with the fused kernels (decode runs
    ``tt_linear_batched_a`` with the slot-gathered A) must be
    token-identical to the unfused engine."""
    cfg, spec, params = _setup("metatt", "4+1d", num_tasks=3, scale=0.8)
    rt = AdapterRuntime.build(mode, params["base"], spec,
                              params["adapter"], params["frozen"])
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (4 + i,), 0,
                                  cfg.vocab_size) for i in range(4)]
    reqs = [Request(p, 5, task=i % 3) for i, p in enumerate(prompts)]
    kw = dict(max_batch=3, cache_len=32, out_cap=8)
    ref_out = Engine(cfg, rt, **kw).generate(reqs)
    fused_out = Engine(cfg, rt, kernels=KernelConfig(
        backend="pallas", interpret=True), **kw).generate(reqs)
    for r, f in zip(ref_out, fused_out):
        assert r.tolist() == f.tolist()


# ---------------------------------------------------------------------------
# ops-level tile padding
# ---------------------------------------------------------------------------


def test_ops_tt_linear_pads_n_and_k():
    """Non-multiple N and K (GeGLU d_ff / odd vocab slices) used to trip
    the kernel assert — now they pad with zeros and slice back."""
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (3, 5, 200), jnp.float32)
    w = jax.random.normal(ks[1], (200, 391), jnp.float32) / 14
    a = jax.random.normal(ks[2], (200, 9), jnp.float32) / 14
    b = jax.random.normal(ks[3], (9, 391), jnp.float32) / 3
    y = ops.tt_linear(x, w, a, b, alpha=1.3, backend="pallas",
                      interpret=True)
    want = ops.tt_linear(x, w, a, b, alpha=1.3, backend="ref")
    np.testing.assert_allclose(y, want, atol=1e-4, rtol=1e-4)
    assert y.shape == (3, 5, 391)


def test_ops_tt_linear_batched_a_pads_all_dims():
    s, k, n, r = 5, 96, 130, 6
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (s, k))
    w = jax.random.normal(ks[1], (k, n)) / 10
    a = jax.random.normal(ks[2], (s, k, r)) / 10
    b = jax.random.normal(ks[3], (r, n)) / 2
    y = ops.tt_linear_batched_a(x, w, a, b, alpha=0.7, backend="pallas",
                                interpret=True)
    want = ops.tt_linear_batched_a(x, w, a, b, alpha=0.7, backend="ref")
    np.testing.assert_allclose(y, want, atol=1e-4, rtol=1e-4)
    # decode layout (S, 1, K) round-trips too
    y3 = ops.tt_linear_batched_a(x[:, None], w, a, b, alpha=0.7,
                                 backend="pallas", interpret=True)
    np.testing.assert_allclose(y3[:, 0], want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ops_flash_attention_pads_odd_seq_lens(causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 70, 4, 32))
    k = jax.random.normal(ks[1], (2, 70, 2, 32))
    v = jax.random.normal(ks[2], (2, 70, 2, 32))
    y = ops.flash_attention(q, k, v, causal=causal, backend="pallas",
                            interpret=True)
    want = ops.flash_attention(q, k, v, causal=causal, backend="ref")
    np.testing.assert_allclose(y, want, atol=2e-4, rtol=2e-4)
    assert y.shape == q.shape


def test_ops_decode_attention_matches_ref():
    b, s, h, kv, d = 3, 40, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    k = jax.random.normal(ks[1], (b, kv, s, d))    # head-major caches
    v = jax.random.normal(ks[2], (b, kv, s, d))
    pos = jnp.array([0, 7, 39])     # includes a fresh slot and a full cache
    y = ops.decode_attention(q, k, v, pos, backend="pallas",
                             interpret=True)
    want = ops.decode_attention(q, k, v, pos, backend="ref")
    np.testing.assert_allclose(y, want, atol=2e-4, rtol=2e-4)
    assert y.shape == (b, 1, h, d)


# ---------------------------------------------------------------------------
# DMRG two-site sweep fixes
# ---------------------------------------------------------------------------


def test_two_site_sweep_exact_resplit_and_grad_count():
    cores = ttlib.random_tt(KEY, (12, 3, 2, 12), rank=6, scale=0.3)
    calls = {"n": 0}

    def loss_fn(params):
        calls["n"] += 1
        return ttlib.tt_norm(params["cores"]) ** 2

    inner = 3
    res = dmrg_lib.two_site_sweep({"cores": cores}, loss_fn, target_rank=4,
                                  inner_steps=inner)
    assert res.ranks == (4, 4, 4)
    # the local problem descends the loss, so the norm must shrink
    assert float(ttlib.tt_norm(res.params["cores"])) < \
        float(ttlib.tt_norm(cores))
    # exactly inner_steps gradient traces per bond, two passes over the
    # d-1 bonds (the old loop computed one wasted extra gradient each)
    d = len(cores)
    assert calls["n"] == 2 * (d - 1) * inner
