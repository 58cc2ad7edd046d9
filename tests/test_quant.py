"""Quantized serving path (kernels/quant.py, w8a16 fused kernels, int8
paged KV cache — DESIGN.md §8). Acceptance criteria:

  * quantize -> dequantize round-trips within the symmetric-int8 bound
    (half a scale step per element), per-channel and group-wise,
  * the w8a16 fused kernels match their dequantize-then-fp oracles in
    interpret mode across adapter kinds and odd (non-tile-multiple)
    shapes — the SAME quantized numbers through two execution paths,
  * the int8 paged KV cache (per-cell scale pools, in-register dequant)
    matches its explicit-dequant reference twin, and the int8 engine's
    pallas leg is token-identical to its ref leg,
  * the int8 engine tracks the fp engine's greedy tokens on the smoke
    config (documented tolerance: the quantization error can flip
    near-tie argmaxes on a random-weight model; >= 90% positional match
    is asserted, and in practice the first tokens of every request
    agree),
  * prefix cache + COW round-trip the quantized representation (warm ==
    cold on an int8 engine),
  * the quantized-base snapshot round-trips through checkpoint/ckpt.py
    with int8 dtypes preserved.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as registry
from repro.checkpoint import ckpt as ckpt_lib
from repro.config.base import (KernelConfig, QuantConfig, RunConfig, SHAPES,
                               ServeConfig)
from repro.core import tt as ttlib
from repro.kernels import dispatch, ops, quant, ref
from repro.models import model as M
from repro.models import transformer as T
from repro.peft import api as peft_api
from repro.serving import AdapterRuntime, Engine, Request

KEY = jax.random.PRNGKey(0)
PALLAS = dispatch.resolve(KernelConfig(backend="pallas", interpret=True))
REF = dispatch.resolve(KernelConfig(backend="ref"))

#: documented greedy-parity tolerance of the int8 engine vs the fp engine
#: (argmax near-ties under quantization noise; see module docstring)
TOKEN_MATCH_MIN = 0.9


# ---------------------------------------------------------------------------
# quantize / dequantize
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("group", [0, 128])
def test_quantize_dequantize_error_bound(group):
    w = jax.random.normal(KEY, (256, 130), jnp.float32)
    q, scale = quant.quantize_int8(w, group_size=group)
    assert q.dtype == jnp.int8
    assert scale.shape == ((1, 130) if group == 0 else (2, 130))
    dq = quant.dequantize_int8(q, scale)
    # symmetric rounding: at most half a scale step per element, with the
    # scale taken over that element's K group
    g = scale.shape[0]
    bound = jnp.repeat(scale, 256 // g, axis=0) * 0.5 + 1e-7
    assert bool(jnp.all(jnp.abs(dq - w) <= bound))
    # group-wise scales are no coarser than per-channel ones
    if group:
        _, sc_pc = quant.quantize_int8(w)
        assert float(jnp.max(scale)) <= float(jnp.max(sc_pc)) + 1e-12


def test_quantize_rejects_indivisible_group():
    w = jnp.ones((100, 8))
    with pytest.raises(ValueError):
        quant.quantize_int8(w, group_size=64)


def test_quantize_base_packs_hot_leaves_only():
    cfg = registry.get_smoke_config("stablelm-1.6b")
    base = T.init_base_params(cfg, KEY)
    qbase = quant.quantize_base(base, group_size=0)
    blk = qbase["blocks"][0]
    for key in ("wq", "wk", "wv", "wo"):
        assert quant.is_quantized(blk["mixer"][key])
        assert blk["mixer"][key]["q8"].dtype == jnp.int8
    for key in ("wu", "wd"):
        assert quant.is_quantized(blk["ffn"][key])
    # embeddings / norms stay fp; the input tree is not mutated
    assert not quant.is_quantized(qbase["embed"]["tok"])
    assert qbase["final_norm"] is base["final_norm"]
    assert not quant.is_quantized(base["blocks"][0]["mixer"]["wq"])
    # a group size that does not divide some K falls back per-channel
    qb2 = quant.quantize_base(base, group_size=1024)
    for blk2 in qb2["blocks"]:
        for w8 in blk2["mixer"].values():
            if quant.is_quantized(w8):
                k = w8["q8"].shape[-2]
                want_g = k // 1024 if k % 1024 == 0 and k >= 1024 else 1
                assert w8["scale"].shape[-2] == max(want_g, 1)


def test_quant_config_validation():
    with pytest.raises(ValueError):
        QuantConfig(weights="int4").validate()
    with pytest.raises(ValueError):
        QuantConfig(group_size=100).validate()
    with pytest.raises(ValueError):
        ServeConfig(cache_mode="dense",
                    quant=QuantConfig(kv="int8")).validate()
    ServeConfig(quant=QuantConfig(kv="int8")).validate()   # paged: fine


# ---------------------------------------------------------------------------
# w8a16 fused kernels vs oracles (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n,r,group", [
    (128, 256, 256, 8, 0),
    (12, 200, 391, 9, 0),       # odd everything -> pad-and-slice path
    (8, 256, 384, 16, 128),     # group-wise: one scale row per K tile
])
def test_w8_tt_linear_matches_ref_twin(m, k, n, r, group):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (m, k), jnp.float32)
    w = jax.random.normal(ks[1], (k, n), jnp.float32) / np.sqrt(k)
    a = jax.random.normal(ks[2], (k, r), jnp.float32) / np.sqrt(k)
    b = jax.random.normal(ks[3], (r, n), jnp.float32) / np.sqrt(r)
    wq, scale = quant.quantize_int8(w, group_size=group)
    y = ops.tt_linear_q(x, wq, scale, a, b, alpha=1.3, backend="pallas",
                        interpret=True)
    want = ref.tt_linear_q_ref(x, wq, scale, a, b, alpha=1.3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    # and the quantized result tracks the fp one at int8 resolution
    fp = ref.tt_linear_ref(x, w, a, b, alpha=1.3)
    assert float(jnp.max(jnp.abs(y - fp))) < 0.1


@pytest.mark.parametrize("group", [0, 128])
def test_w8_tt_linear_batched_a_matches_ref_twin(group):
    s, k, n, r = 5, 256, 130, 6
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (s, k), jnp.float32)
    w = jax.random.normal(ks[1], (k, n), jnp.float32) / np.sqrt(k)
    a = jax.random.normal(ks[2], (s, k, r), jnp.float32) / np.sqrt(k)
    b = jax.random.normal(ks[3], (r, n), jnp.float32) / np.sqrt(r)
    wq, scale = quant.quantize_int8(w, group_size=group)
    y = ops.tt_linear_batched_a_q(x, wq, scale, a, b, alpha=0.7,
                                  backend="pallas", interpret=True)
    want = ref.tt_linear_batched_a_q_ref(x, wq, scale, a, b, alpha=0.7)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    # decode layout (S, 1, K) round-trips
    y3 = ops.tt_linear_batched_a_q(x[:, None], wq, scale, a, b, alpha=0.7,
                                   backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(y3[:, 0]), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_w8_zero_adapter_equals_quantized_base_matmul():
    x = jax.random.normal(KEY, (128, 256), jnp.float32)
    w = jax.random.normal(KEY, (256, 128), jnp.float32) / 16
    wq, scale = quant.quantize_int8(w)
    a = jnp.zeros((256, 16))
    b = jax.random.normal(KEY, (16, 128), jnp.float32)
    y = ops.tt_linear_q(x, wq, scale, a, b, alpha=4.0, backend="pallas",
                        interpret=True)
    want = x @ quant.dequantize_int8(wq, scale)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4)


# ---------------------------------------------------------------------------
# full-model forward over a quantized base: pallas vs ref, adapter kinds
# ---------------------------------------------------------------------------


def _setup(kind="metatt", variant="4d", num_tasks=0, rank=4, scale=0.5):
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"], adapter_kind=kind,
                    adapter_variant=variant, num_tasks=num_tasks,
                    adapter_rank=rank)
    spec = M.build_adapter_spec(run)
    params = M.init_params(cfg, spec, KEY)
    if kind == "metatt":
        params["adapter"] = {"cores": ttlib.random_tt(
            KEY, spec.cfg.mode_sizes, rank, scale=scale)}
    else:
        params["adapter"] = jax.tree_util.tree_map(
            lambda a: scale * jax.random.normal(KEY, a.shape, a.dtype),
            params["adapter"])
    return cfg, spec, params


@pytest.mark.parametrize("kind,variant,num_tasks", [
    ("metatt", "4d", 0),
    ("metatt", "4+1d", 2),
    ("lora", "4d", 0),
    ("vera", "4d", 0),
    ("lotr", "4d", 0),
])
def test_w8_forward_parity_across_adapter_kinds(kind, variant, num_tasks):
    """Quantized base, fused w8a16 kernels vs the ref dequant path — the
    SAME int8 numbers through both execution paths, so the comparison is
    tight (no quantization error in the diff)."""
    cfg, spec, params = _setup(kind, variant, num_tasks)
    qbase = quant.quantize_base(params["base"])
    bc, pl = peft_api.adapter_factors(spec, params["adapter"],
                                      params["frozen"])
    tokens = jax.random.randint(KEY, (2, 9), 0, cfg.vocab_size)
    task = jnp.int32(1) if variant == "4+1d" else None
    out_p = T.forward(qbase, cfg, spec, bc, pl, tokens, task=task,
                      policy=PALLAS)
    out_r = T.forward(qbase, cfg, spec, bc, pl, tokens, task=task,
                      policy=REF)
    np.testing.assert_allclose(out_p.logits, out_r.logits,
                               atol=5e-5, rtol=5e-5)


# ---------------------------------------------------------------------------
# int8 paged KV cache
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("c,heads,layout", [
    pytest.param(1, (4, 4), "mixed", id="1-heads0"),
    pytest.param(4, (4, 2), "mixed", id="4-heads1"),
    pytest.param(8, (4, 4), "ragged_table", id="ragged_table"),
    pytest.param(1, (4, 2), "block_edge", id="block_edge"),
    pytest.param(8, (4, 4), "chunk_cross", id="chunk_cross"),
    pytest.param(8, (4, 2), "idle_slot", id="idle_slot"),
    pytest.param(4, (8, 4), "split_heads", id="split_heads"),
    pytest.param(4, (4, 4), "spec_verify", id="spec_verify"),
])
def test_int8_kv_paged_attention_kernel_matches_ref(c, heads, layout,
                                                    paged_layout):
    """Per-cell-scale int8 pools through the ops seam: kernel in-register
    dequant vs explicit reference dequant, incl. GQA + sentinel pages and
    the layouts of tests/conftest.py's PAGED_LAYOUTS."""
    h, kv = heads
    page, tables, pos, n = paged_layout(layout)
    b, d = tables.shape[0], 16
    ks = jax.random.split(jax.random.PRNGKey(c), 3)
    q = jax.random.normal(ks[0], (b, c, h, d), jnp.float32)
    kc = jax.random.normal(ks[1], (n, kv, page, d), jnp.float32)
    vc = jax.random.normal(ks[2], (n, kv, page, d), jnp.float32)
    kq, k_s = quant.quantize_kv(kc)
    vq, v_s = quant.quantize_kv(vc)
    k_s, v_s = k_s[:, :, None], v_s[:, :, None]   # (N, KV, 1, page) pools
    want = ops.paged_decode_attention(q, kq, vq, tables, pos, k_scale=k_s,
                                      v_scale=v_s, backend="ref")
    got = ops.paged_decode_attention(q, kq, vq, tables, pos, k_scale=k_s,
                                     v_scale=v_s, backend="pallas",
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # int8 attention tracks fp attention at quantization resolution
    fp = ops.paged_decode_attention(q, kc, vc, tables, pos, backend="ref")
    assert float(jnp.max(jnp.abs(want - fp))) < 0.1


def test_quantize_kv_zero_rows_roundtrip_to_zero():
    x = jnp.zeros((3, 4, 8))
    q, s = quant.quantize_kv(x)
    assert bool(jnp.all(q == 0)) and bool(jnp.all(s > 0))


# ---------------------------------------------------------------------------
# engine: int8 serving path
# ---------------------------------------------------------------------------


def _engine_setup():
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    adapter_kind="metatt", adapter_variant="4+1d",
                    num_tasks=2, adapter_rank=4)
    spec = M.build_adapter_spec(run)
    params = M.init_params(cfg, spec, KEY)
    params["adapter"] = {"cores": ttlib.random_tt(
        KEY, spec.cfg.mode_sizes, 4, scale=0.8)}
    rt = AdapterRuntime.build("lora", params["base"], spec,
                              params["adapter"], params["frozen"])
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (4 + i,), 0,
                                  cfg.vocab_size) for i in range(5)]
    reqs = [Request(p, 6, task=i % 2) for i, p in enumerate(prompts)]
    return cfg, rt, reqs


def _serve(cfg, rt, reqs, qc, kernels=None):
    sv = ServeConfig(max_batch=2, cache_len=32, out_cap=8, page_size=8,
                     prefill_chunk=4, quant=qc)
    eng = Engine(cfg, rt, serve=sv, kernels=kernels)
    return [o.tolist() for o in eng.generate(reqs)], eng


def _match_fraction(a, b):
    tot = sum(len(x) for x in a)
    same = sum(int(p == q) for x, y in zip(a, b) for p, q in zip(x, y))
    return same / tot


def test_int8_engine_greedy_parity_and_kv_bytes():
    cfg, rt, reqs = _engine_setup()
    fp, fp_eng = _serve(cfg, rt, reqs, QuantConfig())
    for qc in (QuantConfig(kv="int8"),
               QuantConfig(weights="int8"),
               QuantConfig(weights="int8", kv="int8"),
               QuantConfig(weights="int8", kv="int8", group_size=128)):
        out, eng = _serve(cfg, rt, reqs, qc)
        assert _match_fraction(out, fp) >= TOKEN_MATCH_MIN, qc
        st = eng.last_stats
        assert st.weights_dtype == ("int8" if qc.weights == "int8"
                                    else "fp")
        assert st.kv_dtype == ("int8" if qc.kv == "int8" else "fp")
        if qc.kv == "int8":
            # same num_blocks budget, same blocks peak -> fewer bytes
            assert st.num_blocks == fp_eng.last_stats.num_blocks
            assert st.block_bytes < fp_eng.last_stats.block_bytes
            assert st.kv_bytes_peak < fp_eng.last_stats.kv_bytes_peak


def test_int8_engine_pallas_interpret_matches_ref_backend():
    """Same quantized numbers through the fused w8a16 + int8 paged-
    attention kernels and through the ref path: token-IDENTICAL."""
    cfg, rt, reqs = _engine_setup()
    qc = QuantConfig(weights="int8", kv="int8")
    ref_out, _ = _serve(cfg, rt, reqs, qc)
    pal_out, _ = _serve(cfg, rt, reqs, qc,
                        kernels=KernelConfig(backend="pallas",
                                             interpret=True))
    assert pal_out == ref_out


def test_int8_engine_warm_prefix_cache_token_identical():
    """Prefix cache + COW round-trip THROUGH the quantized representation:
    a warm rerun reuses int8 blocks + scale pools and must reproduce the
    cold run exactly."""
    cfg, rt, reqs = _engine_setup()
    qc = QuantConfig(weights="int8", kv="int8")
    sv = ServeConfig(max_batch=2, cache_len=32, out_cap=8, page_size=8,
                     prefill_chunk=4, quant=qc)
    eng = Engine(cfg, rt, serve=sv)
    cold = [o.tolist() for o in eng.generate(reqs)]
    warm = [o.tolist() for o in eng.generate(reqs)]
    assert warm == cold
    assert eng.last_stats.prefix_hit_rate > 0
    assert eng.last_stats.cow_copies > 0


def test_int8_kv_requires_paged_mode():
    cfg, rt, reqs = _engine_setup()
    with pytest.raises(ValueError):
        Engine(cfg, rt, serve=ServeConfig(
            max_batch=2, cache_len=32, out_cap=8, cache_mode="dense",
            quant=QuantConfig(kv="int8")))
    # weights quant via KernelConfig.quant merges in (dense mode is fine
    # for weights — only the KV side needs the paged layout)
    eng = Engine(cfg, rt, serve=ServeConfig(
        max_batch=2, cache_len=32, out_cap=8, cache_mode="dense"),
        kernels=KernelConfig(quant=QuantConfig(weights="int8")))
    assert eng.quant.weights == "int8"
    assert quant.is_quantized(eng.base_weights["blocks"][0]["mixer"]["wq"])


# ---------------------------------------------------------------------------
# quantized-base snapshot (checkpoint/ckpt.py)
# ---------------------------------------------------------------------------


def test_quantized_base_snapshot_roundtrip(tmp_path):
    cfg = registry.get_smoke_config("stablelm-1.6b")
    base = T.init_base_params(cfg, KEY)
    qbase = quant.quantize_base(base, group_size=0)
    path = ckpt_lib.save_base_snapshot(str(tmp_path / "qbase"), qbase)
    template = jax.tree_util.tree_map(jnp.zeros_like, qbase)
    loaded = ckpt_lib.load_base_snapshot(path, template)
    for got, want in zip(jax.tree_util.tree_leaves(loaded),
                         jax.tree_util.tree_leaves(qbase)):
        assert got.dtype == want.dtype          # int8 stays int8
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_engine_snapshot_roundtrip_same_tokens(tmp_path):
    cfg, rt, reqs = _engine_setup()
    qc = QuantConfig(weights="int8", kv="int8")
    out1, eng1 = _serve(cfg, rt, reqs, qc)
    path = eng1.save_base_snapshot(str(tmp_path / "snap"))
    _, eng2 = _serve(cfg, rt, reqs, qc)
    eng2.load_base_snapshot(path)
    out2 = [o.tolist() for o in eng2.generate(reqs)]
    assert out2 == out1
