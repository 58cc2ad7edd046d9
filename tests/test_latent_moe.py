"""The DeepSeek-V3 / Kimi K2 block at tiny sizes on the CPU, against plain
references written here: latent attention with YaRN, the flash kernels
with a v head dim of their own and a given scale, sigmoid routing with the
correction bias, the dropless held-expert layer and its grouped matmul,
the leading dense layer and the untied head, and the serving engine's
refusal."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as registry
from repro.config.base import SHAPES, RunConfig
from repro.kernels import dispatch, ops
from repro.models import attention as attn_lib
from repro.models import model as model_lib
from repro.models import moe as moe_lib
from repro.models import transformer
from repro.models.layers import NO_ADAPTER, AdapterCtx

KEY = jax.random.PRNGKey(3)
HI = jax.lax.Precision.HIGHEST
KIMI = registry.get_config("kimi-k2-1t-a32b")


def _smoke(**kw):
    return dataclasses.replace(registry.get_smoke_config("kimi-k2-1t-a32b"),
                               **kw).validate()


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _yarn_hf(d, base, factor, orig, beta_fast, beta_slow):
    """YaRN's frequencies in the published code's own steps."""
    def corr_dim(rot):
        return (d * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    mask = 1 - ramp
    extra = 1.0 / base ** (np.arange(0, d, 2) / d)
    inter = 1.0 / (factor * base ** (np.arange(0, d, 2) / d))
    return inter * (1 - mask) + extra * mask, (low, high)


def test_yarn_values_of_kimi_k2():
    y = KIMI.rope_scaling
    want, (low, high) = _yarn_hf(64, 50000.0, 32.0, 4096, 1.0, 1.0)
    assert (low, high) == (19, 20)
    np.testing.assert_allclose(attn_lib.yarn_inv_freq(64, 50000.0, y), want,
                               rtol=1e-6)
    m = 0.1 * math.log(32) + 1
    assert m * m == pytest.approx(1.8133, abs=1e-4)
    assert attn_lib.mla_softmax_scale(KIMI) == pytest.approx(
        192 ** -0.5 * m * m)


def _mla_plain(x, w, cfg):
    """Latent attention written out in f32."""
    b, t, _ = x.shape
    h, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dv, r, eps = cfg.v_head_dim, cfg.kv_lora_rank, cfg.norm_eps
    y = cfg.rope_scaling
    inv, _ = _yarn_hf(dr, cfg.rope_theta, y.factor, y.original_max_position,
                      y.beta_fast, y.beta_slow)
    ang = np.arange(t)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]

    def rope(a):
        a1, a2 = a[..., :dr // 2], a[..., dr // 2:]
        return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin],
                               -1)

    mm = lambda a, m: jnp.matmul(a, m, precision=HI)    # noqa: E731
    q = mm(_rms(mm(x, w["wq_a"]), eps) * (1 + w["q_norm"]), w["wq_b"])
    q = q.reshape(b, t, h, dn + dr)
    ckv = mm(x, w["wkv_a"])
    kv = mm(_rms(ckv[..., :r], eps) * (1 + w["kv_norm"]), w["wkv_b"])
    kv = kv.reshape(b, t, h, dn + dv)
    k_pe = rope(ckv[..., r:][:, :, None])
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe, (b, t, h, dr))], -1)
    scale = (dn + dr) ** -0.5 * (0.1 * math.log(y.factor) + 1) ** 2
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) * scale
    s = jnp.where(np.tril(np.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), kv[..., dn:],
                   precision=HI)
    return mm(o.reshape(b, t, h * dv), w["wo"])


@pytest.mark.parametrize("kernels", ["ref", "pallas"])
def test_mla_forward_with_yarn_matches_plain(kernels):
    cfg = _smoke()
    w = jax.tree_util.tree_map(lambda a: a[0], transformer._mla_init(
        cfg, KEY, 1, jnp.float32))
    w["q_norm"] = 0.1 * jax.random.normal(KEY, w["q_norm"].shape)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 40, cfg.d_model))
    pol = (dispatch.PALLAS_INTERPRET if kernels == "pallas" else None)
    got = attn_lib.mla_attention(x, w, AdapterCtx(NO_ADAPTER.spec, {}, None,
                                                  None, pol), cfg)
    np.testing.assert_allclose(got, _mla_plain(x, w, cfg), atol=2e-4,
                               rtol=2e-4)


@pytest.mark.parametrize("dqk,dv,scale", [(192, 128, 0.1346), (48, 32, 0.3),
                                          (64, 64, None)])
def test_flash_forward_backward_with_own_v_dim_and_scale(dqk, dv, scale):
    """Interpret-mode kernels against autodiff of plain attention; the
    last case is today's equal-width call with the default scale."""
    b, t, h = 1, 256, 2
    ks = jax.random.split(jax.random.fold_in(KEY, dqk), 4)
    q = jax.random.normal(ks[0], (b, t, h, dqk))
    k = jax.random.normal(ks[1], (b, t, h, dqk))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    sc = dqk ** -0.5 if scale is None else scale

    def plain(q, k, v):
        s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) * sc
        s = jnp.where(np.tril(np.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    def flash(q, k, v):
        return dispatch.flash_attention(q, k, v, causal=True, scale=scale,
                                        policy=dispatch.PALLAS_INTERPRET)

    g = jax.random.normal(ks[3], (b, t, h, dv))
    np.testing.assert_allclose(flash(q, k, v), plain(q, k, v), atol=2e-4,
                               rtol=2e-4)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * g), (0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        assert a.shape == w.shape
        np.testing.assert_allclose(a, w, atol=5e-4, rtol=5e-4)


def test_sigmoid_router_chooses_by_biased_score_weights_unbiased():
    x = jax.random.normal(KEY, (16, 32))
    wr = jax.random.normal(jax.random.fold_in(KEY, 1), (32, 12)) * 0.3
    bias = jnp.zeros((12,)).at[5].set(10.0).at[7].set(-10.0)
    top_w, top_i = moe_lib.sigmoid_router(x, wr, bias, 3, 2.827)
    s = jax.nn.sigmoid(jnp.matmul(x, wr, precision=HI))
    top_i, s = np.asarray(top_i), np.asarray(s)
    assert (top_i == 5).any(1).all() and not (top_i == 7).any()
    want_i = np.argsort(-(s + np.asarray(bias)), 1)[:, :3]
    assert (np.sort(top_i, 1) == np.sort(want_i, 1)).all()
    chosen = np.take_along_axis(s, top_i, 1)
    np.testing.assert_allclose(
        top_w, chosen / chosen.sum(1, keepdims=True) * 2.827, rtol=1e-5)


def _layer_w(cfg, key):
    w = jax.tree_util.tree_map(lambda a: a[0], transformer._moe_init(
        cfg, key, 1, jnp.float32))
    w["router_bias"] = 0.1 * jax.random.normal(jax.random.fold_in(key, 9),
                                               (cfg.num_experts,))
    return w


def _uncut_plain(x, w, cfg):
    """The whole expert layer, every expert, written out."""
    s = jax.nn.sigmoid(jnp.matmul(x, w["router"], precision=HI))
    _, idx = jax.lax.top_k(s + w["router_bias"], cfg.experts_per_token)
    ch = jnp.take_along_axis(s, idx, 1)
    wts = ch / (ch.sum(1, keepdims=True) + 1e-20) * cfg.moe_routed_scale
    out = 0.0
    for e in range(cfg.num_experts):
        g = jnp.sum(jnp.where(idx == e, wts, 0.0), 1)[:, None]
        f = jax.nn.silu(x @ w["e_wg"][e]) * (x @ w["e_wu"][e])
        out = out + g * (f @ w["e_wd"][e])
    sh = (jax.nn.silu(x @ w["s_wg"]) * (x @ w["s_wu"])) @ w["s_wd"]
    return out, sh


@pytest.mark.parametrize("kernels", ["ref", "pallas"])
def test_held_shares_add_up_to_the_uncut_layer(kernels):
    """Across the four shares of 2 experts, the held parts, plus the shared
    expert counted once, equal the uncut layer."""
    full = _smoke(num_experts=8, experts_per_token=3)
    w = _layer_w(full, KEY)
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 48, full.d_model))
    pol = dispatch.PALLAS_INTERPRET if kernels == "pallas" else None
    ctx = AdapterCtx(NO_ADAPTER.spec, {}, None, None, pol)
    routed, shared = _uncut_plain(x[0], w, full)
    total, pairs = 0.0, 0
    for lo in range(0, 8, 2):
        cfg = dataclasses.replace(full, experts_held=2, expert_offset=lo)
        ws = dict(w, **{k: w[k][lo:lo + 2] for k in ("e_wg", "e_wu",
                                                     "e_wd")})
        y, aux = moe_lib.moe_ffn(x, ws, ctx, cfg)
        total = total + y[0]
        pairs += int(aux["moe_pairs_here"])
    # each share's layer adds the shared expert, which every chip computes
    # alike: counted once, the sum is the uncut layer
    np.testing.assert_allclose(total - 3 * shared, routed + shared,
                               atol=1e-4, rtol=1e-4)
    assert pairs == 48 * 3


def test_dropless_keeps_every_pair_when_all_route_to_one_expert():
    """A bias that sends every token to held expert 1: the dropless layer
    computes all of them, where capacity dispatch would drop most."""
    full = _smoke(num_experts=8, experts_per_token=2)
    cfg = dataclasses.replace(full, experts_held=2, expert_offset=0)
    w = _layer_w(full, KEY)
    w["router_bias"] = jnp.zeros((8,)).at[1].set(50.0)
    n = 300
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (1, n, cfg.d_model))
    routed, _ = _uncut_plain(x[0], w, full)
    # the held share of the uncut layer: experts 0 and 1
    s = jax.nn.sigmoid(x[0] @ w["router"])
    _, idx = jax.lax.top_k(s + w["router_bias"], 2)
    assert (np.asarray(idx) == 1).any(1).all()
    ch = jnp.take_along_axis(s, idx, 1)
    wts = ch / ch.sum(1, keepdims=True) * cfg.moe_routed_scale
    want = 0.0
    for e in (0, 1):
        g = jnp.sum(jnp.where(idx == e, wts, 0.0), 1)[:, None]
        want = want + g * ((jax.nn.silu(x[0] @ w["e_wg"][e])
                            * (x[0] @ w["e_wu"][e])) @ w["e_wd"][e])
    ws = dict(w, **{k: w[k][:2] for k in ("e_wg", "e_wu", "e_wd")})
    ctx = AdapterCtx(NO_ADAPTER.spec, {}, None, None,
                     dispatch.PALLAS_INTERPRET)
    y, aux = moe_lib.moe_ffn(x, dict(ws, s_wg=0 * ws["s_wg"]), ctx, cfg)
    np.testing.assert_allclose(y[0], want, atol=1e-4, rtol=1e-4)
    assert int(aux["moe_max_expert_rows"]) == n
    cap = int(full.moe_capacity_factor * n * 2 / full.num_experts)
    assert cap < n          # a capacity layer keeps at most cap of the n


def test_grouped_matmul_skips_unused_tiles_and_transposes():
    tm = 128
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (5 * tm, 200))
    w = jax.random.normal(ks[1], (3, 200, 136))
    tg = jnp.asarray([2, 0, 0, 1, 1], jnp.int32)
    nu = jnp.asarray([3], jnp.int32)
    got = ops.grouped_matmul(x, w, tg, nu, tm=tm, backend="pallas",
                             interpret=True)
    want = np.zeros((5 * tm, 136), np.float32)
    for i, e in enumerate([2, 0, 0]):
        want[i * tm:(i + 1) * tm] = np.asarray(x[i * tm:(i + 1) * tm]
                                               @ w[e])
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-4)
    g = jax.random.normal(ks[2], got.shape)
    dx = jax.grad(lambda a: jnp.sum(dispatch.grouped_matmul(
        a, w, tg, nu, tm=tm, policy=dispatch.PALLAS_INTERPRET) * g))(x)
    dx_ref = jax.grad(lambda a: jnp.sum(ops.grouped_matmul(
        a, w, tg, nu, tm=tm, backend="ref") * g))(x)
    np.testing.assert_allclose(dx, dx_ref, atol=1e-3, rtol=1e-4)
    assert float(jnp.abs(dx[3 * tm:]).max()) == 0.0


def test_dense_first_layer_untied_head_and_counters_in_the_train_step():
    cfg = _smoke(experts_held=4, expert_offset=2)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    adapter_kind="metatt", adapter_rank=4)
    spec = model_lib.build_adapter_spec(run)
    assert spec.cfg.matrix_types == ("mla_q_b", "mla_kv_b")
    assert spec.cfg.d_in == (32, 16) and spec.cfg.d_out == (96, 128)
    p = model_lib.init_params(cfg, spec, KEY)
    base = p["base"]
    assert base["dense_blocks"][0]["ffn"]["wg"].shape == (1, 64, 96)
    assert base["blocks"][0]["ffn"]["e_wg"].shape == (2, 4, 64, 32)
    assert base["lm_head"].shape == (128, 64)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    loss, m = model_lib.loss_fn(p["adapter"], base, p["frozen"],
                                {"tokens": tokens}, cfg, spec)
    assert 0 < m["moe_pairs_here"] <= 2 * 32 * 2
    assert m["moe_max_expert_rows"] <= m["moe_pairs_here"]
    # the readout is the untied head: changing it changes the loss
    b2 = dict(base, lm_head=base["lm_head"] * 2.0)
    loss2, _ = model_lib.loss_fn(p["adapter"], b2, p["frozen"],
                                 {"tokens": tokens}, cfg, spec)
    assert float(loss2) != float(loss)
    assert float(loss) == pytest.approx(float(m["ce"]))


def test_engine_refuses_latent_attention_and_held_experts():
    from repro.core import tt as ttlib
    from repro.serving import AdapterRuntime, Engine
    cfg = _smoke()
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    adapter_kind="metatt", adapter_rank=4)
    spec = model_lib.build_adapter_spec(run)
    p = model_lib.init_params(cfg, spec, KEY)
    p["adapter"] = {"cores": ttlib.random_tt(KEY, spec.cfg.mode_sizes, 4)}
    rt = AdapterRuntime.build("live", p["base"], spec, p["adapter"],
                              p["frozen"])
    with pytest.raises(NotImplementedError,
                       match="latent paged cache.*expert task routing"):
        Engine(cfg, rt)


def _moe_down_ctx(cfg, kernels):
    """A MetaTT (4+E)D adapter with random cores on the smoke config, which
    adapts ``moe_down``, as the context of expert layer 1."""
    from repro.core import tt as ttlib
    from repro.peft import api as peft_api
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    adapter_kind="metatt", adapter_variant="4+ed",
                    adapter_rank=4)
    spec = model_lib.build_adapter_spec(run)
    assert spec.adapts("moe_down")
    cores = ttlib.random_tt(jax.random.fold_in(KEY, 5), spec.cfg.mode_sizes,
                            4)
    bc, per_layer = peft_api.adapter_factors(spec, {"cores": cores}, {})
    ly = jax.tree_util.tree_map(lambda a: a[1], per_layer)
    pol = dispatch.PALLAS_INTERPRET if kernels == "pallas" else None
    return AdapterCtx(spec, bc, ly, None, pol)


@pytest.mark.parametrize("kernels", ["ref", "pallas"])
def test_dropless_applies_the_expert_indexed_moe_down_delta(kernels):
    """Under the (4+E)D adapter the dropless layer adds the moe_down delta
    with each expert's own middle core: it equals the capacity layer with
    room for every pair, under the same sigmoid router, and the delta
    moves it."""
    full = _smoke(num_experts=8, experts_per_token=3)
    cfg = dataclasses.replace(full, experts_held=4, expert_offset=2)
    w = _layer_w(full, KEY)
    w = dict(w, **{k: w[k][2:6] for k in ("e_wg", "e_wu", "e_wd")})
    x = jax.random.normal(jax.random.fold_in(KEY, 6), (1, 40, full.d_model))
    ctx = _moe_down_ctx(cfg, kernels)
    y, aux = moe_lib.moe_ffn(x, w, ctx, cfg)
    cap = dataclasses.replace(cfg, moe_dispatch="capacity",
                              moe_capacity_factor=64.0)
    y_cap, aux_cap = moe_lib.moe_ffn(x, w, ctx, cap)
    np.testing.assert_allclose(y, y_cap, atol=1e-4, rtol=1e-4)
    assert float(aux["moe_pairs_here"]) == float(aux_cap["moe_pairs_here"])
    plain = AdapterCtx(NO_ADAPTER.spec, {}, None, None, ctx.policy)
    y0, _ = moe_lib.moe_ffn(x, w, plain, cfg)
    assert float(jnp.abs(y - y0).max()) > 1e-3


def test_moe_down_delta_trains_under_the_dropless_layer():
    """The (4+E)D cores of moe_down take a gradient through the dropless
    layer: each held expert's middle core slice moves, the others' do
    not."""
    from repro.core import tt as ttlib
    cfg = _smoke(experts_held=4, expert_offset=2)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    adapter_kind="metatt", adapter_variant="4+ed",
                    adapter_rank=4)
    spec = model_lib.build_adapter_spec(run)
    p = model_lib.init_params(cfg, spec, KEY)
    cores = ttlib.random_tt(jax.random.fold_in(KEY, 7), spec.cfg.mode_sizes,
                            4)
    tokens = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    g = jax.grad(lambda c: model_lib.loss_fn(
        {"cores": c}, p["base"], p["frozen"], {"tokens": tokens}, cfg,
        spec)[0])(cores)
    # the expert core: its slices of held experts move, the others do not
    e_axis = spec.cfg.mode_sizes.index(cfg.num_experts)
    ge = np.moveaxis(np.asarray(g[e_axis]), 1, 0).reshape(cfg.num_experts,
                                                          -1)
    norms = np.linalg.norm(ge, axis=1)
    held = np.arange(cfg.num_experts)
    held = (held >= 2) & (held < 6)
    assert (norms[held] > 0).all()
    assert (norms[~held & (np.arange(cfg.num_experts) > 0)] == 0).all()


@pytest.mark.parametrize("flood", [False, True])
def test_dropless_usual_and_full_buffers(flood):
    """The rows buffer of a few even shares serves usual routing; a bias
    that sends every token to both held experts overflows it, and the
    chunk takes the buffer of every pair. Both equal the held share of
    the plain layer."""
    full = _smoke(num_experts=16, experts_per_token=2)
    cfg = dataclasses.replace(full, experts_held=2, expert_offset=0)
    n = 300
    usual = moe_lib._buffer_rows(
        moe_lib.EVEN_SHARES * -(-n * 2 * 2 // 16), 2)
    assert usual < moe_lib._buffer_rows(n * 2, 2)
    w = _layer_w(full, KEY)
    if flood:
        w["router_bias"] = jnp.zeros((16,)).at[:2].set(50.0)
    x = jax.random.normal(jax.random.fold_in(KEY, 8), (n, cfg.d_model))
    s = jax.nn.sigmoid(jnp.matmul(x, w["router"], precision=HI))
    _, idx = jax.lax.top_k(s + w["router_bias"], 2)
    ch = jnp.take_along_axis(s, idx, 1)
    wts = ch / ch.sum(1, keepdims=True) * cfg.moe_routed_scale
    want = 0.0
    for e in (0, 1):
        g = jnp.sum(jnp.where(idx == e, wts, 0.0), 1)[:, None]
        want = want + g * ((jax.nn.silu(x @ w["e_wg"][e])
                            * (x @ w["e_wu"][e])) @ w["e_wd"][e])
    ws = dict(w, **{k: w[k][:2] for k in ("e_wg", "e_wu", "e_wd")})
    ctx = AdapterCtx(NO_ADAPTER.spec, {}, None, None,
                     dispatch.PALLAS_INTERPRET)
    y, aux = moe_lib.moe_ffn(x[None], dict(ws, s_wg=0 * ws["s_wg"]), ctx,
                             cfg)
    np.testing.assert_allclose(y[0], want, atol=1e-4, rtol=1e-4)
    rows = -(-np.bincount(np.asarray(idx).ravel(), minlength=16)[:2]
             // moe_lib.GMM_ROWS).sum() * moe_lib.GMM_ROWS
    assert (rows > usual) == flood
    assert int(aux["moe_pairs_here"]) == (2 * n if flood else
                                          int(np.sum(np.asarray(idx) < 2)))


def test_train_step_returns_each_expert_layers_routing():
    """With ``moe_record_routing`` the step returns every expert layer's
    chosen experts (layers, tokens, k), joined across microbatches in the
    batch's token order, and the loss does not change."""
    from repro.config.base import OptimizerConfig, TrainConfig
    from repro.train import train_step as ts
    cfg = _smoke(experts_held=4, expert_offset=2, moe_record_routing=True)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    adapter_kind="metatt", adapter_rank=4)
    spec = model_lib.build_adapter_spec(run)
    p = model_lib.init_params(cfg, spec, KEY)
    tokens = jax.random.randint(KEY, (4, 16), 0, cfg.vocab_size)
    out = {}
    for nmb in (1, 2):
        step = ts.make_train_step(cfg, spec, OptimizerConfig(),
                                  TrainConfig(microbatch=nmb), 10,
                                  donate=False)
        state = ts.init_train_state(p["adapter"], ts.GradCompressor("none"))
        _, m = step(state, p["base"], p["frozen"], {"tokens": tokens})
        out[nmb] = m
    r = np.asarray(out[1]["moe_routing"])
    assert r.shape == (cfg.num_layers - 1, 4 * 16, cfg.experts_per_token)
    np.testing.assert_array_equal(r, np.asarray(out[2]["moe_routing"]))
    plain = dataclasses.replace(cfg, moe_record_routing=False)
    loss, m = model_lib.loss_fn(p["adapter"], p["base"], p["frozen"],
                                {"tokens": tokens}, plain, spec)
    assert "moe_routing" not in m
    assert float(out[1]["ce"]) == pytest.approx(float(m["ce"]), rel=1e-6)
