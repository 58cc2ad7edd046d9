"""Speculative multi-token decode with the rank-truncated TT self-drafter
(serving/speculative.py, DESIGN.md §10) — acceptance criteria:

  * GREEDY TOKEN IDENTITY: speculative greedy decode emits bit-identical
    tokens to the non-speculative engine for EVERY drafter (rank
    truncation and layer stride included — the accept rule only commits
    verifier-argmax prefixes), across paged/dense caches, fp/int8 KV,
    ref/pallas-interpret backends and mesh(1,1)/tp4 sharding,
  * SINGLE TRACE: draft + verify + accept all live inside the one jitted
    while_loop — ``decode_traces == 1`` with speculation on,
  * STATS: draft/accept counters land on EngineStats; a full-rank
    unstrided drafter (drafter == target) accepts everything,
  * DISTRIBUTION: the Leviathan rejection-sampling accept preserves the
    output distribution (frequency test on a small categorical case),
  * BLOCK ACCOUNTING: the drafter's parallel pools ride the SAME block
    tables — no extra allocations, nothing leaked after generate.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as registry
from repro.config.base import (KernelConfig, QuantConfig, RunConfig,
                               SHAPES, ServeConfig, SpecConfig)
from repro.core import tt as ttlib
from repro.models import model as M
from repro.serving import (AdapterRuntime, Engine, Request,
                           SamplingConfig)
from repro.serving import speculative as spec_lib

KEY = jax.random.PRNGKey(0)
PALLAS = KernelConfig(backend="pallas", interpret=True)

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 (fake) devices: run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4 "
           "(scripts/ci.sh spec-parity job)")


def _setup(variant="4+1d", num_tasks=3):
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    adapter_kind="metatt", adapter_variant=variant,
                    num_tasks=num_tasks, adapter_rank=4)
    spec = M.build_adapter_spec(run)
    params = M.init_params(cfg, spec, KEY)
    params["adapter"] = {"cores": ttlib.random_tt(
        KEY, spec.cfg.mode_sizes, 4, scale=0.8)}
    return cfg, spec, params


def _mixed_requests(cfg, n=4, tasks=3):
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (4 + i,), 0,
                                  cfg.vocab_size) for i in range(n)]
    return [Request(p, 5 + (i % 3), task=i % tasks)
            for i, p in enumerate(prompts)]


def _serve(cfg, rt, reqs, *, spec=SpecConfig(), mode="paged",
           quant=QuantConfig(), kernels=None, sampling=SamplingConfig(),
           **kw):
    base = dict(max_batch=2, cache_len=32, out_cap=8, cache_mode=mode,
                page_size=8, prefill_chunk=4, quant=quant, spec=spec)
    base.update(kw)
    eng = Engine(cfg, rt, serve=ServeConfig(**base), kernels=kernels,
                 sampling=sampling)
    return [o.tolist() for o in eng.generate(reqs)], eng


# ---------------------------------------------------------------------------
# greedy token identity across the serving matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["paged", "dense"])
def test_spec_greedy_token_identical(mode):
    """Rank-truncated drafter, both cache modes: the committed stream is
    the non-speculative stream, with draft/accept stats populated."""
    cfg, _, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], M.build_adapter_spec(
        RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                  adapter_kind="metatt", adapter_variant="4+1d",
                  num_tasks=3, adapter_rank=4)), params["adapter"],
        params["frozen"])
    reqs = _mixed_requests(cfg)
    base, _ = _serve(cfg, rt, reqs, mode=mode)
    out, eng = _serve(cfg, rt, reqs, mode=mode,
                      spec=SpecConfig(spec_k=3, draft_rank=2))
    assert out == base
    st = eng.last_stats
    assert st.spec_k == 3
    assert st.decode_steps > 0
    assert st.draft_tokens > 0
    assert 0.0 <= st.acceptance_rate <= 1.0


def test_spec_layer_stride_greedy_token_identical():
    """A layer-strided drafter is a WORSE approximation but greedy
    identity cannot depend on drafter quality."""
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    reqs = _mixed_requests(cfg)
    base, _ = _serve(cfg, rt, reqs)
    out, _ = _serve(cfg, rt, reqs, spec=SpecConfig(
        spec_k=2, draft_rank=2, draft_layer_stride=2))
    assert out == base


@pytest.mark.parametrize("mode", ["lora", "merged"])
def test_spec_greedy_across_runtimes(mode):
    cfg, spec, params = _setup()
    kw = dict(model_cfg=cfg, task=1) if mode == "merged" else {}
    rt = AdapterRuntime.build(mode, params["base"], spec,
                              params["adapter"], params["frozen"], **kw)
    reqs = _mixed_requests(cfg)
    if mode == "merged":
        reqs = [r for r in reqs if r.task == 1]
    base, _ = _serve(cfg, rt, reqs)
    out, _ = _serve(cfg, rt, reqs, spec=SpecConfig(spec_k=3, draft_rank=2))
    assert out == base


def test_spec_greedy_int8_kv_and_pallas_interpret():
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    reqs = _mixed_requests(cfg)
    q = QuantConfig(kv="int8")
    base, _ = _serve(cfg, rt, reqs, quant=q, kernels=PALLAS)
    out, _ = _serve(cfg, rt, reqs, quant=q, kernels=PALLAS,
                    spec=SpecConfig(spec_k=3, draft_rank=2))
    assert out == base


def test_spec_greedy_mesh_1x1():
    """The sharded step graphs (shard_map specs extended with drafter
    weights + dcaches) stay token-identical on a trivial mesh — runs in
    the tier-1 single-device suite."""
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    reqs = _mixed_requests(cfg)
    base, _ = _serve(cfg, rt, reqs)
    out, _ = _serve(cfg, rt, reqs, mesh_shape=(1, 1),
                    spec=SpecConfig(spec_k=3, draft_rank=2))
    assert out == base


@needs4
@pytest.mark.parametrize("mode", ["paged", "dense"])
def test_spec_greedy_tp4(mode):
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    reqs = _mixed_requests(cfg)
    base, _ = _serve(cfg, rt, reqs, mode=mode)
    out, _ = _serve(cfg, rt, reqs, mode=mode, mesh_shape=(1, 4),
                    spec=SpecConfig(spec_k=3, draft_rank=2))
    assert out == base


# ---------------------------------------------------------------------------
# single trace, full-rank acceptance, warm prefix reuse
# ---------------------------------------------------------------------------


def test_spec_single_decode_trace():
    """Draft, verify and accept all live inside the ONE jitted
    while_loop: heterogeneous prompt lengths + speculation still compile
    the decode graph exactly once."""
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    reqs = _mixed_requests(cfg, n=4)
    _, eng = _serve(cfg, rt, reqs, spec=SpecConfig(spec_k=3, draft_rank=2))
    assert eng.last_stats.decode_traces == 1


def test_spec_full_rank_drafter_accepts_everything():
    """draft_rank=0 / stride=1 makes the drafter THE target adapter: its
    argmax always matches the verifier's, so every draft is accepted and
    the engine commits spec_k+1 tokens per decode iteration."""
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    reqs = [Request(np.arange(4) % cfg.vocab_size, 8, task=0)]
    base, _ = _serve(cfg, rt, reqs)
    out, eng = _serve(cfg, rt, reqs, spec=SpecConfig(spec_k=3))
    assert out == base
    assert eng.last_stats.acceptance_rate == 1.0


def test_spec_warm_prefix_cache_token_identical():
    """Prefix hits reuse blocks whose cells carry BOTH the target's and
    the drafter's KV (same tables, parallel pools) — warm speculative
    output matches cold."""
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    reqs = _mixed_requests(cfg)
    cold, eng = _serve(cfg, rt, reqs,
                       spec=SpecConfig(spec_k=3, draft_rank=2))
    assert eng.last_stats.prefix_hit_rate == 0.0
    warm = [o.tolist() for o in eng.generate(reqs)]
    assert warm == cold
    assert eng.last_stats.prefix_hit_rate > 0.0


def test_spec_no_leaked_blocks_and_byte_accounting():
    """The drafter's pools ride the SAME block tables: generate allocates
    no extra blocks for drafts, and every block returns to the free list
    (prefix cache off so release is unconditional). block_bytes grows by
    the drafter region's share."""
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    reqs = _mixed_requests(cfg)
    _, base_eng = _serve(cfg, rt, reqs, prefix_cache=False)
    _, eng = _serve(cfg, rt, reqs, prefix_cache=False,
                    spec=SpecConfig(spec_k=3, draft_rank=2))
    assert eng.bm.free_blocks == eng._num_blocks
    st, bst = eng.last_stats, base_eng.last_stats
    assert st.kv_blocks_peak == bst.kv_blocks_peak
    assert st.block_bytes > bst.block_bytes       # drafter region counted
    # unstrided drafter: same layer count -> exactly double
    assert st.block_bytes == 2 * bst.block_bytes


def test_spec_temperature_engine_smoke():
    """Sampling methods run end-to-end through the rejection-sampling
    accept path (distribution-level checks live in
    test_rejection_sampling_preserves_distribution)."""
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    reqs = _mixed_requests(cfg, n=3)
    out, eng = _serve(
        cfg, rt, reqs, spec=SpecConfig(spec_k=2, draft_rank=2),
        sampling=SamplingConfig(method="top_k", top_k=8, temperature=0.9,
                                repetition_penalty=1.2))
    assert [len(o) for o in out] == [r.max_new_tokens for r in reqs]
    assert eng.last_stats.decode_traces == 1


# ---------------------------------------------------------------------------
# accept-rule unit tests (pure functions from serving/speculative.py)
# ---------------------------------------------------------------------------


def test_greedy_verify_prefix_rule():
    draft = jnp.array([[5, 7, 9], [5, 7, 9]])
    verify = jnp.array([[5, 7, 9, 2],     # all accepted + bonus
                        [5, 8, 9, 2]])    # mismatch at position 1
    emitted, n = spec_lib.greedy_verify(draft, verify)
    assert n.tolist() == [3, 1]
    assert emitted.tolist() == verify.tolist()


def test_rejection_sampling_preserves_distribution():
    """Empirical law of the FIRST committed token under a deliberately
    wrong drafter must match the target distribution p (Leviathan
    correctness), and a perfect drafter (q == p) must accept at a rate
    well above a broken one."""
    V, k, trials = 4, 1, 4000
    p = jnp.array([0.55, 0.25, 0.15, 0.05])
    q = jnp.array([0.10, 0.40, 0.30, 0.20])    # wrong on purpose

    def run(key, qv):
        kd, ka = jax.random.split(key)
        d = jax.random.categorical(kd, jnp.log(qv))[None, None]    # (1, 1)
        emitted, n = spec_lib.rejection_verify(
            ka, d, jnp.broadcast_to(qv, (1, k, V)),
            jnp.broadcast_to(p, (1, k + 1, V)))
        return emitted[0, 0], n[0]

    keys = jax.random.split(jax.random.PRNGKey(1), trials)
    toks, ns = jax.vmap(lambda kk: run(kk, q))(keys)
    freq = np.bincount(np.asarray(toks), minlength=V) / trials
    np.testing.assert_allclose(freq, np.asarray(p), atol=0.03)
    # perfect drafter: acceptance = sum_x min(p, q) = 1
    _, ns_perfect = jax.vmap(lambda kk: run(kk, p))(keys)
    assert float(np.mean(np.asarray(ns_perfect))) > \
        float(np.mean(np.asarray(ns))) + 0.2


def test_truncate_factors_rank_nesting():
    """metatt live-factor truncation keeps the LEADING bond columns —
    composing the truncated factors equals composing the full factors
    with the trailing columns zeroed (rank nesting)."""
    key = jax.random.PRNGKey(2)
    k1, k2, k3 = jax.random.split(key, 3)
    g1 = jax.random.normal(k1, (6, 4))
    c = jax.random.normal(k2, (3, 2, 4, 4))
    g4 = jax.random.normal(k3, (4, 5))
    bc, pl = spec_lib.truncate_factors(
        "metatt", {"g1": g1, "g4": g4}, {"c": c}, 2)
    assert bc["g1"].shape == (6, 2) and bc["g4"].shape == (2, 5)
    assert pl["c"].shape == (3, 2, 2, 2)
    full = jnp.einsum("dr,lmrs,se->lmde", g1.at[:, 2:].set(0),
                      c.at[..., 2:, :].set(0).at[..., :, 2:].set(0),
                      g4.at[2:, :].set(0))
    trunc = jnp.einsum("dr,lmrs,se->lmde", bc["g1"], pl["c"], bc["g4"])
    np.testing.assert_allclose(np.asarray(trunc), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


def test_column_penalty_masks_compose_autoregressively():
    base = jnp.zeros((1, 6), bool).at[0, 1].set(True)
    draft = jnp.array([[3, 3, 5]])
    masks = spec_lib.column_penalty_masks(base, draft, 6)
    assert masks.shape == (1, 4, 6)
    assert masks[0, 0].tolist() == base[0].tolist()       # history only
    assert bool(masks[0, 1, 3]) and not bool(masks[0, 1, 5])
    assert bool(masks[0, 3, 3]) and bool(masks[0, 3, 5])
    assert spec_lib.column_penalty_masks(None, draft, 6) is None
