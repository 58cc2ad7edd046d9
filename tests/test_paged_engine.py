"""Paged serving engine (block/paged KV cache, prefix sharing, in-loop
chunked prefill) — acceptance criteria of the paged-cache refactor:

  * paged engine is token-identical to the dense engine on a mixed-task,
    mixed-length greedy workload, across live/lora/merged runtimes, on
    the reference backend and in Pallas interpret mode,
  * warm (prefix-cache) requests produce token-identical output to
    cold-cache runs, including divergence after a shared partial page
    (copy-on-write),
  * heterogeneous prompt lengths compile the chunked-prefill decode
    graph exactly ONCE (no per-bucket prefill ladder),
  * out-of-blocks admission backpressure serves everything correctly,
  * the paged_decode_attention Pallas kernel matches its reference twin
    through the same ops entry point the model uses.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as registry
from repro.config.base import KernelConfig, RunConfig, SHAPES, ServeConfig
from repro.core import tt as ttlib
from repro.kernels import ops, paged_attention
from repro.models import model as M
from repro.serving import AdapterRuntime, Engine, Request

KEY = jax.random.PRNGKey(0)
PALLAS = KernelConfig(backend="pallas", interpret=True)


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


def _mixed_requests(cfg, n=5, tasks=3):
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (4 + i,), 0,
                                  cfg.vocab_size) for i in range(n)]
    return [Request(p, 5 + (i % 3), task=i % tasks)
            for i, p in enumerate(prompts)]


def _serve(cfg, rt, reqs, mode, *, kernels=None, **kw):
    base = dict(max_batch=2, cache_len=32, out_cap=8, cache_mode=mode,
                page_size=8, prefill_chunk=4)
    base.update(kw)
    eng = Engine(cfg, rt, serve=ServeConfig(**base), kernels=kernels)
    return [o.tolist() for o in eng.generate(reqs)], eng


def test_paged_matches_dense_mixed_task_mixed_length_all_runtimes():
    cfg, spec, params = _setup()
    reqs = _mixed_requests(cfg)
    for mode_name, build_kw, rq in (
            ("live", {}, reqs),
            ("lora", {}, reqs),
            # merged freezes one task: single-task slice of the workload
            ("merged", dict(model_cfg=cfg, task=1),
             [r for r in reqs if r.task == 1])):
        rt = AdapterRuntime.build(mode_name, params["base"], spec,
                                  params["adapter"], params["frozen"],
                                  **build_kw)
        dense, _ = _serve(cfg, rt, rq, "dense")
        paged, _ = _serve(cfg, rt, rq, "paged")
        assert paged == dense, mode_name


@pytest.mark.parametrize("mode", ["live", "lora"])
def test_paged_matches_dense_in_pallas_interpret_mode(mode):
    cfg, spec, params = _setup()
    reqs = _mixed_requests(cfg, n=4)
    rt = AdapterRuntime.build(mode, params["base"], spec,
                              params["adapter"], params["frozen"])
    dense, _ = _serve(cfg, rt, reqs, "dense")
    paged, _ = _serve(cfg, rt, reqs, "paged", kernels=PALLAS)
    assert paged == dense


def test_warm_prefix_cache_token_identical_and_hits():
    cfg, spec, params = _setup()
    reqs = _mixed_requests(cfg)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    cold, eng = _serve(cfg, rt, reqs, "paged")
    assert eng.last_stats.prefix_hit_rate == 0.0
    warm = [o.tolist() for o in eng.generate(reqs)]
    assert warm == cold
    st = eng.last_stats
    assert st.prefix_hit_rate > 0
    assert st.cow_copies > 0          # partial last prompt pages reshared


def test_shared_prefix_divergence_copy_on_write_parity():
    """Two requests sharing a prefix that ends mid-page, then diverging:
    the second maps the cached partial page, COWs it, and must still be
    token-identical to a cold dense run — and the cached original must
    serve a third identical request unchanged."""
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    base_p = np.asarray(
        jax.random.randint(KEY, (10,), 0, cfg.vocab_size))  # 1 page + 2
    div = np.concatenate([base_p[:6], np.array([1, 2, 3], np.int32)])
    reqs = [Request(base_p, 6, task=1), Request(div, 6, task=1),
            Request(base_p, 6, task=1)]
    dense, _ = _serve(cfg, rt, reqs, "dense")
    # max_batch=1 serializes: req 0 registers its prefix, req 1 shares+COWs
    sv = ServeConfig(max_batch=1, cache_len=32, out_cap=8,
                     cache_mode="paged", page_size=8, prefill_chunk=4)
    eng = Engine(cfg, rt, serve=sv)
    paged = [o.tolist() for o in eng.generate(reqs)]
    assert paged == dense
    st = eng.last_stats
    assert st.cow_copies >= 1 and st.prefix_hit_tokens > 0


def test_heterogeneous_prompts_compile_decode_graph_once():
    """The in-loop chunked prefill replaces the dense _bucket ladder: one
    trace serves every prompt length (asserted via a trace counter that
    increments as a Python side effect inside the jitted impl)."""
    cfg, spec, params = _setup(variant="4d", num_tasks=0)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    sv = ServeConfig(max_batch=2, cache_len=32, out_cap=8,
                     page_size=8, prefill_chunk=4)
    eng = Engine(cfg, rt, serve=sv)
    reqs = [Request(jax.random.randint(jax.random.PRNGKey(i), (2 + 3 * i,),
                                       0, cfg.vocab_size), 4)
            for i in range(5)]          # prompt lengths 2, 5, 8, 11, 14
    eng.generate(reqs)
    assert eng.last_stats.decode_traces == 1
    assert eng.last_stats.prefill_traces == 0
    # the dense engine's bucket ladder, by contrast, compiles per bucket
    dense = Engine(cfg, rt, serve=ServeConfig(
        max_batch=2, cache_len=32, out_cap=8, cache_mode="dense"))
    dense.generate(reqs)
    assert dense.last_stats.prefill_traces > 1


def test_out_of_blocks_backpressure_still_serves_everything():
    cfg, spec, params = _setup()
    reqs = _mixed_requests(cfg)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    dense, _ = _serve(cfg, rt, reqs, "dense")
    # 4 blocks of 8 tokens: at most ~2 requests resident -> waits > 0
    paged, eng = _serve(cfg, rt, reqs, "paged", num_blocks=4,
                        max_batch=4)
    assert paged == dense
    assert eng.last_stats.backpressure_waits > 0
    assert eng.last_stats.kv_blocks_peak <= 4


def test_warm_request_in_tight_pool_falls_back_cold_not_deadlock():
    """A pool just big enough for one request, fully occupied by that
    request's cached prefix: the warm re-admission's own prefix match
    pins the cached blocks, so the COW block cannot be allocated — the
    scheduler must drop the match and admit cold instead of deadlocking."""
    cfg, spec, params = _setup(variant="4d", num_tasks=0)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    sv = ServeConfig(max_batch=1, cache_len=16, out_cap=8, page_size=8,
                     prefill_chunk=4)            # num_blocks == 2
    eng = Engine(cfg, rt, serve=sv)
    prompt = jax.random.randint(KEY, (9,), 0, cfg.vocab_size)
    cold = eng.generate([Request(prompt, 7)])[0].tolist()
    warm = eng.generate([Request(prompt, 7)])[0].tolist()
    assert warm == cold
    assert eng.last_stats.backpressure_waits == 0  # resolved in plan()


def test_prefix_chains_are_namespaced_per_task():
    """Task-adapted matrices make deep-layer KV task-dependent: an
    identical prompt under a DIFFERENT task must not reuse the cached
    prefix (and must still match the dense engine's output)."""
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    prompt = jax.random.randint(KEY, (9,), 0, cfg.vocab_size)
    dense, _ = _serve(cfg, rt, [Request(prompt, 5, task=1)], "dense")
    _, eng = _serve(cfg, rt, [Request(prompt, 5, task=0)], "paged")
    other = [o.tolist() for o in eng.generate([Request(prompt, 5, task=1)])]
    assert eng.last_stats.prefix_hit_tokens == 0   # no cross-task reuse
    assert other == dense
    same = [o.tolist() for o in eng.generate([Request(prompt, 5, task=1)])]
    assert eng.last_stats.prefix_hit_tokens > 0    # within-task reuse
    assert same == dense


def test_paged_engine_rejects_oversized_request():
    cfg, spec, params = _setup(variant="4d", num_tasks=0)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    eng = Engine(cfg, rt, serve=ServeConfig(max_batch=1, cache_len=16,
                                            out_cap=8, page_size=8))
    long_prompt = jnp.zeros((12,), jnp.int32)
    with pytest.raises(ValueError):
        eng.generate([Request(long_prompt, 8)])   # 12 + 8 > cache_len
    with pytest.raises(ValueError):
        ServeConfig(cache_len=64, page_size=8, num_blocks=4).validate()


@pytest.mark.parametrize("c,heads,layout", [
    pytest.param(1, (4, 4), "mixed", id="1-heads0"),
    pytest.param(4, (4, 2), "mixed", id="4-heads1"),
    pytest.param(8, (8, 2), "mixed", id="8-heads2"),
    pytest.param(8, (4, 4), "ragged_table", id="ragged_table"),
    pytest.param(1, (4, 2), "block_edge", id="block_edge"),
    pytest.param(8, (4, 4), "chunk_cross", id="chunk_cross"),
    pytest.param(8, (4, 2), "idle_slot", id="idle_slot"),
    pytest.param(4, (8, 4), "split_heads", id="split_heads"),
    pytest.param(4, (4, 4), "spec_verify", id="spec_verify"),
])
def test_paged_attention_kernel_matches_ref(c, heads, layout, paged_layout):
    """kernels/paged_attention.py vs kernels/ref.py twin through the ops
    entry point: GQA broadcast, sentinel table entries, and the layouts of
    tests/conftest.py's PAGED_LAYOUTS (tables that end mid-step, contexts
    ending on a step's edge, chunks across pages, idle slots, fewer kv
    heads a step than the pools hold, the verifier's chunk)."""
    h, kv = heads
    page, tables, pos, n = paged_layout(layout)
    b, p_tab, d = tables.shape[0], tables.shape[1], 16
    pps, per_step = paged_attention.step_shape(p_tab, kv, page, d,
                                               jnp.float32, False)
    if layout == "ragged_table":
        assert p_tab % pps
    if layout == "split_heads":
        assert per_step < kv
    ks = jax.random.split(jax.random.PRNGKey(c), 3)
    q = jax.random.normal(ks[0], (b, c, h, d), jnp.float32)
    kc = jax.random.normal(ks[1], (n, kv, page, d), jnp.float32)
    vc = jax.random.normal(ks[2], (n, kv, page, d), jnp.float32)
    ref = ops.paged_decode_attention(q, kc, vc, tables, pos, backend="ref")
    pal = ops.paged_decode_attention(q, kc, vc, tables, pos,
                                     backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("mode", ["paged", "dense"])
def test_request_times_in_lifecycle_order(mode):
    """Every request's times come in lifecycle order: submitted, admitted
    into a slot, first token, done (five requests through two slots, so
    three of them queue)."""
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    _, eng = _serve(cfg, rt, _mixed_requests(cfg), mode)
    res = eng.last_results
    assert len({r.t_submit for r in res}) == 1
    for r in res:
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done, r
    # the queued requests were admitted only as slots came free
    assert max(r.t_admit for r in res) > min(r.t_done for r in res)


def test_first_token_lands_ceil_plen_over_chunk_steps_after_admission():
    """With no prefix hit, a request admitted into a free slot emits its
    first token ceil(plen / prefill_chunk) loop steps after admission;
    its ``t_first`` is that step's place inside the decode call that ran
    it, between the call's dispatch and return."""
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    plens, chunk = (5, 9, 13, 4, 11), 4
    reqs = [Request(jax.random.randint(jax.random.PRNGKey(10 + i), (n,), 0,
                                       cfg.vocab_size), 5 + i % 3, task=i % 3)
            for i, n in enumerate(plens)]
    _, eng = _serve(cfg, rt, reqs, "paged", prefill_chunk=chunk)
    st = eng.last_stats
    assert st.prefix_hit_tokens == 0
    log = st.decode_call_log
    assert [c[2] for c in log[1:]] == [c[3] for c in log[:-1]]
    for n, r in zip(plens, eng.last_results):
        # the loop's step count when the request entered its slot: the
        # "before" of the first call dispatched after its admission
        at = next(c[2] for c in log if c[0] >= r.t_admit)
        target = at + -(-n // chunk)
        t_d, t_r, s0, s1 = next(c for c in log if c[2] < target <= c[3])
        assert r.t_first == pytest.approx(
            t_d + (target - s0) / (s1 - s0) * (t_r - t_d), rel=0, abs=1e-9)
