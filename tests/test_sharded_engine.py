"""Tensor-parallel sharded serving engine (DESIGN.md §9).

Acceptance criteria of the mesh-aware engine refactor:

  * the sharded engine (shard_map over a ("data", "model") mesh, KV
    caches kv-head-sharded, vocab-striped readout + logits all-gather)
    is TOKEN-IDENTICAL to the single-device engine for greedy decode —
    across runtimes (live / lora / merged), cache modes (paged / dense),
    kv dtypes (fp / int8) and kernel backends (ref / pallas-interpret),
  * warm (prefix-cache) requests stay token-identical under sharding —
    the host-side BlockManager / PrefixCache / COW machinery is
    shard-agnostic (one block id indexes every shard's pool),
  * the paged pools are PHYSICALLY sharded: each device holds a
    1/|model| kv-head stripe of every pool leaf,
  * EngineStats reports GLOBAL byte figures with a ``shards`` field
    whose per-shard projections sum back to the global numbers.

The 4-device cases need fake host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m pytest -q tests/test_sharded_engine.py

(the scripts/ci.sh ``sharded-parity`` job does exactly this). On a
single device they skip; the mesh(1,1) cases still run and exercise the
whole shard_map machinery in the tier-1 suite.
"""
import jax
import numpy as np
import pytest

from repro import configs as registry
from repro.config.base import (KernelConfig, QuantConfig, RunConfig,
                               SHAPES, ServeConfig)
from repro.core import tt as ttlib
from repro.models import model as M
from repro.serving import AdapterRuntime, Engine, Request

KEY = jax.random.PRNGKey(0)
PALLAS = KernelConfig(backend="pallas", interpret=True)

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 (fake) devices: run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4 "
           "(scripts/ci.sh sharded-parity job)")


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


def _serve(cfg, rt, reqs, *, mesh=(), mode="paged", quant=QuantConfig(),
           kernels=None, **kw):
    base = dict(max_batch=2, cache_len=32, out_cap=8, cache_mode=mode,
                page_size=8, prefill_chunk=4, quant=quant,
                mesh_shape=mesh)
    base.update(kw)
    eng = Engine(cfg, rt, serve=ServeConfig(**base), kernels=kernels)
    return [o.tolist() for o in eng.generate(reqs)], eng


def test_mesh_1x1_token_identical_to_unsharded():
    """The shard_map machinery itself (specs, tp context, collectives of
    size 1) must be transparent — runs in the tier-1 single-device
    suite."""
    cfg, spec, params = _setup()
    reqs = _mixed_requests(cfg)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    ref, _ = _serve(cfg, rt, reqs)
    for mode in ("paged", "dense"):
        got, eng = _serve(cfg, rt, reqs, mesh=(1, 1), mode=mode)
        assert got == ref, mode
        assert eng.last_stats.shards == 1


@needs4
def test_tp4_token_parity_all_runtimes():
    """mesh(1,4) vs mesh() greedy token parity for live / lora / merged
    runtimes on a mixed-task, mixed-length paged workload."""
    cfg, spec, params = _setup()
    reqs = _mixed_requests(cfg)
    for mode_name, build_kw, rq in (
            ("live", {}, reqs),
            ("lora", {}, reqs),
            ("merged", dict(model_cfg=cfg, task=1),
             [r for r in reqs if r.task == 1])):
        rt = AdapterRuntime.build(mode_name, params["base"], spec,
                                  params["adapter"], params["frozen"],
                                  **build_kw)
        ref, _ = _serve(cfg, rt, rq)
        tp4, eng = _serve(cfg, rt, rq, mesh=(1, 4))
        assert tp4 == ref, mode_name
        assert eng.last_stats.shards == 4


@needs4
@pytest.mark.parametrize("mode", ["paged", "dense"])
def test_tp4_token_parity_both_cache_modes(mode):
    cfg, spec, params = _setup()
    reqs = _mixed_requests(cfg)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    ref, _ = _serve(cfg, rt, reqs, mode=mode)
    tp4, _ = _serve(cfg, rt, reqs, mesh=(1, 4), mode=mode)
    assert tp4 == ref


@needs4
def test_tp4_int8_kv_and_weights_parity():
    """w8a16 + int8 paged KV under TP: the int8 scale pools shard with
    the cells through the same block tables; the sharded int8 engine
    must match the single-device int8 engine token for token."""
    cfg, spec, params = _setup()
    reqs = _mixed_requests(cfg)
    rt = AdapterRuntime.build("lora", params["base"], spec,
                              params["adapter"], params["frozen"])
    q8 = QuantConfig(weights="int8", kv="int8")
    ref, _ = _serve(cfg, rt, reqs, quant=q8)
    tp4, eng = _serve(cfg, rt, reqs, mesh=(1, 4), quant=q8)
    assert tp4 == ref
    # scale pools are physically sharded alongside the int8 cells
    ks = eng._paged_caches[0]["self"]["k_s"]
    local = ks.addressable_shards[0].data.shape
    assert local[2] == ks.shape[2] // 4      # (nb, N, KV, 1, page)


@needs4
def test_tp4_pallas_interpret_parity():
    """The Pallas paged-attention / fused-linear kernels run PER SHARD
    inside shard_map (local head group, local pool shard) — interpret
    mode on CPU must stay token-identical to the unsharded ref engine."""
    cfg, spec, params = _setup()
    reqs = _mixed_requests(cfg, n=4)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    ref, _ = _serve(cfg, rt, reqs)
    tp4, _ = _serve(cfg, rt, reqs, mesh=(1, 4), kernels=PALLAS)
    assert tp4 == ref


@needs4
def test_tp4_warm_prefix_cache_token_identical():
    """Prefix sharing under sharding: the host-side chain/COW decisions
    are shard-independent, so a warm second pass must reproduce the cold
    tokens exactly and actually hit the cache."""
    cfg, spec, params = _setup()
    reqs = _mixed_requests(cfg)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    cold, eng = _serve(cfg, rt, reqs, mesh=(1, 4))
    assert eng.last_stats.prefix_hit_rate == 0.0
    warm = [o.tolist() for o in eng.generate(reqs)]
    assert warm == cold
    st = eng.last_stats
    assert st.prefix_hit_rate > 0
    assert st.cow_copies > 0


@needs4
def test_tp4_stats_per_shard_sums_to_global():
    """EngineStats reports GLOBAL bytes + a shards field; the per-shard
    projections must sum back to the global figures, match 1/4 of the
    dense-equivalent reservation, and agree with the physical pool
    placement."""
    cfg, spec, params = _setup()
    reqs = _mixed_requests(cfg)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    _, eng1 = _serve(cfg, rt, reqs)
    _, eng4 = _serve(cfg, rt, reqs, mesh=(1, 4))
    s1, s4 = eng1.last_stats, eng4.last_stats
    assert (s1.shards, s4.shards) == (1, 4)
    # global accounting is mesh-independent
    assert s4.block_bytes == s1.block_bytes
    assert s4.kv_bytes_peak == s1.kv_bytes_peak
    # per-shard figures sum to global, and are global/4 under TP=4
    assert s4.block_bytes_per_shard * s4.shards == s4.block_bytes
    assert s4.kv_bytes_peak_per_shard * s4.shards == s4.kv_bytes_peak
    assert s4.kv_bytes_peak_per_shard == s4.kv_bytes_peak // 4
    assert s1.kv_bytes_peak_per_shard == s1.kv_bytes_peak
    # device truth: each shard holds a 1/4 kv-head stripe of every pool
    for leaf in jax.tree_util.tree_leaves(eng4._paged_caches):
        local = leaf.addressable_shards[0].data.shape
        assert local[2] == leaf.shape[2] // 4, (leaf.shape, local)


def test_mesh_validation_errors():
    cfg, spec, params = _setup(variant="4d", num_tasks=0)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    with pytest.raises(ValueError):     # not a (data, model) pair
        ServeConfig(mesh_shape=(2,)).validate()
    with pytest.raises(ValueError):     # unknown TP axis name
        ServeConfig(mesh_shape=(1, 1), tp_axis="pod").validate()
    with pytest.raises(ValueError):     # more devices than the host has
        Engine(cfg, rt, serve=ServeConfig(
            mesh_shape=(1, 4096), cache_len=32, out_cap=8))


@needs4
def test_mesh_rejects_indivisible_heads():
    """Heads that do not divide the model axis must fail loudly — a
    silent replicated fallback would void the per-shard KV-bytes
    claim."""
    import dataclasses
    cfg, spec, params = _setup(variant="4d", num_tasks=0)
    bad = dataclasses.replace(registry.get_smoke_config("stablelm-1.6b"),
                              num_heads=2, num_kv_heads=2)
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    with pytest.raises(ValueError, match="num_heads"):
        Engine(bad, rt, serve=ServeConfig(mesh_shape=(1, 4),
                                          cache_len=32, out_cap=8))
