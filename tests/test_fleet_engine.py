"""Fleet-scale serving: data-axis request striping, disaggregated
prefill/decode with paged-KV block handoff, row-parallel TP
(DESIGN.md §11).

Acceptance criteria:

  * data-parallel striping (dp2 x tp2) is TOKEN-IDENTICAL to the
    single-replica engine for greedy decode under deterministic
    routing — each data shard decodes only its own slot stripe and
    the paged pools are physically striped over the data axis,
  * disaggregated prefill/decode (dedicated prefill worker pool,
    host-side block-table handoff + pool-to-pool block migration)
    preserves tokens, leaks no blocks, and keeps ``decode_traces == 1``
    (the prefill worker reuses the decode trace),
  * the row-parallel TP variant matches the column-only oracle
    (deterministic CPU math makes "near-parity <= 1e-3" exact token
    identity here), and is exact on a mesh of 1,
  * the Router places requests deterministically (least-loaded with
    lowest-index tie-break, or strict round-robin).

The 4-device cases need fake host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m pytest -q tests/test_fleet_engine.py

(the scripts/ci.sh ``fleet-parity`` job runs them under 8). On a
single device they skip; the mesh(1,1) and single-device disagg cases
still run in the tier-1 suite.
"""
import jax
import numpy as np
import pytest

from repro import configs as registry
from repro.config.base import (QuantConfig, RunConfig, SHAPES,
                               ServeConfig)
from repro.core import tt as ttlib
from repro.models import model as M
from repro.serving import AdapterRuntime, Engine, Request, Router

KEY = jax.random.PRNGKey(0)

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 (fake) devices: run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4 "
           "(scripts/ci.sh fleet-parity job)")


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


def _runtime():
    cfg, spec, params = _setup()
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    return cfg, rt


def _mixed_requests(cfg, n=5, tasks=3):
    prompts = [jax.random.randint(jax.random.PRNGKey(i), (4 + i,), 0,
                                  cfg.vocab_size) for i in range(n)]
    return [Request(p, 5 + (i % 3), task=i % tasks)
            for i, p in enumerate(prompts)]


def _serve(cfg, rt, reqs, *, mesh=(), **kw):
    base = dict(max_batch=2, cache_len=32, out_cap=8, page_size=8,
                prefill_chunk=4, mesh_shape=mesh)
    base.update(kw)
    eng = Engine(cfg, rt, serve=ServeConfig(**base))
    return [o.tolist() for o in eng.generate(reqs)], eng


# ---------------------------------------------------------------------------
# Router units (pure host-side, tier-1)
# ---------------------------------------------------------------------------

def test_router_round_robin_cycles():
    r = Router(3, "round_robin")
    assert [r.route(10) for _ in range(7)] == [0, 1, 2, 0, 1, 2, 0]
    # cost is tracked but never consulted by the round-robin policy
    assert r.loads() == [30, 20, 20]


def test_router_least_loaded_deterministic_tie_break():
    r = Router(3, "least_loaded")
    # ties break toward the lowest replica index
    assert r.route(5) == 0
    assert r.route(3) == 1
    assert r.route(1) == 2
    # loads now [5, 3, 1] -> replica 2 is least loaded
    assert r.route(10) == 2
    assert r.loads() == [5, 3, 11]
    # completion decrements the replica's outstanding cost
    r.complete(2, 10)
    assert r.route(1) == 2


def test_router_validation():
    with pytest.raises(ValueError):
        Router(0, "round_robin")
    with pytest.raises(ValueError):
        Router(2, "nope")


def test_router_out_of_order_completions():
    """Completions arrive in ANY order relative to routing (one replica
    can fully drain while another holds earlier requests): load drains
    exactly per replica and later ties stay deterministic."""
    r = Router(2, "least_loaded")
    assert [r.route(c) for c in (4, 2, 3)] == [0, 1, 1]
    # replica 1's SECOND request completes before its first
    r.complete(1, 3)
    r.complete(1, 2)
    assert r.loads() == [4, 0]
    r.complete(0, 4)
    assert r.loads() == [0, 0]
    # fully drained: the tie breaks toward replica 0 again
    assert r.route(1) == 0


def test_router_interleaved_route_complete():
    """route/complete interleaving mid-stream: refunds reshuffle the
    least-loaded ordering deterministically."""
    r = Router(3, "least_loaded")
    assert [r.route(c) for c in (6, 3, 3)] == [0, 1, 2]
    assert r.route(1) == 1            # tie 3,3 -> lowest index
    r.complete(2, 3)                  # replica 2 drains first
    assert r.route(2) == 2
    r.complete(0, 6)
    assert r.route(1) == 0
    assert r.loads() == [1, 4, 2]


def test_router_complete_rejects_bad_refunds():
    """Bookkeeping violations raise (never silently clamp): unknown
    replica, negative cost, refund exceeding the replica's outstanding
    load (double complete) — and load can never go negative."""
    r = Router(2, "least_loaded")
    r.route(5)
    with pytest.raises(ValueError):
        r.complete(2, 1)              # unknown replica
    with pytest.raises(ValueError):
        r.complete(-1, 1)
    with pytest.raises(ValueError):
        r.complete(0, -1)             # negative cost
    with pytest.raises(ValueError):
        r.complete(0, 6)              # over-refund
    r.complete(0, 5)
    with pytest.raises(ValueError):
        r.complete(0, 5)              # double complete
    assert r.loads() == [0, 0]


def test_fleet_config_validation():
    cfg, rt = _runtime()
    with pytest.raises(ValueError):
        ServeConfig(disagg=True, cache_mode="dense").validate()
    with pytest.raises(ValueError):
        ServeConfig(router="random").validate()
    with pytest.raises(ValueError):
        ServeConfig(row_parallel=True).validate()   # needs a mesh
    with pytest.raises(ValueError):
        ServeConfig(mesh_shape=(1, 1), row_parallel=True,
                    quant=QuantConfig(weights="int8",
                                      group_size=64)).validate()
    with pytest.raises(ValueError):             # dp>1 needs paged KV
        Engine(cfg, rt, serve=ServeConfig(mesh_shape=(2, 1),
                                          cache_mode="dense"))


# ---------------------------------------------------------------------------
# Disaggregated prefill/decode — single device (tier-1)
# ---------------------------------------------------------------------------

def test_disagg_token_identical_single_device():
    """The prefill-worker pool + block handoff must be invisible in the
    output: same tokens as the co-batched engine, one decode trace
    (the worker reuses it), and per-request times in order."""
    cfg, rt = _runtime()
    reqs = _mixed_requests(cfg)
    reqs.append(Request(reqs[0].prompt, 1, task=0))  # finishes at prefill
    ref, _ = _serve(cfg, rt, reqs)
    got, eng = _serve(cfg, rt, reqs, disagg=True)
    assert got == ref
    st = eng.last_stats
    assert st.decode_traces == 1
    # every request's times in lifecycle order; the first token of a
    # handed-off request comes from the prefill worker, before decode
    for res in eng.last_results:
        assert (res.t_submit <= res.t_admit <= res.t_first
                <= res.t_done), res
    # the prefill worker reports as replica -1 with a handoff count:
    # 5 decode-bound requests handed off, the max_new==1 one finished
    # at prefill harvest and never touched a decode replica
    pf = st.replica_stats[-1]
    assert pf["replica"] == -1 and pf["handoffs"] == 5
    assert st.replica_stats[0]["evicted"] == 5 and pf["evicted"] == 6


def test_disagg_leaks_no_blocks():
    cfg, rt = _runtime()
    reqs = _mixed_requests(cfg)
    _, eng = _serve(cfg, rt, reqs, disagg=True, prefix_cache=False)
    for bm in eng.bms + eng._pf_bms:
        assert bm.free_blocks == eng._num_blocks
    # with the prefix cache on, pinned prefix blocks live in the
    # PREFILL pool only; decode pools always drain to empty
    _, eng = _serve(cfg, rt, reqs, disagg=True)
    assert all(bm.free_blocks == eng._num_blocks for bm in eng.bms)
    for bm, px in zip(eng._pf_bms, eng._pf_prefixes):
        assert bm.free_blocks + px.cached_blocks == eng._num_blocks


def test_disagg_warm_prefix_reuse():
    """A second pass through the same prompts must hit the prefill
    worker's prefix cache and still emit identical tokens — without
    retracing."""
    cfg, rt = _runtime()
    reqs = _mixed_requests(cfg)
    ref, _ = _serve(cfg, rt, reqs)
    _, eng = _serve(cfg, rt, reqs, disagg=True)
    warm = [o.tolist() for o in eng.generate(reqs)]
    assert warm == ref
    assert eng.last_stats.prefix_hit_rate > 0.0
    assert eng.last_stats.decode_traces == 1


def test_disagg_pool_budget_reported():
    cfg, rt = _runtime()
    _, eng = _serve(cfg, rt, _mixed_requests(cfg), disagg=True)
    # two pools of _num_blocks each on a mesh of 1
    assert eng.last_stats.num_blocks == 2 * eng._num_blocks


# ---------------------------------------------------------------------------
# Fleet transparency on a mesh of 1 (tier-1)
# ---------------------------------------------------------------------------

def test_mesh_1x1_row_parallel_exact():
    """Row-parallel sharding of wo/wd with a size-1 psum epilogue must
    be bit-transparent — tier-1 evidence the rp math is exact."""
    cfg, rt = _runtime()
    reqs = _mixed_requests(cfg)
    ref, _ = _serve(cfg, rt, reqs)
    got, eng = _serve(cfg, rt, reqs, mesh=(1, 1), row_parallel=True)
    assert got == ref
    assert eng.last_stats.data_shards == 1


def test_mesh_1x1_disagg_transparent():
    cfg, rt = _runtime()
    reqs = _mixed_requests(cfg)
    ref, _ = _serve(cfg, rt, reqs)
    got, eng = _serve(cfg, rt, reqs, mesh=(1, 1), disagg=True)
    assert got == ref
    assert eng.last_stats.decode_traces == 1


# ---------------------------------------------------------------------------
# 4-device fleet cases
# ---------------------------------------------------------------------------

@needs4
def test_dp2_tp2_token_identical_to_dp1_tp1():
    """The headline fleet invariant: striping requests over two data
    replicas (each a tp2 shard group) under deterministic routing
    changes NOTHING about greedy tokens."""
    cfg, rt = _runtime()
    reqs = _mixed_requests(cfg)
    ref, _ = _serve(cfg, rt, reqs, mesh=(1, 1))
    got, eng = _serve(cfg, rt, reqs, mesh=(2, 2))
    assert got == ref
    st = eng.last_stats
    assert st.data_shards == 2 and st.shards == 2
    assert st.decode_traces == 1
    # every request landed on exactly one replica
    reps = [r for r in st.replica_stats if r["replica"] >= 0]
    assert sorted(r["replica"] for r in reps) == [0, 1]
    assert sum(r["admitted"] for r in reps) == len(reqs)
    assert sum(r["evicted"] for r in reps) == len(reqs)
    assert all(r["queue_depth"] == 0 for r in reps)


@needs4
def test_dp2_pools_physically_striped():
    """Each data replica owns a private 1/|data| stripe of every pool
    leaf (on top of the 1/|model| kv-head stripe)."""
    cfg, rt = _runtime()
    _, eng = _serve(cfg, rt, _mixed_requests(cfg), mesh=(2, 2))
    assert eng.last_stats.num_blocks == 2 * eng._num_blocks
    for leaf in jax.tree_util.tree_leaves(eng._paged_caches):
        shard = leaf.addressable_shards[0].data
        assert leaf.shape[1] == 2 * eng._num_blocks
        assert shard.shape[1] == eng._num_blocks


@needs4
def test_dp2_round_robin_token_identical():
    cfg, rt = _runtime()
    reqs = _mixed_requests(cfg)
    ref, _ = _serve(cfg, rt, reqs, mesh=(1, 1))
    got, _ = _serve(cfg, rt, reqs, mesh=(2, 2), router="round_robin")
    assert got == ref


@needs4
def test_dp2_disagg_token_identical():
    """Striping AND disaggregation composed: per-replica prefill
    worker pools hand finished sequences to their decode twins."""
    cfg, rt = _runtime()
    reqs = _mixed_requests(cfg)
    ref, _ = _serve(cfg, rt, reqs, mesh=(1, 1))
    got, eng = _serve(cfg, rt, reqs, mesh=(2, 2), disagg=True)
    assert got == ref
    st = eng.last_stats
    assert st.decode_traces == 1
    assert st.replica_stats[-1]["handoffs"] == len(reqs)
    assert all(bm.free_blocks == eng._num_blocks for bm in eng.bms)
    for bm, px in zip(eng._pf_bms, eng._pf_prefixes):
        assert bm.free_blocks + px.cached_blocks == eng._num_blocks


@needs4
def test_tp4_row_parallel_matches_column_oracle():
    """Row-parallel wo/wd/FFN-down with an all-reduce epilogue vs the
    column-only oracle. CPU float math is deterministic, so the
    <=1e-3 near-parity bar is witnessed as exact token identity."""
    cfg, rt = _runtime()
    reqs = _mixed_requests(cfg)
    col, _ = _serve(cfg, rt, reqs, mesh=(1, 4))
    row, _ = _serve(cfg, rt, reqs, mesh=(1, 4), row_parallel=True)
    assert row == col


@needs4
def test_dp4_token_identical():
    """Pure data axis: four single-shard replicas."""
    cfg, rt = _runtime()
    reqs = _mixed_requests(cfg)
    ref, _ = _serve(cfg, rt, reqs, mesh=(1, 1))
    got, eng = _serve(cfg, rt, reqs, mesh=(4, 1))
    assert got == ref
    assert eng.last_stats.data_shards == 4
