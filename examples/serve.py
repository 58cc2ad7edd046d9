"""Continuous-batching serving with a MetaTT adapter (paper §2.4 + §3.2).

Serves a mixed-task request stream through the slot engine
(repro/serving/engine.py) under each adapter runtime:

  * live   — the TT contraction runs per decode step; a (B,) task-id vector
             gathers per-slot C[l, t, m] slices from ONE shared 4+1d TT, so
             a single decode batch mixes tasks.
  * lora   — middle cores pre-folded into the left boundary (two GEMMs per
             adapted matrix; "matching the speeds of LoRA" per the paper).
  * merged — ΔW of one task folded into the frozen weights (zero overhead);
             single-task streams only.

``--tp N`` serves through the tensor-parallel engine (DESIGN.md §9):
shard_map over a (1, N) ("data", "model") mesh, KV pools kv-head-sharded
per device — token-identical output, per-shard KV bytes = global / N.
Needs N devices (on CPU:
``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

``--spec-k K`` turns on speculative decode (DESIGN.md §10): a
rank-truncated slice of the SAME shared TT (``--draft-rank r``, 0 = full
rank; ``--draft-layer-stride s`` keeps every s-th block) drafts K tokens
per engine step and one verifier pass accepts a prefix — greedy output
stays token-identical to the non-speculative run, which the example
checks.

Fleet serving (DESIGN.md §11): ``--dp N`` stripes requests over N decode
replicas on the "data" mesh axis (composes with ``--tp``; needs dp*tp
devices) and prints per-replica admission/eviction/peak-block stats;
``--disagg`` splits prefill onto a dedicated worker pool that hands
paged KV blocks to the decode replicas; ``--row-parallel`` shards the
second matmul of each pair (wo/wd) row-parallel with an all-reduce
epilogue instead of all-gathering activations.

    PYTHONPATH=src python examples/serve.py [--tokens 16] [--requests 8]
"""
import argparse
import time

import jax

from repro import configs as registry
from repro.config.base import (RegistryConfig, RunConfig, SHAPES,
                               ServeConfig)
from repro.core import tt as ttlib
from repro.launch import compile_cache
from repro.models import model as M
from repro.serving import (AdapterRuntime, ChaosInjector, Engine, FINISHED,
                           Request, SpecConfig, audit)


def serve(cfg, runtime, reqs, *, max_batch, cache_len, out_cap, tp=0,
          dp=0, disagg=False, row_parallel=False, spec=None, slots=0,
          chaos=None):
    mesh = (dp or 1, tp or 1) if (tp or dp or row_parallel) else ()
    sv = ServeConfig(max_batch=max_batch, cache_len=cache_len,
                     out_cap=out_cap, mesh_shape=mesh, disagg=disagg,
                     row_parallel=row_parallel,
                     spec=spec or SpecConfig(),
                     registry=RegistryConfig(max_resident_tasks=slots))
    eng = Engine(cfg, runtime, serve=sv)
    eng.generate(reqs)   # warm-up: compile once + populate the prefix cache
    t0 = time.perf_counter()
    outs = eng.generate(reqs, chaos=chaos)
    dt = time.perf_counter() - t0
    toks = sum(len(o) for o in outs)
    # per-generate observability: KV blocks in use, prefix-cache hit rate,
    # admit/evict/COW counts (serving/stats.py)
    st = eng.last_stats
    print(f"  stats: {st.summary(eng.last_results)}")
    if st.data_shards > 1 or disagg:
        # per-replica placement/pressure figures (replica -1 is the
        # dedicated prefill worker under --disagg)
        for r in st.replica_stats:
            print(f"    replica {r['replica']:>2}: "
                  f"admitted={r['admitted']} evicted={r['evicted']} "
                  f"kv_blocks_peak={r['kv_blocks_peak']} "
                  f"waits={r['backpressure_waits']}"
                  + (f" handoffs={r['handoffs']}" if "handoffs" in r
                     else ""))
    # request lifecycle (DESIGN.md §13): per-request terminal status —
    # printed whenever something other than a clean FINISH happened
    # (deadline sweep, scripted cancel, chaos fault, preemption)
    if chaos is not None or any(rr.status != FINISHED or rr.preemptions
                                for rr in eng.last_results):
        for i, rr in enumerate(eng.last_results):
            print(f"    request {i:>2}: {rr.status:<9} "
                  f"tokens={rr.n_generated:<3} "
                  f"preemptions={rr.preemptions}")
    if chaos is not None:
        audit(eng)  # host-pool invariants hold at rest after the faults
        print(f"  chaos: alloc_faults={chaos.alloc_faults} "
              f"scatter_faults={chaos.scatter_faults} "
              f"killed={chaos.killed}")
    return outs, dt, toks


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4, help="decode slots")
    ap.add_argument("--tasks", type=int, default=3)
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel shards on the 'model' mesh "
                         "axis (0 = single device)")
    ap.add_argument("--dp", type=int, default=0,
                    help="decode replicas on the 'data' mesh axis — "
                         "requests are striped by the deterministic "
                         "router (0 = no data axis; needs dp*tp devices)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregate prefill onto a dedicated worker "
                         "pool with paged-KV block handoff to decode")
    ap.add_argument("--row-parallel", action="store_true",
                    help="row-parallel wo/wd with a psum epilogue "
                         "instead of the all-gather (needs --tp/--dp)")
    ap.add_argument("--max-resident-tasks", type=int, default=0,
                    help="adapter pool slots per replica (DESIGN.md "
                         "§12): serve --tasks tasks through a fixed "
                         "K-slot device pool with LRU paging (0 = whole "
                         "task axis resident). Applies to the live and "
                         "lora runtimes; merged folds one task into the "
                         "weights and has no task axis to page")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request wall-clock budget measured from "
                         "generate() (0 = none): requests past it are "
                         "aborted between steps and finish with status "
                         "TIMEOUT plus whatever tokens they produced "
                         "(DESIGN.md §13)")
    ap.add_argument("--chaos", action="store_true",
                    help="re-run the live stream under a seeded "
                         "ChaosInjector (forced allocation backpressure, "
                         "one scripted cancel, one NaN-logit fault) and "
                         "check survivors stay token-identical "
                         "(DESIGN.md §13)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="draft tokens per engine step (0 = speculative "
                         "decode off)")
    ap.add_argument("--draft-rank", type=int, default=0,
                    help="drafter TT bond rank — leading slice of the "
                         "shared cores (0 = full rank)")
    ap.add_argument("--draft-layer-stride", type=int, default=1,
                    help="drafter keeps every s-th transformer block")
    args = ap.parse_args()

    cfg = registry.get_smoke_config("stablelm-1.6b")
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    adapter_kind="metatt", adapter_variant="4+1d",
                    num_tasks=args.tasks, adapter_rank=8)
    spec = M.build_adapter_spec(run)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, spec, key)
    params["adapter"] = {"cores": ttlib.random_tt(
        key, spec.cfg.mode_sizes, 8, scale=0.5)}
    base, adapter, frozen = (params["base"], params["adapter"],
                             params["frozen"])

    keys = jax.random.split(key, args.requests)
    deadline = args.deadline_ms / 1e3 if args.deadline_ms > 0 else None
    reqs = [Request(jax.random.randint(keys[i], (4 + i % 5,), 0,
                                       cfg.vocab_size),
                    args.tokens, task=i % args.tasks,
                    deadline_s=deadline, request_id=f"r{i}")
            for i in range(args.requests)]
    cache_len = 16 + args.tokens
    kw = dict(max_batch=args.batch, cache_len=cache_len,
              out_cap=args.tokens, tp=args.tp, dp=args.dp,
              disagg=args.disagg, row_parallel=args.row_parallel)
    # adapter paging applies to the TASKED runtimes only (see --help)
    tasked_kw = dict(kw, slots=args.max_resident_tasks)

    rt_live = AdapterRuntime.build("live", base, spec, adapter, frozen)
    live, t_live, toks = serve(cfg, rt_live, reqs, **tasked_kw)

    if args.chaos:
        # seeded fault schedule (DESIGN.md §13): backpressure on the
        # first two host steps, cancel r1 mid-flight, NaN-fail r2 after
        # its second token — survivors must match the clean run exactly
        inj = ChaosInjector(seed=0, alloc_fail_steps=(0, 1),
                            alloc_fail_rate=0.2,
                            cancel_at={1: ["r1"]},
                            nan_after={"r2": 2} if args.requests > 2
                            else None)
        chaosed, _, _ = serve(cfg, rt_live, reqs, chaos=inj, **tasked_kw)
        faulted = {"r1", "r2"}
        same_chaos = all(a.tolist() == b.tolist()
                         for r, a, b in zip(reqs, live, chaosed)
                         if r.request_id not in faulted)
        print(f"  chaos survivors identical to clean run: {same_chaos}")

    spec_cfg = None
    if args.spec_k:
        spec_cfg = SpecConfig(spec_k=args.spec_k,
                              draft_rank=args.draft_rank,
                              draft_layer_stride=args.draft_layer_stride)
        speced, t_spec, _ = serve(cfg, rt_live, reqs, spec=spec_cfg,
                                  **tasked_kw)
        same_spec = all(a.tolist() == b.tolist()
                        for a, b in zip(live, speced))

    rt_lora = AdapterRuntime.build("lora", base, spec, adapter, frozen)
    lora, t_lora, _ = serve(cfg, rt_lora, reqs, **tasked_kw)

    # merged: one task's ΔW folded into the weights -> zero-overhead stream
    # for that task (mixed-task streams need live/lora)
    rt_merged = AdapterRuntime.build("merged", base, spec, adapter, frozen,
                                     model_cfg=cfg, task=0)
    t0_reqs = [r for r in reqs if r.task == 0]
    merged, t_merged, _ = serve(cfg, rt_merged, t0_reqs, **kw)

    same_lora = all(a.tolist() == b.tolist() for a, b in zip(live, lora))
    live_t0 = [o for r, o in zip(reqs, live) if r.task == 0]
    same_merged = all(a.tolist() == b.tolist()
                      for a, b in zip(live_t0, merged))
    print(f"served {args.requests} requests x {args.tokens} tokens through "
          f"{args.batch} slots, {args.tasks} tasks mixed per batch")
    print(f"live TT runtime   : {t_live:.2f}s  {toks/t_live:7.1f} tok/s "
          "(steady state)")
    if spec_cfg is not None:
        print(f"live + spec k={args.spec_k:<2}: {t_spec:.2f}s  "
              f"{toks/t_spec:7.1f} tok/s "
              f"(identical output: {same_spec})")
    print(f"lora-form runtime : {t_lora:.2f}s  {toks/t_lora:7.1f} tok/s "
          f"(identical output: {same_lora})")
    print(f"merged (task 0)   : {t_merged:.2f}s "
          f"(identical output: {same_merged})")
    for i in range(min(3, len(reqs))):
        print(f"request {i} (task {reqs[i].task}): {live[i].tolist()}")


if __name__ == "__main__":
    main()
