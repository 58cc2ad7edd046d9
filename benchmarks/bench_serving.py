"""Serving benchmarks.

1. Paper §2.4 decode-step latency: live TT contraction vs pre-merged
   (fold-into-dense) weights vs the bare base model — the paper's claim is
   merged MetaTT == LoRA == base.
2. Engine throughput: the jitted-while-loop continuous-batching engine
   (repro/serving/) serving a MIXED-TASK batch (>= 2 distinct task ids per
   decode batch, one shared 4+1d TT) vs the seed's one-request-shape
   per-token Python loop, in tokens/sec.
3. Paged vs dense KV cache on a shared-prefix workload: token parity is
   asserted and throughput / peak KV memory / prefix-cache hit rate come
   from the engine's OWN stats object (engine.last_stats — the numbers a
   deployment would scrape), not benchmark-side re-derivation.
4. fp vs int8 (w8a16 weights + int8 KV) paged serving: greedy-token match
   fraction (>= TOKEN_MATCH_MIN asserted) and peak KV bytes (int8 must
   come in below fp at the same num_blocks budget) — again from
   engine.last_stats.
5. ``--mesh``: tensor-parallel vs single-device serving (DESIGN.md §9) —
   the TP=4 engine must be token-identical to TP=1 and report per-shard
   peak KV bytes of global/4; the ``serving/tp4_vs_tp1`` row (plus both
   engines' stats) is merged into ``BENCH_serving.json`` in place.
   Needs 4 devices: run under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the
   scripts/ci.sh sharded-parity job does).
6. ``--fleet``: data-striped (dp2 x tp4) vs single-replica (dp1 x tp4)
   serving (DESIGN.md §11) — token identity and per-replica block
   accounting are asserted and the ``serving/dp2_vs_dp1`` row is merged
   into ``BENCH_serving.json``. Needs 8 devices (the scripts/ci.sh
   fleet-parity job forces them).

Throughput figures always come from a SECOND ``generate`` call — the
first, traced call pays jit compilation and is excluded from every
``tokens_per_s`` wall. Paged rows also report the latency phase split
(``ttft_ms`` time-to-first-token vs ``tpot_ms`` per-token decode
latency, p50 over the requests' own times in engine.last_results).

Engine stats of every engine run land in ``ENGINE_STATS`` (reset per
``run()``) so ``benchmarks/run.py --json`` can emit them machine-readably.

    PYTHONPATH=src python benchmarks/bench_serving.py [--smoke] [--mesh]
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro import configs as registry
from repro.config.base import (KernelConfig, QuantConfig, RegistryConfig,
                               RunConfig, SHAPES, ServeConfig)
from repro.core import tt as ttlib
from repro.core.merge import fold_transformer
from repro.kernels import dispatch
from repro.models import model as M, transformer as T
from repro.peft import api as peft_api
from repro.serving import AdapterRuntime, Engine, Request
from repro.serving import engine as se
from repro.serving.stats import latency_quantiles

#: engine stats (dataclasses.asdict + derived rates) of every timed engine
#: run in the latest run() call, labeled — consumed by run.py --json
ENGINE_STATS: list = []

#: documented int8-vs-fp greedy-parity floor (argmax near-ties flip under
#: quantization noise on a random-weight smoke model)
TOKEN_MATCH_MIN = 0.9


def _record_stats(label: str, st) -> None:
    d = dataclasses.asdict(st)
    d.pop("decode_call_log")            # one tuple per call: too long
    d.update(label=label, tokens_per_s=st.tokens_per_s,
             prefix_hit_rate=st.prefix_hit_rate,
             kv_bytes_peak=st.kv_bytes_peak)
    ENGINE_STATS.append(d)


def _p50_ms(eng, name: str) -> str:
    """Median ``ttft`` or ``tpot`` of the engine's last generate call, in
    ms, from each request's own times."""
    v = latency_quantiles(eng.last_results).get(f"{name}_p50")
    return "nan" if v is None else f"{v * 1e3:.2f}"


def _decode_step_rows(rows) -> None:
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run_cfg = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                        adapter_kind="metatt", adapter_rank=8)
    spec = M.build_adapter_spec(run_cfg)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, spec, key)
    params["adapter"] = {"cores": ttlib.random_tt(key, spec.cfg.mode_sizes,
                                                  8, scale=0.1)}
    bc, pl = peft_api.adapter_factors(spec, params["adapter"],
                                      params["frozen"])
    B, S = 4, 64
    token = jax.random.randint(key, (B, 1), 0, cfg.vocab_size)
    caches = T.init_caches(cfg, B, S, jnp.float32)
    pos = jnp.int32(3)

    live = jax.jit(lambda tok, c: T.decode_step(
        params["base"], cfg, spec, bc, pl, tok, c, pos)[0])
    us_live = time_call(live, token, caches)
    rows.append(emit("serving/decode_live_tt", us_live, "adapter=metatt-r8"))

    # same decode step through the fused dispatch seam (interpret mode on
    # CPU is a correctness emulator, not a speed number; TPU is the target)
    fused = jax.jit(lambda tok, c: T.decode_step(
        params["base"], cfg, spec, bc, pl, tok, c, pos,
        policy=dispatch.PALLAS_INTERPRET)[0])
    us_fused = time_call(fused, token, caches, iters=3, warmup=1)
    rows.append(emit("serving/decode_live_fused_interpret", us_fused,
                     "adapter=metatt-r8,interpret=1"))

    # merged: fold ΔW into every adapted weight, run with NO adapter
    folded = fold_transformer(params["adapter"], spec.cfg, params["base"],
                              cfg)
    merged_fn = jax.jit(lambda tok, c: T.decode_step(
        folded, cfg, peft_api.NONE, {}, None, tok, c, pos)[0])
    us_merged = time_call(merged_fn, token, caches)
    rows.append(emit("serving/decode_merged", us_merged,
                     f"overhead_removed={us_live-us_merged:.0f}us"))

    base_fn = jax.jit(lambda tok, c: T.decode_step(
        params["base"], cfg, peft_api.NONE, {}, None, tok, c, pos)[0])
    us_base = time_call(base_fn, token, caches)
    rows.append(emit("serving/decode_base_no_adapter", us_base,
                     f"merged_vs_base_ratio={us_merged/us_base:.3f}"))


def _engine_rows(rows, *, smoke: bool) -> None:
    """Mixed-task continuous batching vs the seed per-token Python loop."""
    n_req, n_new, slots, n_tasks = (4, 8, 2, 2) if smoke else (12, 24, 4, 3)
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run_cfg = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                        adapter_kind="metatt", adapter_variant="4+1d",
                        num_tasks=n_tasks, adapter_rank=8)
    spec = M.build_adapter_spec(run_cfg)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, spec, key)
    params["adapter"] = {"cores": ttlib.random_tt(key, spec.cfg.mode_sizes,
                                                  8, scale=0.5)}
    keys = jax.random.split(key, n_req)
    prompts = [jax.random.randint(keys[i], (4 + i % 4,), 0, cfg.vocab_size)
               for i in range(n_req)]
    # >= 2 distinct task ids in every decode batch, one shared 4+1d TT
    reqs = [Request(p, n_new, task=i % n_tasks)
            for i, p in enumerate(prompts)]
    cache_len = 8 + n_new

    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    eng = Engine(cfg, rt, max_batch=slots, cache_len=cache_len,
                 out_cap=n_new)
    eng.generate(reqs)                       # compile
    t0 = time.perf_counter()
    outs = eng.generate(reqs)
    dt_eng = time.perf_counter() - t0
    toks = sum(len(o) for o in outs)
    tasks_served = len({r.task for r in reqs})
    rows.append(emit("serving/engine_mixed_task_continuous",
                     dt_eng / toks * 1e6,
                     f"tok_per_s={toks/dt_eng:.1f},slots={slots},"
                     f"tasks={tasks_served}"))

    # seed path: per-token Python loop, one request shape at a time
    prefill = se.make_prefill(cfg, spec, cache_len)
    step = se.make_serve_step(cfg, spec)

    def one_shot(prompt, task):
        lg, caches, _ = prefill(params["base"], params["adapter"],
                                params["frozen"], prompt[None], None, None,
                                task)
        tok = jnp.argmax(lg[:, -1], axis=-1)[:, None]
        n = 1
        for i in range(n_new - 1):
            lg, caches = step(params["base"], params["adapter"],
                              params["frozen"], tok, caches,
                              jnp.int32(prompt.shape[0] + i), None, task)
            tok = jnp.argmax(lg, axis=-1)[:, None]
            n += 1
        jax.block_until_ready(tok)
        return n

    for p in {int(p.shape[0]): p for p in prompts}.values():
        one_shot(p, jnp.int32(0))            # compile every prompt shape
    t0 = time.perf_counter()
    toks_py = sum(one_shot(p, jnp.int32(r.task))
                  for p, r in zip(prompts, reqs))
    dt_py = time.perf_counter() - t0
    rows.append(emit("serving/python_loop_one_shot", dt_py / toks_py * 1e6,
                     f"tok_per_s={toks_py/dt_py:.1f},"
                     f"speedup_engine={dt_py/toks_py*toks/dt_eng:.2f}x"))


def _fused_engine_rows(rows, *, smoke: bool) -> None:
    """Engine fused-vs-unfused from the SAME dispatch seam: identical
    requests, identical runtime, only ``kernels=`` differs. The fused
    engine's decode loop runs ``tt_linear_batched_a`` (slot-gathered A)
    and the decode-shaped flash kernel; on CPU the Pallas leg runs under
    interpret (correctness emulator), so the derived column also asserts
    token parity — the number that matters off-TPU."""
    n_req, n_new, slots, n_tasks = (3, 5, 2, 2) if smoke else (6, 8, 3, 3)
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run_cfg = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                        adapter_kind="metatt", adapter_variant="4+1d",
                        num_tasks=n_tasks, adapter_rank=8)
    spec = M.build_adapter_spec(run_cfg)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, spec, key)
    params["adapter"] = {"cores": ttlib.random_tt(key, spec.cfg.mode_sizes,
                                                  8, scale=0.5)}
    keys = jax.random.split(key, n_req)
    reqs = [Request(jax.random.randint(keys[i], (4 + i % 3,), 0,
                                       cfg.vocab_size), n_new,
                    task=i % n_tasks) for i in range(n_req)]
    rt = AdapterRuntime.build("lora", params["base"], spec,
                              params["adapter"], params["frozen"])
    outs = {}
    for name, kcfg in (("unfused", None),
                       ("fused_interpret", KernelConfig(backend="pallas",
                                                        interpret=True))):
        # dense mode: the single-token decode path is what the batched-A
        # and decode-flash kernels fuse (the paged path is benchmarked in
        # _paged_rows)
        eng = Engine(cfg, rt, serve=ServeConfig(
            max_batch=slots, cache_len=8 + n_new, out_cap=n_new,
            cache_mode="dense"), kernels=kcfg)
        eng.generate(reqs)               # compile
        t0 = time.perf_counter()
        outs[name] = eng.generate(reqs)
        dt = time.perf_counter() - t0
        toks = sum(len(o) for o in outs[name])
        rows.append(emit(f"serving/engine_{name}", dt / toks * 1e6,
                         f"tok_per_s={toks/dt:.1f},slots={slots},"
                         f"tasks={n_tasks},runtime=lora"))
    parity = all(a.tolist() == b.tolist() for a, b in
                 zip(outs["unfused"], outs["fused_interpret"]))
    rows.append(emit("serving/engine_fused_token_parity", 0.0,
                     f"identical_tokens={parity}"))
    if not parity:
        raise AssertionError(
            "fused engine decode diverged from the unfused path")


def _paged_rows(rows, *, smoke: bool) -> None:
    """Paged vs dense KV cache on a shared-prefix mixed-task workload.

    Half the requests share a common prompt prefix (the multi-task
    deployment shape: one system prompt, many tasks — sharable across
    tasks precisely because ONE MetaTT tensor train serves them all).
    The dense engine reserves max_batch × cache_len up front; the paged
    engine allocates per request and reuses prefix blocks, so its peak
    KV memory (engine.last_stats.kv_bytes_peak) must come in lower and
    its prefix hit rate nonzero. Token parity dense-vs-paged is asserted.
    """
    n_req, n_new, slots = (6, 6, 3) if smoke else (16, 16, 4)
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run_cfg = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                        adapter_kind="metatt", adapter_variant="4+1d",
                        num_tasks=2, adapter_rank=8)
    spec = M.build_adapter_spec(run_cfg)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, spec, key)
    params["adapter"] = {"cores": ttlib.random_tt(key, spec.cfg.mode_sizes,
                                                  8, scale=0.5)}
    rt = AdapterRuntime.build("lora", params["base"], spec,
                              params["adapter"], params["frozen"])
    cache_len = 32 + n_new
    sys_prompt = np.asarray(jax.random.randint(key, (18,), 0,
                                               cfg.vocab_size))
    keys = jax.random.split(key, n_req)
    reqs = []
    for i in range(n_req):
        tail = np.asarray(jax.random.randint(keys[i], (2 + i % 4,), 0,
                                             cfg.vocab_size))
        prompt = (np.concatenate([sys_prompt, tail])
                  if i % 2 == 0 else tail)      # half share the prefix
        reqs.append(Request(prompt, n_new, task=i % 2))

    outs = {}
    for mode in ("dense", "paged"):
        eng = Engine(cfg, rt, serve=ServeConfig(
            max_batch=slots, cache_len=cache_len, out_cap=n_new,
            cache_mode=mode, page_size=8, prefill_chunk=8))
        eng.generate(reqs)                      # compile + warm the cache
        t0 = time.perf_counter()
        outs[mode] = eng.generate(reqs)
        dt = time.perf_counter() - t0
        st = eng.last_stats                     # the engine's own numbers
        rows.append(emit(
            f"serving/engine_{mode}_shared_prefix", dt / max(
                st.tokens_generated, 1) * 1e6,
            f"tok_per_s={st.tokens_per_s:.1f},"
            f"ttft_ms={_p50_ms(eng, 'ttft')},"
            f"tpot_ms={_p50_ms(eng, 'tpot')},"
            f"kv_bytes_peak={st.kv_bytes_peak},"
            f"kv_blocks_peak={st.kv_blocks_peak}/{st.num_blocks},"
            f"prefix_hit_rate={st.prefix_hit_rate:.2f},"
            f"cow={st.cow_copies},waits={st.backpressure_waits},"
            f"decode_traces={st.decode_traces},"
            f"prefill_traces={st.prefill_traces}"))
        _record_stats(f"engine_{mode}_shared_prefix", st)
        print(f"# engine stats [{mode}]: {st.summary(eng.last_results)}")
        if mode == "dense":
            dense_bytes = st.kv_bytes_peak   # the engine's own number
        if mode == "paged":
            parity = all(a.tolist() == b.tolist() for a, b in
                         zip(outs["dense"], outs["paged"]))
            rows.append(emit(
                "serving/paged_vs_dense", 0.0,
                f"identical_tokens={parity},"
                f"kv_bytes_paged={st.kv_bytes_peak},"
                f"kv_bytes_dense={dense_bytes},"
                f"prefix_hit_rate={st.prefix_hit_rate:.2f}"))
            if not parity:
                raise AssertionError("paged engine diverged from dense")
            if not st.prefix_hit_rate > 0:
                raise AssertionError("shared-prefix workload missed the "
                                     "prefix cache")
            if not st.kv_bytes_peak < dense_bytes:
                raise AssertionError(
                    f"paged peak KV {st.kv_bytes_peak} not below dense "
                    f"reservation {dense_bytes}")


def _quant_rows(rows, *, smoke: bool) -> None:
    """fp vs int8 (weights=int8 w8a16 + kv=int8) paged serving on the
    shared-prefix mixed-task workload (DESIGN.md §8).

    Both engines run the same requests at the same ``num_blocks`` budget;
    the int8 run must (a) track the fp run's greedy tokens within the
    documented TOKEN_MATCH_MIN tolerance and (b) report lower peak KV
    bytes (its blocks are int8 cells + per-cell scales — roughly half of
    bf16, a quarter of f32) — both read from engine.last_stats.
    """
    n_req, n_new, slots = (6, 6, 3) if smoke else (16, 16, 4)
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run_cfg = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                        adapter_kind="metatt", adapter_variant="4+1d",
                        num_tasks=2, adapter_rank=8)
    spec = M.build_adapter_spec(run_cfg)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, spec, key)
    params["adapter"] = {"cores": ttlib.random_tt(key, spec.cfg.mode_sizes,
                                                  8, scale=0.5)}
    rt = AdapterRuntime.build("lora", params["base"], spec,
                              params["adapter"], params["frozen"])
    cache_len = 32 + n_new
    sys_prompt = np.asarray(jax.random.randint(key, (18,), 0,
                                               cfg.vocab_size))
    keys = jax.random.split(key, n_req)
    reqs = []
    for i in range(n_req):
        tail = np.asarray(jax.random.randint(keys[i], (2 + i % 4,), 0,
                                             cfg.vocab_size))
        prompt = (np.concatenate([sys_prompt, tail])
                  if i % 2 == 0 else tail)
        reqs.append(Request(prompt, n_new, task=i % 2))

    outs, stats = {}, {}
    for name, qc in (("fp", QuantConfig()),
                     ("int8", QuantConfig(weights="int8", kv="int8"))):
        eng = Engine(cfg, rt, serve=ServeConfig(
            max_batch=slots, cache_len=cache_len, out_cap=n_new,
            page_size=8, prefill_chunk=8, quant=qc))
        eng.generate(reqs)                      # compile + warm the cache
        t0 = time.perf_counter()
        outs[name] = eng.generate(reqs)
        dt = time.perf_counter() - t0
        st = eng.last_stats
        stats[name] = st
        rows.append(emit(
            f"serving/engine_paged_{name}",
            dt / max(st.tokens_generated, 1) * 1e6,
            f"tok_per_s={st.tokens_per_s:.1f},w={st.weights_dtype},"
            f"kv={st.kv_dtype},kv_bytes_peak={st.kv_bytes_peak},"
            f"kv_blocks_peak={st.kv_blocks_peak}/{st.num_blocks},"
            f"block_bytes={st.block_bytes},"
            f"prefix_hit_rate={st.prefix_hit_rate:.2f}"))
        _record_stats(f"engine_paged_{name}", st)
        print(f"# engine stats [{name}]: {st.summary(eng.last_results)}")
    total = sum(len(o) for o in outs["fp"])
    same = sum(int(a == b) for f, q in zip(outs["fp"], outs["int8"])
               for a, b in zip(f.tolist(), q.tolist()))
    match = same / total
    rows.append(emit(
        "serving/int8_vs_fp", 0.0,
        f"token_match={match:.3f},"
        f"kv_bytes_int8={stats['int8'].kv_bytes_peak},"
        f"kv_bytes_fp={stats['fp'].kv_bytes_peak},"
        f"block_bytes_int8={stats['int8'].block_bytes},"
        f"block_bytes_fp={stats['fp'].block_bytes}"))
    if match < TOKEN_MATCH_MIN:
        raise AssertionError(
            f"int8 engine greedy tokens match fp at {match:.3f} < "
            f"{TOKEN_MATCH_MIN} tolerance")
    if not stats["int8"].kv_bytes_peak < stats["fp"].kv_bytes_peak:
        raise AssertionError(
            f"int8 peak KV bytes {stats['int8'].kv_bytes_peak} not below "
            f"fp {stats['fp'].kv_bytes_peak} at equal num_blocks")


def _mesh_rows(rows, *, smoke: bool, mesh_shape=(1, 4)) -> None:
    """Tensor-parallel vs single-device paged serving (DESIGN.md §9) on
    the shared-prefix mixed-task workload.

    Both engines serve identical requests; the TP engine shards the KV
    pools on the kv-head axis over the "model" mesh axis. Asserted from
    the engines' own stats: token identity (greedy decode is bitwise
    deterministic under the head/vocab-stripe sharding), global KV
    accounting unchanged, and per-shard peak KV bytes == global / tp.
    """
    tp = int(np.prod(mesh_shape))
    if jax.device_count() < tp:
        raise SystemExit(
            f"--mesh needs {tp} devices; on CPU run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp}")
    n_req, n_new, slots = (6, 6, 3) if smoke else (16, 16, 4)
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run_cfg = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                        adapter_kind="metatt", adapter_variant="4+1d",
                        num_tasks=2, adapter_rank=8)
    spec = M.build_adapter_spec(run_cfg)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, spec, key)
    params["adapter"] = {"cores": ttlib.random_tt(key, spec.cfg.mode_sizes,
                                                  8, scale=0.5)}
    rt = AdapterRuntime.build("lora", params["base"], spec,
                              params["adapter"], params["frozen"])
    cache_len = 32 + n_new
    sys_prompt = np.asarray(jax.random.randint(key, (18,), 0,
                                               cfg.vocab_size))
    keys = jax.random.split(key, n_req)
    reqs = []
    for i in range(n_req):
        tail = np.asarray(jax.random.randint(keys[i], (2 + i % 4,), 0,
                                             cfg.vocab_size))
        prompt = (np.concatenate([sys_prompt, tail])
                  if i % 2 == 0 else tail)
        reqs.append(Request(prompt, n_new, task=i % 2))

    outs, stats = {}, {}
    for label, mesh in (("tp1", ()), (f"tp{tp}", tuple(mesh_shape))):
        eng = Engine(cfg, rt, serve=ServeConfig(
            max_batch=slots, cache_len=cache_len, out_cap=n_new,
            page_size=8, prefill_chunk=8, mesh_shape=mesh))
        eng.generate(reqs)                      # compile + warm the cache
        t0 = time.perf_counter()
        outs[label] = eng.generate(reqs)
        dt = time.perf_counter() - t0
        st = eng.last_stats
        stats[label] = st
        rows.append(emit(
            f"serving/engine_{label}",
            dt / max(st.tokens_generated, 1) * 1e6,
            f"tok_per_s={st.tokens_per_s:.1f},shards={st.shards},"
            f"kv_bytes_peak={st.kv_bytes_peak},"
            f"kv_bytes_peak_per_shard={st.kv_bytes_peak_per_shard},"
            f"prefix_hit_rate={st.prefix_hit_rate:.2f}"))
        _record_stats(f"engine_{label}", st)
        print(f"# engine stats [{label}]: {st.summary(eng.last_results)}")
    t1, t4 = stats["tp1"], stats[f"tp{tp}"]
    parity = all(a.tolist() == b.tolist() for a, b in
                 zip(outs["tp1"], outs[f"tp{tp}"]))
    rows.append(emit(
        f"serving/tp{tp}_vs_tp1", 0.0,
        f"identical_tokens={parity},shards={t4.shards},"
        f"kv_bytes_peak={t4.kv_bytes_peak},"
        f"kv_bytes_peak_per_shard={t4.kv_bytes_peak_per_shard},"
        f"tok_per_s_tp1={t1.tokens_per_s:.1f},"
        f"tok_per_s_tp{tp}={t4.tokens_per_s:.1f}"))
    if not parity:
        raise AssertionError("sharded engine diverged from single-device")
    if t4.kv_bytes_peak != t1.kv_bytes_peak:
        raise AssertionError("global KV accounting changed under TP")
    if t4.kv_bytes_peak_per_shard * t4.shards != t4.kv_bytes_peak:
        raise AssertionError("per-shard KV bytes do not sum to global")


def _fleet_rows(rows, *, smoke: bool, mesh_shape=(2, 4)) -> None:
    """Data-striped vs single-replica paged serving at fixed TP width
    (DESIGN.md §11) on the shared-prefix mixed-task workload.

    Both engines serve identical requests through the same tp-wide
    shard groups; the dp2 engine stripes requests over two decode
    replicas (deterministic least-loaded routing), each owning a
    private stripe of the slots and the block pool. Asserted from the
    engines' own stats: token identity, per-replica block accounting
    (every replica's peak stays inside its private ``num_blocks / dp``
    budget and the striped pool leaves physically hold 1/dp of the
    global blocks per data shard), and unchanged global KV accounting.
    The compile (first traced) call is excluded from every wall.
    """
    dp, tp = int(mesh_shape[0]), int(mesh_shape[1])
    if jax.device_count() < dp * tp:
        raise SystemExit(
            f"--fleet needs {dp * tp} devices; on CPU run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={dp * tp}")
    n_req, n_new, slots = (6, 6, 3) if smoke else (16, 16, 4)
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run_cfg = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                        adapter_kind="metatt", adapter_variant="4+1d",
                        num_tasks=2, adapter_rank=8)
    spec = M.build_adapter_spec(run_cfg)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, spec, key)
    params["adapter"] = {"cores": ttlib.random_tt(key, spec.cfg.mode_sizes,
                                                  8, scale=0.5)}
    rt = AdapterRuntime.build("lora", params["base"], spec,
                              params["adapter"], params["frozen"])
    cache_len = 32 + n_new
    sys_prompt = np.asarray(jax.random.randint(key, (18,), 0,
                                               cfg.vocab_size))
    keys = jax.random.split(key, n_req)
    reqs = []
    for i in range(n_req):
        tail = np.asarray(jax.random.randint(keys[i], (2 + i % 4,), 0,
                                             cfg.vocab_size))
        prompt = (np.concatenate([sys_prompt, tail])
                  if i % 2 == 0 else tail)
        reqs.append(Request(prompt, n_new, task=i % 2))

    outs, stats, pools = {}, {}, {}
    for label, mesh in (("dp1", (1, tp)), (f"dp{dp}", (dp, tp))):
        eng = Engine(cfg, rt, serve=ServeConfig(
            max_batch=slots, cache_len=cache_len, out_cap=n_new,
            page_size=8, prefill_chunk=8, mesh_shape=mesh))
        eng.generate(reqs)      # compile — excluded from the timed wall
        t0 = time.perf_counter()
        outs[label] = eng.generate(reqs)
        dt = time.perf_counter() - t0
        st = eng.last_stats
        stats[label] = st
        pools[label] = eng._paged_caches
        rows.append(emit(
            f"serving/engine_fleet_{label}",
            dt / max(st.tokens_generated, 1) * 1e6,
            f"tok_per_s={st.tokens_per_s:.1f},dp={st.data_shards},"
            f"shards={st.shards},ttft_ms={_p50_ms(eng, 'ttft')},"
            f"tpot_ms={_p50_ms(eng, 'tpot')},"
            f"kv_bytes_peak={st.kv_bytes_peak},"
            f"kv_blocks_peak={st.kv_blocks_peak}/{st.num_blocks}"))
        _record_stats(f"engine_fleet_{label}", st)
        print(f"# engine stats [{label}]: {st.summary(eng.last_results)}")
    d1, d2 = stats["dp1"], stats[f"dp{dp}"]
    parity = all(a.tolist() == b.tolist() for a, b in
                 zip(outs["dp1"], outs[f"dp{dp}"]))
    reps = [r for r in d2.replica_stats if r["replica"] >= 0]
    per_replica_blocks = d2.num_blocks // dp
    rep_peak_bytes = [r["kv_blocks_peak"] * d2.block_bytes for r in reps]
    rows.append(emit(
        f"serving/dp{dp}_vs_dp1", 0.0,
        f"identical_tokens={parity},dp={d2.data_shards},"
        f"replica_kv_bytes_peak={'|'.join(map(str, rep_peak_bytes))},"
        f"replica_block_budget={per_replica_blocks},"
        f"tok_per_s_dp1={d1.tokens_per_s:.1f},"
        f"tok_per_s_dp{dp}={d2.tokens_per_s:.1f},"
        f"ttft_ms_dp{dp}={_p50_ms(eng, 'ttft')},"
        f"tpot_ms_dp{dp}={_p50_ms(eng, 'tpot')}"))
    if not parity:
        raise AssertionError("dp-striped engine diverged from dp1")
    if len(reps) != dp or sorted(r["replica"] for r in reps) != list(
            range(dp)):
        raise AssertionError(f"expected {dp} replica stats, got {reps}")
    if sum(r["admitted"] for r in reps) != len(reqs):
        raise AssertionError("replica admissions do not cover the batch")
    for r in reps:
        if not 0 < r["kv_blocks_peak"] <= per_replica_blocks:
            raise AssertionError(
                f"replica {r['replica']} peak {r['kv_blocks_peak']} "
                f"outside its private budget {per_replica_blocks}")
    if d2.block_bytes != d1.block_bytes:
        raise AssertionError("per-block bytes changed under dp striping")
    for leaf in jax.tree_util.tree_leaves(pools[f"dp{dp}"]):
        if leaf.addressable_shards[0].data.shape[1] * dp != leaf.shape[1]:
            raise AssertionError(
                "pool leaves are not physically striped 1/dp per data "
                f"shard: {leaf.addressable_shards[0].data.shape} of "
                f"{leaf.shape}")


def _multitask_rows(rows, *, smoke: bool) -> None:
    """Paged adapter registry (DESIGN.md §12): a zipf(1.1) stream over
    256 distinct tasks served through an 8-slot device pool vs the
    all-resident engine.

    The workload is the registry's design point — a long-tailed task
    popularity where a handful of hot tasks cover most admissions (high
    hit rate) while the cold tail still faults through the pool. Token
    identity against the all-resident engine is asserted outright;
    ``decode_traces`` must stay 1 (fault-ins are one pre-jitted donated
    scatter, never a retrace). Throughput of the pooled engine must stay
    within 15% of all-resident (asserted in the full run only — smoke
    shapes on CPU are timing noise).
    """
    n_tasks, n_slots = 256, 8
    n_req, n_new = (48, 4) if smoke else (192, 8)
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run_cfg = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                        adapter_kind="metatt", adapter_variant="4+1d",
                        num_tasks=n_tasks, adapter_rank=8)
    spec = M.build_adapter_spec(run_cfg)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, spec, key)
    params["adapter"] = {"cores": ttlib.random_tt(key, spec.cfg.mode_sizes,
                                                  8, scale=0.5)}
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    # zipf(1.1) by explicit rank probabilities (bounded support, unlike
    # rng.zipf): task id == popularity rank
    rng = np.random.default_rng(0)
    p = 1.0 / np.arange(1, n_tasks + 1) ** 1.1
    tasks = rng.choice(n_tasks, size=n_req, p=p / p.sum())
    keys = jax.random.split(key, n_req)
    reqs = [Request(np.asarray(jax.random.randint(
        keys[i], (4 + i % 4,), 0, cfg.vocab_size)), n_new,
        task=int(tasks[i])) for i in range(n_req)]

    outs, stats = {}, {}
    for label, reg in (("all_resident", RegistryConfig()),
                       (f"pool{n_slots}",
                        RegistryConfig(max_resident_tasks=n_slots))):
        eng = Engine(cfg, rt, serve=ServeConfig(
            max_batch=4, cache_len=16 + n_new, out_cap=n_new, page_size=8,
            prefill_chunk=8, registry=reg))
        eng.generate(reqs)      # compile — excluded from the timed wall
        t0 = time.perf_counter()
        outs[label] = eng.generate(reqs)
        dt = time.perf_counter() - t0
        st = eng.last_stats
        stats[label] = st
        rows.append(emit(
            f"serving/engine_multitask_{label}",
            dt / max(st.tokens_generated, 1) * 1e6,
            f"tok_per_s={st.tokens_per_s:.1f},"
            f"tasks={n_tasks},slots={st.max_resident_tasks},"
            f"adapter_hit_rate={st.adapter_hit_rate:.2f},"
            f"adapter_faults={st.adapter_faults},"
            f"adapter_evictions={st.adapter_evictions},"
            f"decode_traces={st.decode_traces}"))
        _record_stats(f"engine_multitask_{label}", st)
        print(f"# engine stats [{label}]: {st.summary(eng.last_results)}")
    full, pool = stats["all_resident"], stats[f"pool{n_slots}"]
    parity = all(a.tolist() == b.tolist() for a, b in
                 zip(outs["all_resident"], outs[f"pool{n_slots}"]))
    ratio = pool.tokens_per_s / max(full.tokens_per_s, 1e-9)
    rows.append(emit(
        "serving/zipf_256tasks", 0.0,
        f"identical_tokens={parity},tasks={n_tasks},slots={n_slots},"
        f"zipf_a=1.1,requests={n_req},"
        f"adapter_hit_rate={pool.adapter_hit_rate:.2f},"
        f"adapter_faults={pool.adapter_faults},"
        f"adapter_evictions={pool.adapter_evictions},"
        f"adapter_waits={pool.adapter_waits},"
        f"tok_per_s_all={full.tokens_per_s:.1f},"
        f"tok_per_s_pool={pool.tokens_per_s:.1f},"
        f"tok_per_s_ratio={ratio:.2f}"))
    if not parity:
        raise AssertionError(
            "pooled-registry engine diverged from all-resident")
    if pool.decode_traces != 1:
        raise AssertionError(
            f"adapter fault-ins retraced the decode graph: "
            f"decode_traces={pool.decode_traces}")
    if pool.adapter_faults == 0 or pool.adapter_hits == 0:
        raise AssertionError(
            "zipf workload should both fault (cold tail) and hit (hot "
            f"head): faults={pool.adapter_faults} hits={pool.adapter_hits}")
    if not smoke and ratio < 0.85:
        raise AssertionError(
            f"pooled throughput {ratio:.2f}x all-resident — outside the "
            "15% budget")


def _decaying_tt(key, mode_sizes, rank, scale, decay):
    """Random TT whose bond strength decays geometrically — the spectrum
    shape DMRG rank adaptation produces on trained adapters (and the
    regime where rank-truncated drafters track the target; a flat random
    spectrum makes truncation a valid but useless approximation)."""
    cores = ttlib.random_tt(key, mode_sizes, rank, scale=scale)
    w = decay ** jnp.arange(rank)
    out = []
    for i, c in enumerate(cores):
        if i == 0:
            out.append(c * w[None, :])
        else:
            shape = [1] * c.ndim
            shape[0] = c.shape[0]
            out.append(c * w[: c.shape[0]].reshape(shape))
    return out


def _spec_rows(rows, *, smoke: bool) -> None:
    """Speculative decode (rank-truncated + layer-strided TT self-drafter,
    DESIGN.md §10) vs the plain paged engine on the shared-prefix
    workload.

    The random-weight smoke model is made REPRESENTATIVE of the regime
    speculation targets: the adapter's TT cores get a geometrically
    decaying bond spectrum (what DMRG rank adaptation yields on trained
    adapters — so the rank-truncated drafter tracks the target) and the
    base's block output projections are damped so each block is a small
    residual perturbation (trained-network shape — so the layer-strided
    drafter stays close). Asserted, all from engine.last_stats + outputs:
    greedy token IDENTITY with speculation on (the accept rule only
    commits verifier-argmax prefixes), acceptance_rate > 0.5, and
    tokens/sec strictly above the non-speculative baseline (best-of-3
    walls — the drafter runs half the layers, so k drafts + 1 verify
    cost less than k+1 target passes)."""
    import jax.tree_util as jtu
    n_req, n_new, slots = (6, 16, 3) if smoke else (8, 24, 4)
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run_cfg = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                        adapter_kind="metatt", adapter_variant="4+1d",
                        num_tasks=2, adapter_rank=8)
    spec = M.build_adapter_spec(run_cfg)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, spec, key)
    params["adapter"] = {"cores": _decaying_tt(key, spec.cfg.mode_sizes,
                                               8, 0.5, 0.35)}
    blocks = jtu.tree_map_with_path(
        lambda p, a: a * 0.05 if any(getattr(k, "key", None) in
                                     ("wo", "wd") for k in p) else a,
        params["base"]["blocks"])
    base = dict(params["base"])
    base["blocks"] = blocks
    rt = AdapterRuntime.build("live", base, spec, params["adapter"],
                              params["frozen"])
    cache_len = 32 + n_new
    sys_prompt = np.asarray(jax.random.randint(key, (18,), 0,
                                               cfg.vocab_size))
    keys = jax.random.split(key, n_req)
    reqs = []
    for i in range(n_req):
        tail = np.asarray(jax.random.randint(keys[i], (2 + i % 4,), 0,
                                             cfg.vocab_size))
        prompt = (np.concatenate([sys_prompt, tail])
                  if i % 2 == 0 else tail)
        reqs.append(Request(prompt, n_new, task=i % 2))

    from repro.config.base import SpecConfig
    outs, walls, stats = {}, {}, {}
    for label, sc in (("base", SpecConfig()),
                      ("spec", SpecConfig(spec_k=3, draft_rank=4,
                                          draft_layer_stride=2))):
        eng = Engine(cfg, rt, serve=ServeConfig(
            max_batch=slots, cache_len=cache_len, out_cap=n_new,
            page_size=8, prefill_chunk=8, spec=sc))
        eng.generate(reqs)                      # compile + warm the cache
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            outs[label] = eng.generate(reqs)
            best = min(best, time.perf_counter() - t0)
        walls[label] = best
        st = eng.last_stats
        stats[label] = st
        rows.append(emit(
            f"serving/engine_{label}_speculative"
            if label == "spec" else "serving/engine_no_spec",
            best / max(st.tokens_generated, 1) * 1e6,
            f"tok_per_s={st.tokens_generated/best:.1f},"
            f"spec_k={st.spec_k},accept={st.acceptance_rate:.3f},"
            f"tok_per_step={st.tokens_per_step:.2f},"
            f"decode_traces={st.decode_traces}"))
        _record_stats(f"engine_{label}_spec_workload", st)
        print(f"# engine stats [{label}]: {st.summary(eng.last_results)}")
    parity = all(a.tolist() == b.tolist() for a, b in
                 zip(outs["base"], outs["spec"]))
    accept = stats["spec"].acceptance_rate
    speedup = walls["base"] / walls["spec"]
    rows.append(emit(
        "serving/spec_vs_base", 0.0,
        f"identical_tokens={parity},accept={accept:.3f},"
        f"speedup={speedup:.2f}x,spec_k=3,draft_rank=4,"
        f"draft_layer_stride=2,"
        f"tok_per_step={stats['spec'].tokens_per_step:.2f}"))
    if not parity:
        raise AssertionError(
            "speculative greedy decode diverged from the baseline engine")
    if not accept > 0.5:
        raise AssertionError(
            f"drafter acceptance {accept:.3f} <= 0.5 on the decaying-"
            "spectrum workload")
    if not speedup > 1.0:
        raise AssertionError(
            f"speculative engine not faster: {speedup:.2f}x <= 1.0")


def _chaos_rows(rows, *, smoke: bool) -> None:
    """Serving resilience smoke (DESIGN.md §13): the shared-prefix
    workload under a seeded chaos schedule — forced allocation
    failures, one scripted cancel, one NaN-logit injection — vs the
    fault-free run.

    Asserted: every SURVIVOR (request not deliberately killed) is
    token-identical to the fault-free run, the cancelled / failed
    requests carry the right terminal status, ``decode_traces`` stays
    1 (aborts and the NaN guard ride the one compiled graph), and the
    per-step pool audits (run by the injector on every host-loop
    iteration) plus the at-rest audit hold — zero leaked blocks, zero
    leaked adapter pins. The ``serving/chaos_survivors`` row records
    what fired and what survived.
    """
    from repro.serving import FINISHED, ChaosInjector, audit
    n_req, n_new, slots = (6, 6, 3) if smoke else (12, 12, 4)
    cfg = registry.get_smoke_config("stablelm-1.6b")
    run_cfg = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                        adapter_kind="metatt", adapter_variant="4+1d",
                        num_tasks=2, adapter_rank=8)
    spec = M.build_adapter_spec(run_cfg)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, spec, key)
    params["adapter"] = {"cores": ttlib.random_tt(key, spec.cfg.mode_sizes,
                                                  8, scale=0.5)}
    rt = AdapterRuntime.build("lora", params["base"], spec,
                              params["adapter"], params["frozen"])
    cache_len = 32 + n_new
    keys = jax.random.split(key, n_req)
    reqs = [Request(np.asarray(jax.random.randint(
        keys[i], (4 + i % 4,), 0, cfg.vocab_size)), n_new, task=i % 2,
        request_id=f"r{i}") for i in range(n_req)]

    sv = ServeConfig(max_batch=slots, cache_len=cache_len, out_cap=n_new,
                     page_size=8, prefill_chunk=8)
    eng = Engine(cfg, rt, serve=sv)
    baseline = eng.generate(reqs)           # compile + fault-free tokens
    chaos = ChaosInjector(seed=0, alloc_fail_steps=(0, 1),
                          alloc_fail_rate=0.2,
                          cancel_at={1: ["r1"]},
                          nan_after={"r3": 1})
    t0 = time.perf_counter()
    out = eng.generate(reqs, chaos=chaos)
    dt = time.perf_counter() - t0
    st = eng.last_stats
    audit(eng)                              # at rest: drained, zero pins
    victims = {"r1", "r3"}
    survivors = [i for i in range(n_req)
                 if reqs[i].request_id not in victims]
    identical = all(out[i].tolist() == baseline[i].tolist()
                    for i in survivors)
    statuses = [r.status for r in eng.last_results]
    rows.append(emit(
        "serving/chaos_survivors",
        dt / max(st.tokens_generated, 1) * 1e6,
        f"survivors_identical={identical},"
        f"survivors={len(survivors)}/{n_req},"
        f"alloc_faults={chaos.alloc_faults},"
        f"cancelled={st.cancelled},nan_faults={st.numerics_faults},"
        f"failed={st.failed_requests},waits={st.backpressure_waits},"
        f"decode_traces={st.decode_traces}"))
    _record_stats("engine_chaos_survivors", st)
    print(f"# engine stats [chaos]: {st.summary(eng.last_results)}")
    if not identical:
        raise AssertionError(
            "chaos perturbed a survivor's tokens — scheduling faults "
            "must never change math")
    if statuses[1] != "CANCELLED" or statuses[3] != "FAILED":
        raise AssertionError(
            f"victim statuses wrong: r1={statuses[1]} r3={statuses[3]}")
    if any(statuses[i] != FINISHED for i in survivors):
        raise AssertionError(f"survivor not FINISHED: {statuses}")
    if chaos.alloc_faults == 0:
        raise AssertionError("chaos schedule never fired an alloc fault")
    if st.decode_traces != 1:
        raise AssertionError(
            f"chaos retraced the decode graph: {st.decode_traces}")


def _merge_rows_into_json(rows) -> None:
    """Merge freshly produced CSV rows (+ ENGINE_STATS) into
    BENCH_serving.json in place — rows with the same name are replaced,
    everything else is preserved, so the ``--mesh`` job composes with
    ``run.py --json`` regardless of execution order."""
    import json
    import os
    from benchmarks.run import REPO_ROOT, _row_dicts
    path = os.path.join(REPO_ROOT, "BENCH_serving.json")
    payload = {"rows": [], "engine_stats": []}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    new = _row_dicts(rows)
    names = {r["name"] for r in new}
    payload["rows"] = [r for r in payload.get("rows", [])
                       if r["name"] not in names] + new
    labels = {s["label"] for s in ENGINE_STATS}
    payload["engine_stats"] = [s for s in payload.get("engine_stats", [])
                               if s.get("label") not in labels]
    payload["engine_stats"] += ENGINE_STATS
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"# merged {sorted(names)} into {path}", flush=True)


def run_mesh(*, smoke: bool = False) -> list:
    """The ``--mesh`` entry point: only the TP-vs-single-device rows
    (CI runs this as its own job, with --smoke, under forced fake
    devices)."""
    ENGINE_STATS.clear()
    rows = []
    _mesh_rows(rows, smoke=smoke)
    _merge_rows_into_json(rows)
    return rows


def run_fleet(*, smoke: bool = False) -> list:
    """The ``--fleet`` entry point: only the dp2-vs-dp1 rows (the
    scripts/ci.sh fleet-parity job runs this with --smoke under 8
    forced fake devices)."""
    ENGINE_STATS.clear()
    rows = []
    _fleet_rows(rows, smoke=smoke)
    _merge_rows_into_json(rows)
    return rows


def run_spec(*, smoke: bool = False) -> list:
    """The ``--spec`` entry point: only the speculative-vs-baseline rows,
    merged into BENCH_serving.json (the scripts/ci.sh spec-parity job)."""
    ENGINE_STATS.clear()
    rows = []
    _spec_rows(rows, smoke=smoke)
    _merge_rows_into_json(rows)
    return rows


def run_multitask(*, smoke: bool = False) -> list:
    """The ``--multitask`` entry point: zipf-over-256-tasks adapter
    paging rows only (the scripts/ci.sh adapter-paging job runs this
    with --smoke; merges serving/zipf_256tasks into
    BENCH_serving.json)."""
    ENGINE_STATS.clear()
    rows = []
    _multitask_rows(rows, smoke=smoke)
    _merge_rows_into_json(rows)
    return rows


def run_chaos(*, smoke: bool = False) -> list:
    """The ``--chaos`` entry point: the seeded-chaos survivor row only
    (the scripts/ci.sh chaos-parity job runs this with --smoke; merges
    serving/chaos_survivors into BENCH_serving.json)."""
    ENGINE_STATS.clear()
    rows = []
    _chaos_rows(rows, smoke=smoke)
    _merge_rows_into_json(rows)
    return rows


def run(*, smoke: bool = False) -> list:
    ENGINE_STATS.clear()
    rows = []
    _decode_step_rows(rows)
    _engine_rows(rows, smoke=smoke)
    _fused_engine_rows(rows, smoke=smoke)
    _paged_rows(rows, smoke=smoke)
    _quant_rows(rows, smoke=smoke)
    _spec_rows(rows, smoke=smoke)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes for CI")
    ap.add_argument("--mesh", action="store_true",
                    help="tensor-parallel vs single-device rows only "
                         "(needs 4 devices; merges serving/tp4_vs_tp1 "
                         "into BENCH_serving.json; honors --smoke)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative-vs-baseline rows only (merges "
                         "serving/spec_vs_base into BENCH_serving.json; "
                         "honors --smoke)")
    ap.add_argument("--fleet", action="store_true",
                    help="data-striped dp2 vs dp1 rows only (needs 8 "
                         "devices; merges serving/dp2_vs_dp1 into "
                         "BENCH_serving.json; honors --smoke)")
    ap.add_argument("--multitask", action="store_true",
                    help="zipf(1.1) over 256 tasks through an 8-slot "
                         "adapter pool vs all-resident (merges "
                         "serving/zipf_256tasks into BENCH_serving.json; "
                         "honors --smoke)")
    ap.add_argument("--chaos", action="store_true",
                    help="seeded chaos survivor row only (merges "
                         "serving/chaos_survivors into "
                         "BENCH_serving.json; honors --smoke)")
    args = ap.parse_args()
    if args.mesh:
        print("name,us_per_call,derived")
        run_mesh(smoke=args.smoke)
    elif args.fleet:
        print("name,us_per_call,derived")
        run_fleet(smoke=args.smoke)
    elif args.multitask:
        print("name,us_per_call,derived")
        run_multitask(smoke=args.smoke)
    elif args.chaos:
        print("name,us_per_call,derived")
        run_chaos(smoke=args.smoke)
    elif args.spec:
        print("name,us_per_call,derived")
        run_spec(smoke=args.smoke)
    else:
        run(smoke=args.smoke)
