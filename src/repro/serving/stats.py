"""Lightweight per-``generate`` engine observability.

The engine fills one ``EngineStats`` per ``generate`` call and keeps it on
``engine.last_stats``; ``benchmarks/bench_serving.py`` and
``examples/serve.py`` print it. Everything here is host-side counting —
no device syncs beyond what the engine already does. Per-request times
live on ``engine.last_results`` (``RequestResult.t_*``);
``latency_quantiles`` reduces them.

Byte accounting is GLOBAL (all shards): ``block_bytes`` / ``kv_bytes_peak``
describe the whole logical cache regardless of the serve mesh, so
paged-vs-dense and int8-vs-fp comparisons read identically on a mesh of 1
and on a TP mesh. ``shards`` records how many ways the kv-head axis is
sharded (1 without a mesh); the ``*_per_shard`` properties divide the
global figures down to what one device actually holds (DESIGN.md §9).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class EngineStats:
    """Counters for one ``Engine.generate`` call (all host-side ints /
    floats; derived rates are properties so serialized dicts stay flat)."""
    cache_mode: str = "paged"
    requests: int = 0
    tokens_generated: int = 0
    wall_s: float = 0.0
    admitted: int = 0
    evicted: int = 0
    decode_calls: int = 0        # host->device decode-loop invocations
    decode_steps: int = 0        # decode-loop iterations (engine steps)
    decode_call_log: list = dataclasses.field(default_factory=list)
    #   paged mode, one tuple per decode call: (t_dispatch, t_return,
    #   steps_before, steps_after) — perf_counter seconds around the call
    #   and its wait, and the worker's loop-step count before and after
    decode_traces: int = 0       # jit (re)traces of the decode graph
    prefill_traces: int = 0      # dense mode: per-bucket prefill compiles
    # --- quantization (DESIGN.md §8) ---
    weights_dtype: str = "fp"    # "fp" | "int8" — frozen base matmul leaves
    kv_dtype: str = "fp"         # "fp" | "int8" — KV cache cells
    # --- KV memory (GLOBAL, all-shard bytes — see module docstring) ---
    page_size: int = 0
    num_blocks: int = 0          # pool budget (paged) / dense equivalent
    kv_blocks_peak: int = 0      # max blocks simultaneously in use
    block_bytes: int = 0         # global device bytes per block (all
    #                              layers, k+v, + per-cell scales in int8
    #                              mode; every shard holds 1/shards of it)
    shards: int = 1              # kv-head shards ("model" axis size; 1 =
    #                              single device, DESIGN.md §9)
    # --- fleet serving (DESIGN.md §11) ---
    data_shards: int = 1         # decode replicas ("data" axis size)
    replica_stats: list = dataclasses.field(default_factory=list)
    #   one dict per data replica: {"replica", "admitted", "evicted",
    #   "queue_depth" (requests still pending at the end of generate —
    #   0 unless generate aborted), "backpressure_waits",
    #   "kv_blocks_peak"}. Populated for every paged generate (one entry
    #   on a mesh of 1); under disaggregation the prefill worker reports
    #   as replica -1 with an extra "handoffs" count.
    # --- prefix cache ---
    prefix_lookups: int = 0      # admissions that consulted the cache
    prefix_hit_tokens: int = 0   # prompt tokens served from cached blocks
    prefix_lookup_tokens: int = 0  # prompt tokens eligible for reuse
    cow_copies: int = 0          # copy-on-write block copies
    cache_evictions: int = 0     # prefix blocks reclaimed under pressure
    # --- scheduler ---
    backpressure_waits: int = 0  # admissions deferred for lack of blocks
    #                              or of an adapter slot
    # --- adapter registry (DESIGN.md §12) ---
    max_resident_tasks: int = 0  # device task-slot pool size per replica
    #                              (0 = whole task axis resident, registry
    #                              off — the adapter_* counters stay 0)
    adapter_hits: int = 0        # admissions whose task was already pooled
    adapter_faults: int = 0      # host->device task-slice fault-ins
    adapter_evictions: int = 0   # idle residents displaced by a fault
    adapter_waits: int = 0       # admissions deferred: all slots pinned
    #                              (also counted in backpressure_waits)
    # --- speculative decode (DESIGN.md §10) ---
    spec_k: int = 0              # drafts per engine step (0 = spec off)
    draft_tokens: int = 0        # drafter proposals (active decode rows)
    accepted_tokens: int = 0     # proposals the verifier accepted
    # --- resilience (request lifecycle / failover, DESIGN.md §13) ---
    cancelled: int = 0           # requests ended by Engine.cancel
    timeouts: int = 0            # requests ended by their deadline_s
    preemptions: int = 0         # recompute preemptions (victim re-queued)
    failed_requests: int = 0     # requests ended with status FAILED
    numerics_faults: int = 0     # in-graph NaN/inf logit detections
    replicas_lost: int = 0       # replicas drained via Router.mark_down
    failover_requests: int = 0   # in-flight/queued requests re-routed off
    #                              a dead replica (recompute re-admission)

    @property
    def tokens_per_s(self) -> float:
        """Generated tokens / wall seconds of the generate call (0.0
        before any timed run)."""
        return self.tokens_generated / self.wall_s if self.wall_s else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of reuse-eligible prompt tokens served from cached
        blocks (0.0 when nothing was eligible)."""
        if not self.prefix_lookup_tokens:
            return 0.0
        return self.prefix_hit_tokens / self.prefix_lookup_tokens

    @property
    def kv_bytes_peak(self) -> int:
        """Peak GLOBAL (all-shard) KV bytes in use:
        ``kv_blocks_peak * block_bytes``."""
        return self.kv_blocks_peak * self.block_bytes

    @property
    def block_bytes_per_shard(self) -> int:
        """Device bytes one shard holds per block — the kv-head axis is
        sharded ``shards`` ways, every other dim whole, so this is
        exactly ``block_bytes / shards``."""
        return self.block_bytes // max(self.shards, 1)

    @property
    def kv_bytes_peak_per_shard(self) -> int:
        """Peak KV bytes resident on ONE device:
        ``kv_blocks_peak * block_bytes_per_shard`` (== global peak on a
        mesh of 1; ≈ global / |model| under TP)."""
        return self.kv_blocks_peak * self.block_bytes_per_shard

    @property
    def adapter_hit_rate(self) -> float:
        """Fraction of admissions whose task slice was already in the
        device pool (0.0 when the registry is off or nothing admitted)."""
        n = self.adapter_hits + self.adapter_faults
        return self.adapter_hits / n if n else 0.0

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafter proposals the verifier accepted (0.0 when
        speculation is off or no decode steps ran)."""
        if not self.draft_tokens:
            return 0.0
        return self.accepted_tokens / self.draft_tokens

    @property
    def tokens_per_step(self) -> float:
        """Committed tokens per decode-loop iteration: how full the
        batch runs, and with speculation above 1.0 per slot (chunked
        prefill steps count too, so long prompts dilute it)."""
        if not self.decode_steps:
            return 0.0
        return self.tokens_generated / self.decode_steps

    def summary(self, results=()) -> str:
        """One-line human-readable digest (printed by examples/serve.py
        and bench_serving). ``results`` (``engine.last_results``) adds
        the p50/p95 time to first token and per output token."""
        lat = latency_quantiles(results)
        return (f"mode={self.cache_mode} w={self.weights_dtype} "
                f"kv={self.kv_dtype} shards={self.shards} "
                + (f"dp={self.data_shards} " if self.data_shards > 1
                   else "")
                + f"reqs={self.requests} "
                f"toks={self.tokens_generated} "
                f"tok/s={self.tokens_per_s:.1f} "
                f"tok/step={self.tokens_per_step:.2f} "
                + "".join(f"{k}={v * 1e3:.1f}ms " for k, v in lat.items())
                + f"kv_blocks_peak={self.kv_blocks_peak}/{self.num_blocks} "
                f"kv_bytes_peak={self.kv_bytes_peak} "
                f"(per_shard={self.kv_bytes_peak_per_shard}) "
                f"prefix_hit_rate={self.prefix_hit_rate:.2f} "
                f"cow={self.cow_copies} admits={self.admitted} "
                f"evicts={self.evicted} waits={self.backpressure_waits} "
                f"decode_traces={self.decode_traces} "
                f"prefill_traces={self.prefill_traces}"
                + (f" adapters={self.max_resident_tasks}slots "
                   f"hit={self.adapter_hit_rate:.2f} "
                   f"faults={self.adapter_faults} "
                   f"aevicts={self.adapter_evictions} "
                   f"awaits={self.adapter_waits}"
                   if self.max_resident_tasks else "")
                + (f" spec_k={self.spec_k} "
                   f"accept={self.acceptance_rate:.2f}"
                   if self.spec_k else "")
                + (f" cancelled={self.cancelled} "
                   f"timeouts={self.timeouts} "
                   f"preempts={self.preemptions} "
                   f"failed={self.failed_requests} "
                   f"nan_faults={self.numerics_faults} "
                   f"replicas_lost={self.replicas_lost} "
                   f"failover_reqs={self.failover_requests}"
                   if (self.cancelled or self.timeouts or self.preemptions
                       or self.failed_requests or self.numerics_faults
                       or self.replicas_lost) else ""))


def latency_quantiles(results) -> dict:
    """p50 and p95 seconds of time to first token (from submission) and
    of time per output token after the first, over ``RequestResult``s
    (``engine.last_results``); a quantity no request reached is left
    out."""
    ttft = [r.t_first - r.t_submit for r in results
            if r.t_first is not None and r.t_submit is not None]
    tpot = [(r.t_done - r.t_first) / (r.n_generated - 1) for r in results
            if r.t_first is not None and r.t_done is not None
            and r.n_generated > 1]
    out = {}
    for name, xs in (("ttft", ttft), ("tpot", tpot)):
        if xs:
            p50, p95 = np.percentile(xs, [50, 95])
            out[f"{name}_p50"], out[f"{name}_p95"] = float(p50), float(p95)
    return out
