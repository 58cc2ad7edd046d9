"""Continuous-batching serving engine with a paged KV cache.

Architecture (README §Serving, DESIGN.md §7):

  * The engine owns ``max_batch`` decode SLOTS stepped together by ONE
    jitted ``jax.lax.while_loop``. Per-slot device state lives in a single
    fixed-shape pytree; request metadata stays on the host.
  * PAGED KV CACHE (default): k/v live in a flat pool of
    ``num_blocks × page_size`` blocks per layer; each slot owns a block
    table mapping logical pages to physical blocks. A host-side
    ``BlockManager`` (free list, refcounts, copy-on-write) owns the pool;
    the ``Scheduler`` admits requests by FREE BLOCKS, not free slots —
    memory is reserved per request need, not worst-case per slot.
  * PREFIX SHARING: prompt pages are indexed in a hash-chained
    ``PrefixCache`` at request completion; later requests sharing a prompt
    prefix map the cached blocks into their table instead of recomputing
    them (refcounted; divergence inside a shared partial page
    copies-on-write at admit time, so the decode loop never stops for a
    copy). Chains are namespaced per task id on task-routed runtimes —
    any task-adapted matrix perturbs the residual stream, so deep-layer
    prefix KV is task-dependent even with frozen k/v projections; what
    ONE global MetaTT adapter buys over per-task LoRA stacks is one
    engine and one block pool for every task (see block_manager.py).
  * IN-LOOP CHUNKED PREFILL: the while_loop body processes a fixed
    ``(B, prefill_chunk)`` token block — prefilling slots consume up to
    ``prefill_chunk`` prompt tokens per step while decode slots carry one
    real token, co-batched in the SAME graph. There is no separate prefill
    function and no per-bucket recompile ladder: the step compiles once
    for all prompt lengths (the dense mode's ``_bucket`` ladder survives
    only behind ``ServeConfig(cache_mode="dense")``, the parity baseline).
  * The loop returns to the host exactly when some slot finishes — the
    host EVICTS it (harvests the output row, returns blocks to the pool /
    prefix cache) and ADMITS pending requests into freed slots while other
    slots keep generating.
  * TASK ROUTING: each slot carries a task id; with a 4+1d adapter under
    the live/lora runtime the (B,) slot task vector gathers per-row
    C[l, t_b, m] slices from the one shared tensor train (paper
    Eq. (4)/(6)) — a single decode batch mixes tasks.
  * ADAPTER PAGING (DESIGN.md §12): with
    ``ServeConfig(registry=RegistryConfig(max_resident_tasks=K))`` the
    per-task factor axis on device shrinks to a fixed K-slot pool per
    replica; the full factors stay host-side and a host AdapterRegistry
    (task → slot, pins, LRU eviction — the BlockManager pattern applied
    to adapters) pages task slices in via one jitted donated scatter
    per fault. The slot task vector then carries POOL-SLOT indices, the
    Scheduler gates admission on slot availability exactly like block
    availability, and prefix-cache namespaces stay keyed on the TASK ID
    so an evicted-and-readmitted task still warm-hits its cached
    prompts. One engine serves an open-ended task population (paper
    Eq. (4)/(6): per-task marginal cost = one core slice).
  * QUANTIZED SERVING (DESIGN.md §8): MetaTT's base is frozen by
    construction, so base weights + KV cache are pure read-only
    bandwidth. ``QuantConfig(weights="int8")`` packs the base matmul
    leaves once at construction (the fused w8a16 kernels dequantize
    in-register; the TT delta stays fp); ``kv="int8"`` stores paged KV
    cells as int8 with per-cell scale pools in the same block layout, so
    the same num_blocks HBM budget holds ~2x (bf16) the tokens and
    prefix sharing / COW round-trip the quantized representation.
  * TENSOR-PARALLEL SERVING (DESIGN.md §9): with
    ``ServeConfig(mesh_shape=(data, model))`` the engine builds a mesh
    (sharding/rules.py::serve_mesh) and wraps every jitted step graph —
    admit, COW, the prefill+decode while_loop — in ``shard_map``. The
    K/V (and int8 scale) pools shard on the KV-HEAD axis over "model":
    each shard scatters and attends only its contiguous head group
    against its local pool shard, the readout computes a per-shard
    vocab stripe, and the (B, V) logits are all-gathered for in-graph
    sampling — the only collectives in the loop. TT cores, block
    tables, slot state, task ids and the sampling PRNG are replicated,
    and ALL admission / eviction / COW decisions stay host-side on the
    shard-agnostic BlockManager (one block id indexes every shard's
    pool), so sharded greedy decode is token-identical to the
    single-device engine and per-shard peak KV bytes are 1/|model| of
    the global figure (``EngineStats.kv_bytes_peak_per_shard``).
  * FLEET SERVING (DESIGN.md §11): the second mesh axis stripes the
    engine data-parallel — a host-side ``Router`` places each request on
    one of |data| decode REPLICAS (deterministic least-loaded or
    round-robin), each replica owning its own ``max_batch`` slot stripe,
    its own ``num_blocks`` stripe of the paged pools (block ids are
    replica-local) and its own Scheduler/BlockManager/PrefixCache. The
    jitted step graphs shard_map over BOTH axes, so each data shard
    decodes only its own slot stripe — admit/COW writes on the other
    replicas drop via out-of-bounds sentinels. ``ServeConfig(disagg=
    True)`` additionally splits prefill from decode: a prefill WORKER
    (a second state+pool pair with identical geometry, so it reuses the
    same compiled graphs — decode_traces stays 1) chunk-prefills
    prompts and emits the first token, then the host hands the sequence
    to a decode replica by migrating its prompt blocks pool-to-pool
    (BlockManager.migrate_to + transformer.migrate_cache_blocks); the
    prefix cache lives with the prefill pool. ``row_parallel=True``
    switches wo/wd to row-sharded weights with a psum epilogue
    (models/layers.py::serve_rp_linear) — near-parity (~1e-3) against
    the column-only mode, which stays the bit-exact parity oracle.

The engine requires attention-pattern models (stateful mixers — mamba /
xlstm — have no position-indexed cache to page).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Callable, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.checkpoint import ckpt as ckpt_lib
from repro.config.base import (KernelConfig, ModelConfig, QuantConfig,
                               ServeConfig)
from repro.kernels import dispatch as kernel_dispatch
from repro.kernels import quant as quant_lib
from repro.models import transformer
from repro.peft import api as peft_api
from repro.serving import adapter_registry
from repro.serving import chaos as chaos_mod
from repro.serving import sampling as sampling_lib
from repro.serving import speculative as spec_lib
from repro.serving.adapter_registry import AdapterRegistry
from repro.serving.adapter_runtime import AdapterRuntime
from repro.serving.block_manager import BlockManager, PrefixCache
from repro.serving.router import Router
from repro.serving.scheduler import Scheduler
from repro.serving.stats import EngineStats
from repro.sharding import (serve_cache_pspec, serve_cache_sharding,
                            serve_dp_index, serve_mesh, serve_tp_slice,
                            set_serve_dp, set_serve_rp, set_serve_tp)


@dataclasses.dataclass
class Request:
    """One generation request. prompt: 1-D int token ids (list/np/jnp).

    deadline_s: optional wall-clock budget measured from ``generate``
    entry — a request still unfinished when it expires ends with status
    TIMEOUT and whatever tokens it produced. request_id: host-side
    handle for ``Engine.cancel`` (defaults to the request's batch
    index)."""
    prompt: Any
    max_new_tokens: int
    task: int = 0
    deadline_s: Optional[float] = None
    request_id: Optional[Any] = None


# terminal request statuses (DESIGN.md §13). PREEMPTED and replica
# failover are not terminal: the victim re-enters the queue through the
# recompute path and still ends in one of these (RequestResult.preemptions
# records how many recompute round-trips it survived).
FINISHED = "FINISHED"
CANCELLED = "CANCELLED"
TIMEOUT = "TIMEOUT"
FAILED = "FAILED"


class RequestResult(NamedTuple):
    """Per-request outcome of one ``generate`` call
    (``engine.last_results``). ``tokens`` holds everything the request
    emitted — possibly fewer than max_new_tokens when it was cancelled,
    timed out or failed; FAILED requests (in-graph NaN/inf logit
    detection) keep the tokens emitted BEFORE the fault."""
    tokens: np.ndarray
    status: str
    n_generated: int
    preemptions: int = 0


def _pad_caches(caches, cfg: ModelConfig, batch: int, cache_len: int,
                num_super_blocks: Optional[int] = None):
    """Place length-T prefill caches into a fixed cache_len-wide template.
    ``num_super_blocks`` sizes the template for the speculative drafter's
    layer-strided sub-model."""
    template = transformer.init_caches(cfg, batch, cache_len,
                                       cfg.compute_dtype,
                                       num_super_blocks=num_super_blocks)
    if caches is None:
        return template

    def pad(c, z):
        return jax.lax.dynamic_update_slice(z, c.astype(z.dtype),
                                            (0,) * c.ndim)

    return [jax.tree_util.tree_map(pad, c, t)
            for c, t in zip(caches, template)]


class DecodeState(NamedTuple):
    """Dense-mode loop-carried per-slot device state. ``dcaches`` is the
    speculative drafter's parallel KV region (None when speculation is
    off); steps/drafted/accepted are loop-carried int32 scalar counters
    the host reads off the final state (stats.py)."""
    tok: jnp.ndarray        # (B, 1)  last sampled token per slot
    pos: jnp.ndarray        # (B,)    cache position tok will be written at
    remaining: jnp.ndarray  # (B,)    tokens still to sample
    active: jnp.ndarray     # (B,)    slot is mid-generation
    widx: jnp.ndarray       # (B,)    next column of the output buffer
    out: jnp.ndarray        # (B, out_cap) generated tokens
    task: jnp.ndarray       # (B,)    per-slot task id (4+1d routing)
    key: jnp.ndarray        # PRNG key (in-graph sampling)
    caches: Any             # transformer KV caches, batch axis = slots
    dcaches: Any = None     # drafter KV caches (speculative decode)
    steps: Any = 0          # loop iterations (engine steps)
    drafted: Any = 0        # drafter tokens proposed
    accepted: Any = 0       # drafter tokens accepted by the verifier
    failed: Any = None      # (B,) bool: in-graph NaN guard tripped


class PagedState(NamedTuple):
    """Paged-mode loop-carried per-slot device state. A slot is either
    PREFILLING (done < plen: the body consumes up to ``prefill_chunk``
    prompt tokens per step) or DECODING (one sampled token per step) —
    both co-batched in the same fixed-shape graph. Block tables are NOT
    loop-carried: they only change at admit/evict boundaries, which the
    loop already crosses, so the host passes them as a plain argument."""
    tok: jnp.ndarray        # (B, 1)  last sampled token per slot
    prompt: jnp.ndarray     # (B, Lp) full prompt tokens (right-padded)
    plen: jnp.ndarray       # (B,)    prompt length
    done: jnp.ndarray       # (B,)    tokens whose KV is in cache
    remaining: jnp.ndarray  # (B,)    tokens still to sample
    active: jnp.ndarray     # (B,)    slot is mid-request
    widx: jnp.ndarray       # (B,)    next column of the output buffer
    out: jnp.ndarray        # (B, out_cap) generated tokens
    task: jnp.ndarray       # (B,)    per-slot task id (4+1d routing)
    key: jnp.ndarray        # PRNG key (in-graph sampling)
    caches: Any             # paged KV pools (leaves (nb, N, KV, page, hd))
    dcaches: Any = None     # drafter KV pools, same block tables
    steps: Any = 0          # loop iterations (engine steps)
    drafted: Any = 0        # drafter tokens proposed
    accepted: Any = 0       # drafter tokens accepted by the verifier
    failed: Any = None      # (B,) bool: in-graph NaN guard tripped


class Engine:
    """Continuous-batching engine over an AdapterRuntime.

    ``serve`` (config.base.ServeConfig) picks the cache layout: "paged"
    (default — block/paged cache, prefix sharing, in-loop chunked prefill)
    or "dense" (the PR-1 slot layout, kept as the parity baseline). The
    legacy keyword arguments populate a ServeConfig when ``serve`` is not
    given. ``cache_len`` bounds prompt_len + max_new_tokens per request;
    ``out_cap`` bounds max_new_tokens. ``generate`` serves any number of
    requests through the fixed slots, admitting/evicting as they finish;
    per-call observability lands on ``engine.last_stats``.

    ``serve.mesh_shape=(data, model)`` makes the engine tensor-parallel
    (DESIGN.md §9): KV caches shard on the kv-head axis over "model"
    inside shard_map-wrapped step graphs, token-identically to the
    single-device engine (greedy). num_heads / num_kv_heads /
    padded_vocab must divide the "model" axis size.
    """

    def __init__(self, model_cfg: ModelConfig, runtime: AdapterRuntime, *,
                 max_batch: Optional[int] = None,
                 cache_len: Optional[int] = None,
                 out_cap: Optional[int] = None,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 sampling: sampling_lib.SamplingConfig =
                 sampling_lib.SamplingConfig(),
                 seed: int = 0,
                 kernels: KernelConfig = KernelConfig(),
                 serve: Optional[ServeConfig] = None):
        for mixer, _ in model_cfg.block_pattern:
            if mixer != "attn":
                raise NotImplementedError(
                    f"slot engine needs attention KV caches; mixer {mixer!r} "
                    "carries stateful caches that cannot be slot-inserted "
                    "or paged")
        if model_cfg.is_encdec:
            raise NotImplementedError("enc-dec serving is not slotted yet")
        if runtime.tasked and runtime.spec.adapts("moe_down"):
            # moe_down deltas apply over expert-sorted (E, C, ff) blocks
            # (models/moe.py), whose leading axis is experts — a per-request
            # (B,) task vector cannot index them.
            raise NotImplementedError(
                "per-request task routing does not reach the expert-sorted "
                "moe_down path; serve this adapter with a scalar task "
                "(per-task engines) or drop moe_down from matrix_types")
        legacy = dict(max_batch=max_batch, cache_len=cache_len,
                      out_cap=out_cap, prompt_buckets=prompt_buckets)
        if serve is None:
            serve = ServeConfig(
                max_batch=max_batch if max_batch is not None else 4,
                cache_len=cache_len if cache_len is not None else 64,
                out_cap=out_cap if out_cap is not None else 32,
                prompt_buckets=(tuple(prompt_buckets)
                                if prompt_buckets is not None else ()))
        elif any(v is not None for v in legacy.values()):
            given = [k for k, v in legacy.items() if v is not None]
            raise ValueError(
                f"pass serving shape knobs either via serve=ServeConfig "
                f"or via keyword arguments, not both (got serve= and "
                f"{given})")
        self.sv = serve.validate()
        self.cfg = model_cfg
        self.rt = runtime
        self.max_batch = self.sv.max_batch
        self.cache_len = self.sv.cache_len
        self.out_cap = self.sv.out_cap
        self.prompt_buckets = tuple(sorted(self.sv.prompt_buckets))
        self.sampling = sampling.validate()
        # tensor-parallel serving (DESIGN.md §9): the mesh is built once;
        # every step graph below is shard_map-wrapped over it. Head /
        # vocab groups are sliced contiguously per shard, so the sharded
        # dims must divide the TP axis (no silent replicated fallback —
        # the KV-pool memory claim would quietly evaporate).
        self.mesh = None
        self._tp = 1
        self._dp = 1                    # data replicas (DESIGN.md §11)
        self._dp_axis = "data"
        if self.sv.mesh_shape:
            self.mesh = serve_mesh(self.sv.mesh_shape)
            self._tp = int(self.mesh.shape[self.sv.tp_axis])
            # whichever mesh axis is NOT tensor-parallel stripes the
            # engine data-parallel: replica slot stripes + pool stripes
            self._dp_axis = ("data" if self.sv.tp_axis == "model"
                             else "model")
            self._dp = int(self.mesh.shape[self._dp_axis])
            if self._dp > 1 and self.sv.cache_mode != "paged":
                raise ValueError(
                    "data-axis request striping needs cache_mode='paged' "
                    "(replica pool stripes are paged block stripes)")
            for dim, name in ((model_cfg.num_heads, "num_heads"),
                              (model_cfg.num_kv_heads, "num_kv_heads"),
                              (model_cfg.padded_vocab, "padded_vocab")):
                if dim % self._tp:
                    raise ValueError(
                        f"{name}={dim} is not divisible by the "
                        f"{self.sv.tp_axis}-axis size {self._tp}; the "
                        "sharded engine slices contiguous head / vocab "
                        "groups per shard")
            if self.sv.row_parallel and model_cfg.d_ff % self._tp:
                raise ValueError(
                    f"row_parallel serving row-slices the ffn-down "
                    f"weight: d_ff={model_cfg.d_ff} must be divisible by "
                    f"the {self.sv.tp_axis}-axis size {self._tp}")
        # resolved once; static inside the jitted step graphs. With a
        # (4+1)d adapter the fused decode route is the batched-A kernel
        # (kernels/tt_linear.py::tt_linear_batched_a); paged attention
        # routes through kernels/paged_attention.py.
        self.policy = kernel_dispatch.resolve(kernels)
        # quantization (DESIGN.md §8): KernelConfig.quant and
        # ServeConfig.quant merge (int8 wins) — the base is packed ONCE
        # here, so every prefill/decode graph reads int8 weight leaves;
        # the KV side sizes the paged pools below.
        kq = (kernels.quant if isinstance(kernels, KernelConfig)
              else QuantConfig())
        sq = self.sv.quant
        self.quant = QuantConfig(
            weights="int8" if "int8" in (kq.weights, sq.weights) else "none",
            kv="int8" if "int8" in (kq.kv, sq.kv) else "none",
            group_size=kq.group_size or sq.group_size).validate()
        self._kv_quant = self.quant.kv == "int8"
        if self._kv_quant and self.sv.cache_mode != "paged":
            raise ValueError(
                "kv=int8 quantization needs cache_mode='paged' (the int8 "
                "cells and their scale pools live in the paged block "
                "layout)")
        if self.sv.row_parallel and self.quant.group_size:
            # config.base catches ServeConfig.quant; the KernelConfig
            # merge can re-introduce grouped scales, so re-check here
            raise ValueError(
                "row_parallel is incompatible with grouped int8 scales "
                "(group_size > 0): scale groups tile the contraction "
                "axis the row slices cut; use per-channel group_size=0")
        base = runtime.base
        if self.quant.weights == "int8":
            base = quant_lib.quantize_base(
                base, group_size=self.quant.group_size)
        # paged adapter registry (DESIGN.md §12): with
        # registry.max_resident_tasks=K the engine keeps a fixed K-slot
        # device pool per replica instead of the whole num_tasks axis —
        # the full factors stay HOST-side and admission faults task
        # slices in on demand. The per-slot (B,) task vector then carries
        # POOL-SLOT indices, so the traced task gather (and with it
        # decode_traces == 1) is untouched; only its index space shrinks.
        self.reg_cfg = self.sv.registry
        self._reg_on = self.reg_cfg.enabled
        if self._reg_on and not runtime.tasked:
            raise ValueError(
                f"RegistryConfig.max_resident_tasks="
                f"{self.reg_cfg.max_resident_tasks} needs a task-routed "
                "runtime (metatt 4+1d live/lora with num_tasks set); "
                "untasked/merged runtimes have no per-task slices to page")
        self._host_per_layer = None
        per_layer = runtime.per_layer
        if self._reg_on:
            self._host_per_layer = jax.device_get(runtime.per_layer)
            per_layer = self._commit_pool(adapter_registry.pool_factors(
                runtime.per_layer,
                self._dp * self.reg_cfg.max_resident_tasks))
        self._key = jax.random.PRNGKey(seed)
        self._weights = (base, runtime.broadcast, per_layer)
        # speculative decode (DESIGN.md §10): the drafter is a
        # rank-truncated / layer-strided slice of the SAME weight bundle
        # (sliced here once, on the possibly int8-packed base), proposing
        # spec_k tokens per engine step that the target verifies in one
        # co-batched pass inside the decode while_loop.
        self.spec = self.sv.spec
        self._spec_on = self.spec.enabled
        self._draft_weights = ()
        self._nb_draft = self.cfg.num_super_blocks
        self._host_draft_pl = None
        if self._spec_on:
            dbase, dbc, dpl, self._nb_draft = spec_lib.build_drafter(
                self.spec, self.rt.spec.kind, base, runtime.broadcast,
                runtime.per_layer, len(self.cfg.block_pattern))
            if self._reg_on:
                # the drafter factors are leading bond columns of the
                # SAME task slices (speculative.truncate_factors keeps
                # the task axis), so they page with their target slice:
                # one fault scatters both pools at the same slot
                self._host_draft_pl = jax.device_get(dpl)
                dpl = self._commit_pool(adapter_registry.pool_factors(
                    dpl, self._dp * self.reg_cfg.max_resident_tasks))
            self._draft_weights = (dbase, dbc, dpl)
        # the step graphs take target weights (+ drafter weights when
        # speculating) as leading args so none bake in as constants
        self._step_weights = self._weights + self._draft_weights
        if self._reg_on:
            # ONE jitted donated scatter per fault: the pool keeps its
            # shape and the slot index is traced, so every fault reuses
            # the same compile; donation makes it an in-place slot write.
            # Plain jit OUTSIDE shard_map — the pool is committed
            # replicated on the serve mesh (_commit_pool), so a replicated
            # update between loop exits is valid on every shard without
            # touching the sharded step graphs.
            if self._spec_on:
                self._afault = jax.jit(
                    lambda pl, dpl, slot, col, dcol: (
                        adapter_registry.scatter_slot(pl, slot, col),
                        adapter_registry.scatter_slot(dpl, slot, dcol)),
                    donate_argnums=(0, 1))
            else:
                self._afault = jax.jit(adapter_registry.scatter_slot,
                                       donate_argnums=(0,))
        self._decode_traces = 0
        self._prefill_traces = 0
        self.last_stats = self._new_stats()
        # request lifecycle (DESIGN.md §13): ids queued for cancellation
        # (consumed by the running generate), per-generate results with
        # status, and the live-bookkeeping handle chaos audits read
        self._cancel_ids = set()
        self.last_results: List[RequestResult] = []
        self._live = None
        self._chaos = None
        if self.sv.cache_mode == "dense":
            # dense mode has no Scheduler; the engine drives its (single)
            # registry directly in the dense admission/harvest loop.
            # _build_host_pools recreates the paged-mode registries.
            self.registries = ([AdapterRegistry(
                self.reg_cfg.max_resident_tasks,
                policy=self.reg_cfg.eviction)] if self._reg_on else [])
            self._prefill = jax.jit(self._prefill_impl)
            self._init_dense()
        else:
            self._init_paged()

    # ------------------------------------------------------------------
    # step-graph construction (single-device jit, or jit(shard_map) over
    # the serve mesh — DESIGN.md §9)
    # ------------------------------------------------------------------

    def _rep_spec(self, tree):
        """Fully-replicated PartitionSpec pytree matching ``tree``."""
        return jax.tree_util.tree_map(lambda _: P(), tree)

    def _commit_pool(self, tree):
        """Commit an adapter slot pool replicated onto the serve mesh
        (identity without one). Faulting runs through a plain jit, so
        the pool must carry an explicit replicated sharding — otherwise
        the fault output lands single-device and the shard_mapped step
        would reject it."""
        if self.mesh is None:
            return tree
        return jax.device_put(
            tree, jax.sharding.NamedSharding(self.mesh, P()))

    def _shard_mapped(self, fn, in_specs, out_specs):
        """Wrap a step impl in ``shard_map`` over the serve mesh (identity
        without one). The wrapper installs the serve-TP trace context
        (sharding.set_serve_tp) around tracing, which is what makes the
        attention / readout call sites slice this shard's head and vocab
        groups; it is cleared before control returns to the host."""
        if self.mesh is None:
            return fn
        axis, tp = self.sv.tp_axis, self._tp
        rp = bool(self.sv.row_parallel)
        dp = (self._dp_axis, self._dp) if self._dp > 1 else None

        def traced(*args):
            set_serve_tp(axis, tp)
            set_serve_rp(rp)
            if dp is not None:
                set_serve_dp(*dp)
            try:
                return fn(*args)
            finally:
                set_serve_tp(None)
                set_serve_rp(False)
                set_serve_dp(None)

        return jax.shard_map(traced, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _init_dense(self) -> None:
        """Jit (and, on a mesh, shard_map) the dense-mode step graphs.
        Sharded layout: decode caches (nb, B, KV, S, hd) shard the
        kv-head axis on "model"; prefill stays a plain replicated jit
        (it computes full-width caches that admit slices per shard).
        With speculation the drafter weights ride as three extra leading
        args and the drafter's KV region as a state field, so the decode
        graph's donate index shifts from 3 to 6."""
        don = 6 if self._spec_on else 3
        if self.mesh is None:
            self._admit = jax.jit(self._admit_impl, donate_argnums=(0,))
            self._decode = jax.jit(self._decode_impl, donate_argnums=(don,))
            self._kill = jax.jit(self._kill_dense_impl, donate_argnums=(0,))
            return
        template = transformer.init_caches(
            self.cfg, self.max_batch, self.cache_len, self.cfg.compute_dtype)
        dspec = P()
        d1spec = P()
        if self._spec_on:
            dtemplate = transformer.init_caches(
                self.cfg, self.max_batch, self.cache_len,
                self.cfg.compute_dtype, num_super_blocks=self._nb_draft)
            dspec = serve_cache_pspec(dtemplate, self.sv.tp_axis)
            d1spec = self._rep_spec(dtemplate)
        sspec = DecodeState(
            tok=P(), pos=P(), remaining=P(), active=P(), widx=P(),
            out=P(), task=P(), key=P(),
            caches=serve_cache_pspec(template, self.sv.tp_axis),
            dcaches=dspec, steps=P(), drafted=P(), accepted=P(),
            failed=P())
        wspec = tuple(self._rep_spec(w) for w in self._step_weights)
        self._admit = jax.jit(self._shard_mapped(
            self._admit_impl,
            (sspec, P(), self._rep_spec(template), d1spec, P(), P(), P(),
             P()), sspec), donate_argnums=(0,))
        self._decode = jax.jit(self._shard_mapped(
            self._decode_impl, (*wspec, sspec, P()), sspec),
            donate_argnums=(don,))
        self._kill = jax.jit(self._shard_mapped(
            self._kill_dense_impl, (sspec, P()), sspec),
            donate_argnums=(0,))

    def _init_paged(self) -> None:
        sv = self.sv
        self._chunk = min(sv.prefill_chunk, sv.cache_len)
        if self._spec_on:
            # the verifier scores [committed tok, k drafts] in one pass
            # through the SAME (B, C) co-batched graph chunked prefill
            # uses, so the chunk must fit k+1 columns (validated
            # spec_k + 1 <= cache_len in config.base)
            self._chunk = max(self._chunk, self.spec.spec_k + 1)
        self._page = sv.page_size
        self._num_blocks = sv.resolved_num_blocks
        # table width: worst-case pages per request, plus sentinel columns
        # so pad-column writes past a request's allocation land out of
        # table instead of clamping into a real page
        self._p_tab = (sv.pages_per_request
                       + max(1, -(-self._chunk // self._page)))
        self._lp = sv.cache_len + self._chunk   # prompt buffer width
        self._disagg = sv.disagg
        # data-axis striping (DESIGN.md §11): |data| decode replicas,
        # each owning max_batch slots and a num_blocks stripe of the
        # pools; _num_blocks / max_batch are PER-REPLICA figures and all
        # host-side block ids are replica-local.
        self._slots = self._dp * self.max_batch
        # any task-adapted matrix (q/v by default) perturbs the residual
        # stream, so layer>=1 prefix KV is task-dependent even where k/v
        # projections are frozen — tasked runtimes key prefix chains per
        # task id; untasked runtimes (one task, merged, none) share one
        # namespace across all requests
        self._kv_tasked = self.rt.tasked
        self._build_host_pools()
        self._tables = np.full((self._slots, self._p_tab),
                               self._num_blocks, np.int32)
        self._block_bytes = self._kv_bytes(self._page)
        if self._spec_on:
            # the drafter's parallel KV region: same block geometry, same
            # host-side tables, 1/stride the layers — its bytes ride the
            # same per-block accounting
            self._block_bytes += self._kv_bytes(
                self._page, num_super_blocks=self._nb_draft)
        # the physical block pools persist ACROSS generate calls — the
        # prefix cache indexes into them, so warm requests reuse KV
        # computed by earlier calls (the drafter pools too: prompt cells
        # carry drafter KV written by the in-loop sync pass, so prefix
        # hits warm BOTH models)
        self._paged_caches = self._fresh_pools()
        self._draft_pools = (self._fresh_pools(
            num_super_blocks=self._nb_draft) if self._spec_on else None)
        self._pf_caches = self._pf_draft_pools = None
        if self._disagg:
            # the prefill worker's state/pool pair shares every shape
            # with the decode side, so the jitted graphs below serve
            # both without retracing (decode_traces stays 1)
            self._pf_tables = np.full((self._slots, self._p_tab),
                                      self._num_blocks, np.int32)
            self._pf_caches = self._fresh_pools()
            self._pf_draft_pools = (self._fresh_pools(
                num_super_blocks=self._nb_draft) if self._spec_on
                else None)
        don = 6 if self._spec_on else 3
        if self.mesh is None:
            self._padmit = jax.jit(self._paged_admit_impl,
                                   donate_argnums=(0,))
            self._pcow = jax.jit(self._cow_impl, donate_argnums=(0,))
            self._pdecode = jax.jit(self._paged_decode_impl,
                                    donate_argnums=(don,))
            self._pkill = jax.jit(self._kill_paged_impl,
                                  donate_argnums=(0,))
            if self._disagg:
                self._pmigrate = jax.jit(self._migrate_impl,
                                         donate_argnums=(0,))
            return
        # sharded step graphs (DESIGN.md §9/§11): pools shard on the
        # kv-head axis over "model" and on the BLOCKS axis over the data
        # axis; slot-striped state leaves, tables and the loop counters
        # shard their leading axis over "data" when |data| > 1, so each
        # replica's while_loop sees only its own slot stripe. On a
        # single data shard everything below reduces exactly to the §9
        # layout (replicated slot state, replicated tables).
        fleet = self._dp > 1
        dpax = self._dp_axis if fleet else None
        sl = P(self._dp_axis) if fleet else P()
        cspec = serve_cache_pspec(self._paged_caches, self.sv.tp_axis,
                                  dp_axis=dpax)
        dspec = (serve_cache_pspec(self._draft_pools, self.sv.tp_axis,
                                   dp_axis=dpax)
                 if self._spec_on else P())
        sspec = PagedState(
            tok=sl, prompt=sl, plen=sl, done=sl, remaining=sl,
            active=sl, widx=sl, out=sl, task=sl, key=sl,
            caches=cspec, dcaches=dspec,
            steps=sl, drafted=sl, accepted=sl, failed=sl)
        wspec = tuple(self._rep_spec(w) for w in self._step_weights)
        self._padmit = jax.jit(self._shard_mapped(
            self._paged_admit_impl,
            (sspec, P(), P(), P(), P(), P(), P(), P(), P()), sspec),
            donate_argnums=(0,))
        self._pcow = jax.jit(self._shard_mapped(
            self._cow_impl, (sspec, P(), P(), P()), sspec),
            donate_argnums=(0,))
        self._pdecode = jax.jit(self._shard_mapped(
            self._paged_decode_impl, (*wspec, sspec, sl, sl), sspec),
            donate_argnums=(don,))
        self._pkill = jax.jit(self._shard_mapped(
            self._kill_paged_impl, (sspec, P()), sspec),
            donate_argnums=(0,))
        if self._disagg:
            self._pmigrate = jax.jit(self._shard_mapped(
                self._migrate_impl,
                (sspec, cspec, dspec, P(), P(), P()), sspec),
                donate_argnums=(0,))

    def _build_host_pools(self) -> None:
        """(Re)build the host-side per-replica admission machinery:
        request router, block managers, prefix caches and schedulers —
        one of each per data replica, plus a parallel prefill-worker set
        under disaggregation (where the prefix cache lives with the
        PREFILL pool and decode replicas skip registration). ``bm`` /
        ``prefix`` / ``sched`` stay as replica-0 aliases for callers
        from the single-replica era."""
        sv = self.sv
        self.router = Router(self._dp, sv.router)
        self.bms = [BlockManager(self._num_blocks, self._page)
                    for _ in range(self._dp)]
        # adapter registries (DESIGN.md §12): one per data replica —
        # replica r owns the global pool-slot stripe [r*K, (r+1)*K).
        # Under disaggregation the prefill worker and the decode replica
        # SHARE one registry: the pin taken at prefill admission carries
        # through the handoff and is released once, at decode harvest.
        self.registries = ([AdapterRegistry(self.reg_cfg.max_resident_tasks,
                                            policy=self.reg_cfg.eviction)
                            for _ in range(self._dp)]
                           if self._reg_on else [])
        regs = self.registries or [None] * self._dp
        if self._disagg:
            self.prefixes = [None] * self._dp
            self._pf_bms = [BlockManager(self._num_blocks, self._page)
                            for _ in range(self._dp)]
            self._pf_prefixes = [
                PrefixCache(bm) if sv.prefix_cache else None
                for bm in self._pf_bms]
            self._pf_scheds = [
                Scheduler(bm, px, self.last_stats, registry=reg)
                for bm, px, reg in zip(self._pf_bms, self._pf_prefixes,
                                       regs)]
        else:
            self.prefixes = [PrefixCache(bm) if sv.prefix_cache else None
                             for bm in self.bms]
            self._pf_bms, self._pf_prefixes, self._pf_scheds = [], [], []
        self.scheds = [Scheduler(bm, px, self.last_stats, registry=reg)
                       for bm, px, reg in zip(self.bms, self.prefixes,
                                              regs)]
        self.bm = self.bms[0]
        self.prefix = (self._pf_prefixes[0] if self._disagg
                       else self.prefixes[0])
        self.sched = (self._pf_scheds[0] if self._disagg
                      else self.scheds[0])

    def _rebuild_replica_pools(self, r: int) -> None:
        """Failover (DESIGN.md §13): replace replica ``r``'s host-side
        admission state — block manager, prefix cache, adapter registry,
        scheduler(s) — with fresh empty instances. The old pools indexed
        KV on a replica that no longer serves; every request they backed
        has already been harvested and re-routed, so nothing references
        them. The replica-0 aliases are kept pointing at the live
        objects for single-replica-era callers."""
        sv = self.sv
        self.bms[r] = BlockManager(self._num_blocks, self._page)
        if self._reg_on:
            self.registries[r] = AdapterRegistry(
                self.reg_cfg.max_resident_tasks,
                policy=self.reg_cfg.eviction)
        reg = self.registries[r] if self._reg_on else None
        if self._disagg:
            self.prefixes[r] = None
            self._pf_bms[r] = BlockManager(self._num_blocks, self._page)
            self._pf_prefixes[r] = (PrefixCache(self._pf_bms[r])
                                    if sv.prefix_cache else None)
            old_pf = self._pf_scheds[r]
            self._pf_scheds[r] = Scheduler(
                self._pf_bms[r], self._pf_prefixes[r], old_pf.stats,
                registry=reg)
        else:
            self.prefixes[r] = (PrefixCache(self.bms[r])
                                if sv.prefix_cache else None)
        old = self.scheds[r]
        self.scheds[r] = Scheduler(self.bms[r], self.prefixes[r],
                                   old.stats, registry=reg)
        if r == 0:
            self.bm = self.bms[0]
            self.prefix = (self._pf_prefixes[0] if self._disagg
                           else self.prefixes[0])
            self.sched = (self._pf_scheds[0] if self._disagg
                          else self.scheds[0])

    def _fresh_pools(self, num_super_blocks: Optional[int] = None):
        """Zero paged K/V (+ int8 scale) pools, kv-head-sharded over the
        serve mesh when one is configured (the host-side BlockManager is
        shard-agnostic: one block id addresses row ``bid`` of every
        shard's pool). With |data| > 1 the pool holds dp stripes of
        ``_num_blocks`` blocks, sharded on the blocks axis — each
        replica's manager addresses its local stripe with local ids.
        ``num_super_blocks`` sizes the speculative drafter's parallel
        pool region."""
        caches = transformer.init_paged_caches(
            self.cfg, self._dp * self._num_blocks, self._page,
            self.cfg.compute_dtype, kv_quant=self._kv_quant,
            num_super_blocks=num_super_blocks)
        if self.mesh is not None:
            caches = jax.device_put(caches, serve_cache_sharding(
                caches, self.mesh, self.sv.tp_axis,
                dp_axis=self._dp_axis if self._dp > 1 else None))
        return caches

    def _new_stats(self, requests: int = 0) -> EngineStats:
        """Fresh per-generate stats object (cache mode / dtypes / shard
        count are engine constants; counters start at zero)."""
        return EngineStats(
            cache_mode=self.sv.cache_mode, requests=requests,
            weights_dtype=("int8" if self.quant.weights == "int8"
                           else "fp"),
            kv_dtype="int8" if self._kv_quant else "fp",
            shards=self._tp,
            max_resident_tasks=self.reg_cfg.max_resident_tasks)

    def _kv_bytes(self, tokens: int,
                  num_super_blocks: Optional[int] = None) -> int:
        """GLOBAL (all-shard) device bytes of k+v cache for ``tokens``
        cells across every layer — the one formula behind both the paged
        block size and the dense-reservation equivalent the benchmarks
        compare against; under TP each shard holds 1/``shards`` of it
        (EngineStats.block_bytes_per_shard does the division). In
        int8 KV mode a cell costs kv_dim int8 bytes plus one f32 scale
        per kv head (k and v each) — roughly half the bf16 cost and a
        quarter of f32, so the same num_blocks budget holds ~2x (bf16) to
        ~4x (f32) the tokens. ``num_super_blocks`` overrides the layer
        count for the drafter's strided region."""
        nb = (self.cfg.num_super_blocks if num_super_blocks is None
              else num_super_blocks)
        layers = nb * len(self.cfg.block_pattern)
        if self._kv_quant:
            per_cell = self.cfg.kv_dim + 4 * self.cfg.num_kv_heads
        else:
            per_cell = (self.cfg.kv_dim
                        * jnp.dtype(self.cfg.compute_dtype).itemsize)
        return 2 * layers * tokens * per_cell

    def _adapter_fault_in(self, r: int, slot: int, task: int) -> None:
        """Scatter one task's host factor slices into pool slot
        ``r * K + slot`` — the device half of an adapter fault
        (DESIGN.md §12). ONE jitted donated scatter covering the live
        C-column / lora-form A-slice (and, when speculating, the
        drafter's truncated twin at the same slot); the pool shape and
        the traced slot index keep the compile cached, so faults never
        retrace. Runs host-side between decode-loop exits and OUTSIDE
        shard_map: the pool is committed replicated on the serve mesh,
        so a replicated functional update is valid on every shard
        without entering the sharded step graphs."""
        g = jnp.int32(r * self.reg_cfg.max_resident_tasks + slot)
        col = adapter_registry.task_slice(self._host_per_layer, task)
        base, bc, pl = self._weights
        if self._spec_on:
            dbase, dbc, dpl = self._draft_weights
            dcol = adapter_registry.task_slice(self._host_draft_pl, task)
            pl, dpl = self._afault(pl, dpl, g, col, dcol)
            self._draft_weights = (dbase, dbc, dpl)
        else:
            pl = self._afault(pl, g, col)
        self._weights = (base, bc, pl)
        self._step_weights = self._weights + self._draft_weights
        self.registries[r].mark_loaded(task)

    def _reset_paged_pool(self) -> None:
        """Drop every block (and the prefix index) — used when a failed
        generate leaves slot refcounts or donated buffers inconsistent."""
        self._build_host_pools()
        self._tables[:] = self._num_blocks
        self._paged_caches = self._fresh_pools()
        if self._spec_on:
            self._draft_pools = self._fresh_pools(
                num_super_blocks=self._nb_draft)
        if self._disagg:
            self._pf_tables[:] = self._num_blocks
            self._pf_caches = self._fresh_pools()
            if self._spec_on:
                self._pf_draft_pools = self._fresh_pools(
                    num_super_blocks=self._nb_draft)

    # ------------------------------------------------------------------
    # dense mode: jitted pieces (weights passed as args so they are never
    # baked into the executable as constants)
    # ------------------------------------------------------------------

    def _prefill_impl(self, base, bc, pl, tokens, last_idx, task):
        """tokens (1, Pb) right-padded -> (last-position logits (V,),
        caches padded to cache_len)."""
        self._prefill_traces += 1       # python side effect: runs per trace
        out = transformer.forward(base, self.cfg, self.rt.spec, bc, pl,
                                  tokens, task=task, policy=self.policy)
        # nb from the caches themselves: the same graph prefills the
        # speculative drafter's layer-strided sub-model (fewer blocks)
        nb = jax.tree_util.tree_leaves(out.caches)[0].shape[0]
        caches = _pad_caches(out.caches, self.cfg, 1, self.cache_len,
                             num_super_blocks=nb)
        last = jnp.take(out.logits[0], last_idx, axis=0)
        return last, caches

    def _admit_impl(self, state: DecodeState, slot, caches1, dcaches1,
                    last_logits, plen, n_new, task_id) -> DecodeState:
        """Insert a prefilled request into slot ``slot`` and sample its
        first token from the prefill logits (counted toward the output).
        Inside the sharded graph the replicated full-width prefill cache
        is sliced to this shard's kv-head stripe before insertion
        (serve_tp_slice no-ops on a single device). ``dcaches1`` is the
        drafter's prefill of the same prompt (None unless speculating)."""
        key, sub = jax.random.split(state.key)
        t0 = sampling_lib.sample(last_logits[None], sub, self.sampling)[0]
        caches1 = jax.tree_util.tree_map(
            lambda c: serve_tp_slice(c, 2), caches1)
        caches = transformer.insert_cache_slot(state.caches, caches1, slot)
        dcaches = state.dcaches
        if self._spec_on:
            dcaches1 = jax.tree_util.tree_map(
                lambda c: serve_tp_slice(c, 2), dcaches1)
            dcaches = transformer.insert_cache_slot(state.dcaches, dcaches1,
                                                    slot)
        return state._replace(
            dcaches=dcaches,
            tok=jax.lax.dynamic_update_slice(state.tok, t0[None, None],
                                             (slot, 0)),
            pos=state.pos.at[slot].set(plen),
            remaining=state.remaining.at[slot].set(n_new - 1),
            active=state.active.at[slot].set(n_new > 1),
            widx=state.widx.at[slot].set(1),
            out=state.out.at[slot].set(0).at[slot, 0].set(t0),
            task=state.task.at[slot].set(task_id),
            failed=state.failed.at[slot].set(False),
            key=key, caches=caches)

    # -- fleet helpers (DESIGN.md §11) ---------------------------------

    def _key_of(self, s):
        """The (2,)-shaped PRNG key for THIS shard's loop: with |data| >
        1 the state carries one key row per replica (their loops may run
        different iteration counts, so a replicated key would desync)."""
        return s.key[0] if self._dp > 1 else s.key

    def _wrap_key(self, k):
        """Inverse of ``_key_of`` for the loop-carried update."""
        return k[None] if self._dp > 1 else k

    def _fleet_key(self, key):
        """Initial state key: per-replica fold_in rows with |data| > 1
        (distinct sampling streams per replica), the plain key otherwise
        — the single-replica engines keep their exact historical
        draws."""
        if self._dp > 1:
            return jax.vmap(lambda i: jax.random.fold_in(key, i))(
                jnp.arange(self._dp))
        return key

    def _zero_ctr(self):
        """Loop counter zero: one int32 per data replica (counts diverge
        across replica loops), a scalar on a single replica."""
        return (jnp.zeros((self._dp,), jnp.int32) if self._dp > 1
                else jnp.int32(0))

    def _loop_cond(self, active0):
        """while_loop predicate: run until some slot's active set changes.
        With |data| > 1 the predicate is made GLOBAL via psums over the
        data axis, so every replica executes the same iteration count —
        divergent per-replica trip counts around the in-loop "model"
        collectives are never relied on. A replica whose stripe is idle
        spins harmlessly: its rows are inactive, so every write drops."""
        if self._dp == 1:
            def cond(s):
                return jnp.any(s.active) & jnp.all(s.active == active0)
            return cond

        def cond(s):
            alive = jnp.any(s.active).astype(jnp.int32)
            changed = jnp.any(s.active != active0).astype(jnp.int32)
            alive = jax.lax.psum(alive, self._dp_axis)
            changed = jax.lax.psum(changed, self._dp_axis)
            return (alive > 0) & (changed == 0)
        return cond

    # -- speculative building blocks (shared by both cache modes) ------

    def _propose(self, lg, mask, key):
        """One drafter proposal from logits ``lg`` (B, V): the token and
        (under a sampling method) the EXACT distribution q it was drawn
        from — the rejection rule needs q, not the raw logits. Greedy
        proposes the argmax and needs no q (accept is exact match)."""
        if self.sampling.method == "greedy":
            d = jnp.argmax(sampling_lib.process_logits(
                lg, self.sampling, penalty_mask=mask),
                axis=-1).astype(jnp.int32)
            return d, None
        q = sampling_lib.token_probs(lg, self.sampling, penalty_mask=mask)
        d = jax.random.categorical(
            key, jnp.log(jnp.maximum(q, 1e-38)), axis=-1).astype(jnp.int32)
        return d, q

    def _spec_accept(self, L, draft, q_probs, base_mask, key):
        """Accept/reject ``draft`` (B, k) against the verifier's one-pass
        logits ``L`` (B, k+1, V). Greedy: longest argmax-matching prefix
        plus the verifier's own next token — committed tokens are
        IDENTICAL to non-speculative greedy decode. Sampling: Leviathan
        rejection sampling against the exact per-column target
        distributions — the output distribution is unchanged. Per-column
        repetition-penalty masks extend ``base_mask`` with the in-chunk
        draft prefix, matching what sequential decode would have
        accumulated."""
        col_masks = spec_lib.column_penalty_masks(base_mask, draft,
                                                  L.shape[-1])
        if self.sampling.method == "greedy":
            g = jnp.argmax(sampling_lib.process_logits(
                L, self.sampling, penalty_mask=col_masks),
                axis=-1).astype(jnp.int32)
            return spec_lib.greedy_verify(draft, g)
        p = sampling_lib.token_probs(L, self.sampling,
                                     penalty_mask=col_masks)
        return spec_lib.rejection_verify(key, draft, q_probs, p)

    def _decode_impl(self, base, bc, pl, *rest) -> DecodeState:
        """Jitted continuous decode: step all active slots until one
        finishes (or none remain) — the host only sees slot boundaries.
        With speculation the drafter weights arrive as three extra args
        and each loop iteration commits up to spec_k+1 tokens per slot:
        k drafter single-token steps (plus one write-only step syncing
        the last draft's KV into the drafter cache), ONE multi-token
        verifier pass scoring all k+1 columns, and the in-graph accept
        rule — all inside the same single-trace while_loop.

        ``nan_at`` (B,) int32 is the chaos NaN-injection threshold per
        slot (-1 = never, the production value — it is a traced arg, so
        chaos runs share the single compiled graph). Independent of
        injection, every step checks its logits finite IN-GRAPH: a
        non-finite row stops emitting, deactivates, and raises its
        ``failed`` flag for the host to fail the request (DESIGN.md
        §13)."""
        if self._spec_on:
            dbase, dbc, dpl, state, nan_at = rest
        else:
            state, nan_at = rest
        self._decode_traces += 1        # python side effect: runs per trace
        active0 = state.active
        rows = jnp.arange(self.max_batch)
        K = self.spec.spec_k
        V = self.cfg.padded_vocab
        rp_on = self.sampling.repetition_penalty != 1.0

        def cond(s):
            return jnp.any(s.active) & jnp.all(s.active == active0)

        def body(s):
            task = s.task if self.rt.tasked else None
            logits, caches = transformer.decode_step(
                base, self.cfg, self.rt.spec, bc, pl, s.tok, s.caches,
                s.pos, task=task, policy=self.policy)
            # NaN guard: poison injected rows (chaos), then fail any row
            # whose logits are non-finite instead of sampling garbage
            inject = s.active & (nan_at >= 0) & (s.widx >= nan_at)
            logits = jnp.where(inject[:, None], jnp.nan, logits)
            bad = s.active & ~jnp.all(jnp.isfinite(logits), axis=-1)
            key, sub = jax.random.split(s.key)
            pm = (sampling_lib.history_mask(s.out, s.widx, V)
                  if rp_on else None)
            nxt = sampling_lib.sample(logits, sub, self.sampling,
                                      penalty_mask=pm)
            # inactive (and failing) slots write to column out_cap -> drop
            emit = s.active & ~bad
            col = jnp.where(emit, s.widx, self.out_cap)
            out = s.out.at[rows, col].set(nxt, mode="drop")
            adv = emit.astype(jnp.int32)
            tok = jnp.where(emit[:, None], nxt[:, None], s.tok)
            return DecodeState(
                tok=tok, pos=s.pos + adv, remaining=s.remaining - adv,
                active=s.active & (s.remaining > 1) & ~bad,
                widx=s.widx + adv,
                out=out, task=s.task, key=key, caches=caches,
                dcaches=s.dcaches, steps=s.steps + 1,
                drafted=s.drafted, accepted=s.accepted,
                failed=s.failed | bad)

        def spec_body(s):
            task = s.task if self.rt.tasked else None
            keys = jax.random.split(s.key, K + 2)
            base_mask = (sampling_lib.history_mask(s.out, s.widx, V)
                         if rp_on else None)
            # drafter phase: K proposals + 1 write-only step that lands
            # the last draft's KV in the drafter cache (the next round's
            # first drafter step attends it when every draft is accepted)
            dc = s.dcaches
            tok_j = s.tok
            drafts, qs = [], []
            mask_j = base_mask
            for j in range(K + 1):
                lg, dc = transformer.decode_step(
                    dbase, self.cfg, self.rt.spec, dbc, dpl, tok_j, dc,
                    s.pos + j, task=task, policy=self.policy)
                if j == K:
                    break
                d_j, q_j = self._propose(lg, mask_j, keys[1 + j])
                drafts.append(d_j)
                if q_j is not None:
                    qs.append(q_j)
                if rp_on:
                    oh = jax.nn.one_hot(d_j, V, dtype=jnp.bool_)
                    mask_j = oh if mask_j is None else (mask_j | oh)
                tok_j = d_j[:, None]
            d = jnp.stack(drafts, axis=1)                   # (B, K)
            # verifier: ONE multi-token pass over [committed tok, drafts]
            toks_v = jnp.concatenate([s.tok, d], axis=1)    # (B, K+1)
            L, caches = transformer.decode_step(
                base, self.cfg, self.rt.spec, bc, pl, toks_v, s.caches,
                s.pos, task=task, policy=self.policy, all_logits=True)
            # NaN guard over the verifier logits (chaos injection poisons
            # them first): a bad row commits nothing and fails
            inject = s.active & (nan_at >= 0) & (s.widx >= nan_at)
            L = jnp.where(inject[:, None, None], jnp.nan, L)
            bad = s.active & ~jnp.all(jnp.isfinite(L), axis=(1, 2))
            q = jnp.stack(qs, axis=1) if qs else None
            emitted, n = self._spec_accept(L, d, q, base_mask, keys[K + 1])
            m = jnp.where(s.active & ~bad,
                          jnp.minimum(n + 1, s.remaining), 0)
            cols = jnp.arange(K + 1)[None, :]
            outcol = jnp.where(cols < m[:, None], s.widx[:, None] + cols,
                               self.out_cap)
            out = s.out.at[rows[:, None], outcol].set(emitted, mode="drop")
            last = jnp.take_along_axis(
                emitted, jnp.maximum(m - 1, 0)[:, None], axis=1)
            tok = jnp.where((m > 0)[:, None], last, s.tok)
            nact = jnp.sum(s.active.astype(jnp.int32))
            return DecodeState(
                tok=tok, pos=s.pos + m, remaining=s.remaining - m,
                active=s.active & (s.remaining > m) & ~bad,
                widx=s.widx + m,
                out=out, task=s.task, key=keys[0], caches=caches,
                dcaches=dc, steps=s.steps + 1,
                drafted=s.drafted + K * nact,
                accepted=s.accepted + jnp.sum(jnp.where(s.active, n, 0)),
                failed=s.failed | bad)

        return jax.lax.while_loop(
            cond, spec_body if self._spec_on else body, state)

    # ------------------------------------------------------------------
    # paged mode: jitted pieces
    # ------------------------------------------------------------------

    def _paged_admit_impl(self, state: PagedState, slot, prompt_row, plen,
                          done0, n_new, task_id, tok0, w0) -> PagedState:
        """Place request metadata into slot ``slot`` (a GLOBAL slot id —
        each data replica rewrites it to a local row and non-owners drop
        the writes via the out-of-bounds sentinel). No prefill here — the
        decode loop's chunked-prefill path consumes the prompt starting
        at ``done0`` (tokens [0, done0) came from the prefix cache; the
        scheduler guarantees done0 <= plen - 1 so the last prompt token
        always runs through the model for its logits). The disaggregated
        handoff re-admits a prefilled sequence with ``done0 == plen``,
        its already-emitted first token as ``tok0`` and ``w0 = 1`` so
        the slot decodes immediately; plain admissions pass
        ``tok0 = w0 = 0``."""
        b = self.max_batch
        ls = slot - serve_dp_index() * b
        ls = jnp.where((ls >= 0) & (ls < b), ls, b)     # non-owner: drop
        return state._replace(
            prompt=state.prompt.at[ls].set(prompt_row, mode="drop"),
            plen=state.plen.at[ls].set(plen, mode="drop"),
            done=state.done.at[ls].set(done0, mode="drop"),
            remaining=state.remaining.at[ls].set(n_new, mode="drop"),
            active=state.active.at[ls].set(True, mode="drop"),
            widx=state.widx.at[ls].set(w0, mode="drop"),
            out=state.out.at[ls].set(0, mode="drop")
                     .at[ls, 0].set(jnp.where(w0 > 0, tok0, 0),
                                    mode="drop"),
            tok=state.tok.at[ls, 0].set(tok0, mode="drop"),
            task=state.task.at[ls].set(task_id, mode="drop"),
            failed=state.failed.at[ls].set(False, mode="drop"))

    def _kill_dense_impl(self, state: DecodeState, slot) -> DecodeState:
        """Abort one dense slot between loop exits: mark it dead in-graph
        so the next decode call never steps it (DESIGN.md §13). The host
        harvests the output row BEFORE calling this (the state is
        donated)."""
        return state._replace(
            active=state.active.at[slot].set(False),
            remaining=state.remaining.at[slot].set(0),
            failed=state.failed.at[slot].set(False))

    def _kill_paged_impl(self, state: PagedState, slot) -> PagedState:
        """Abort one paged slot between loop exits (cancel / deadline /
        preemption victim / failover drain). Same ownership gating as
        ``_paged_admit_impl``: ``slot`` is global, non-owner replicas
        drop the write via the sentinel row. The slot's block-table row
        is reset host-side right after, so any stale prefill writes the
        row could still route land on the sentinel and drop."""
        b = self.max_batch
        ls = slot - serve_dp_index() * b
        ls = jnp.where((ls >= 0) & (ls < b), ls, b)     # non-owner: drop
        return state._replace(
            active=state.active.at[ls].set(False, mode="drop"),
            remaining=state.remaining.at[ls].set(0, mode="drop"),
            failed=state.failed.at[ls].set(False, mode="drop"))

    def _cow_impl(self, state: PagedState, src, dst, rep) -> PagedState:
        """Copy-on-write one physical block (all layers) — scheduled at
        admit time so the decode loop never writes a shared block. The
        block ids are LOCAL to replica ``rep``'s pool stripe; the other
        replicas redirect the write to the sentinel row and drop it. The
        drafter pools are indexed by the SAME block tables, so the copy
        covers them too: shared prefix blocks carry the drafter's KV
        (task-namespaced prefix keys guarantee the same drafter weights
        produced it)."""
        dst = jnp.where(rep == serve_dp_index(), dst, self._num_blocks)
        repl = dict(caches=transformer.copy_cache_block(state.caches,
                                                        src, dst))
        if self._spec_on:
            repl["dcaches"] = transformer.copy_cache_block(state.dcaches,
                                                           src, dst)
        return state._replace(**repl)

    def _migrate_impl(self, state: PagedState, src_caches, src_dcaches,
                      src_ids, dst_ids, rep) -> PagedState:
        """Disaggregated handoff, device half: batched copy of a finished
        prefill's prompt blocks from the prefill worker's pools into the
        decode pools (DESIGN.md §11). ``src_ids``/``dst_ids`` are
        fixed-width (p_tab,) local-id vectors padded with the sentinel;
        replicas other than ``rep`` sentinel the whole destination
        vector, so only the owning stripe lands writes."""
        dst_ids = jnp.where(rep == serve_dp_index(), dst_ids,
                            self._num_blocks)
        repl = dict(caches=transformer.migrate_cache_blocks(
            state.caches, src_caches, src_ids, dst_ids))
        if self._spec_on:
            repl["dcaches"] = transformer.migrate_cache_blocks(
                state.dcaches, src_dcaches, src_ids, dst_ids)
        return state._replace(**repl)

    def _paged_decode_impl(self, base, bc, pl, *rest) -> PagedState:
        """One jitted while_loop co-batching chunked prefill and decode:
        every step runs a fixed (B, C) token block — prefilling slots
        consume up to C prompt tokens, decoding slots one sampled token
        (pad columns' cache writes are overwritten by the step that owns
        those positions; sentinel table entries drop out-of-allocation
        writes). Compiles ONCE for all prompt lengths.

        With speculation the drafter weights arrive as three extra args
        and decoding slots commit up to spec_k+1 tokens per iteration.
        The verifier's multi-column pass IS the chunked-prefill (B, C)
        pass — prefilling rows keep consuming prompt chunks through it
        while decoding rows score [committed tok, d_1..d_k] in columns
        0..k. The drafter runs against parallel KV pools addressed by the
        SAME block tables; per-row position routing keeps the two row
        classes from clobbering each other's drafter KV: during the k+1
        single-token drafter steps, prefilling rows write at
        out-of-table positions (sentinel drop), and during the one
        prompt-sync pass, decoding rows do."""
        if self._spec_on:
            dbase, dbc, dpl, state, tables, nan_at = rest
        else:
            state, tables, nan_at = rest
        self._decode_traces += 1        # python side effect: runs per trace
        active0 = state.active
        C = self._chunk
        K = self.spec.spec_k
        V = self.cfg.padded_vocab
        rp_on = self.sampling.repetition_penalty != 1.0
        rows = jnp.arange(self.max_batch)
        # any position >= p_tab * page indexes past the block table ->
        # the sentinel row -> writes drop, reads return garbage the mask
        # already excludes
        oob = jnp.int32(self._p_tab * self._page)
        cond = self._loop_cond(active0)

        def body(s):
            is_pf = s.done < s.plen
            start = jnp.where(is_pf, s.done, 0)
            chunk = jax.vmap(
                lambda p, st: jax.lax.dynamic_slice(p, (st,), (C,)))(
                    s.prompt, start)
            ntok = jnp.where(is_pf, jnp.minimum(C, s.plen - s.done), 1)
            dec = jnp.pad(s.tok, ((0, 0), (0, C - 1)))
            toks = jnp.where(is_pf[:, None], chunk, dec)
            task = s.task if self.rt.tasked else None
            logits, caches = transformer.paged_step(
                base, self.cfg, self.rt.spec, bc, pl, toks, s.caches,
                tables, s.done, ntok - 1, task=task, policy=self.policy)
            # NaN guard (DESIGN.md §13): chaos poisons injected rows,
            # then ANY non-finite logit row stops emitting and raises
            # its failed flag for the host to fail the request
            inject = s.active & (nan_at >= 0) & (s.widx >= nan_at)
            logits = jnp.where(inject[:, None], jnp.nan, logits)
            bad = s.active & ~jnp.all(jnp.isfinite(logits), axis=-1)
            key, sub = jax.random.split(self._key_of(s))
            pm = (sampling_lib.history_mask(s.out, s.widx, V)
                  if rp_on else None)
            nxt = sampling_lib.sample(logits, sub, self.sampling,
                                      penalty_mask=pm)
            new_done = s.done + ntok
            # a slot emits a token when its step reached the last prompt
            # position (prefill -> first token) or is decoding
            produced = s.active & (new_done >= s.plen) & ~bad
            col = jnp.where(produced, s.widx, self.out_cap)
            out = s.out.at[rows, col].set(nxt, mode="drop")
            adv = produced.astype(jnp.int32)
            tok = jnp.where(produced[:, None], nxt[:, None], s.tok)
            return PagedState(
                tok=tok, prompt=s.prompt, plen=s.plen, done=new_done,
                remaining=s.remaining - adv,
                active=s.active & ((s.remaining > 1) | ~produced) & ~bad,
                widx=s.widx + adv, out=out, task=s.task,
                key=self._wrap_key(key), caches=caches,
                dcaches=s.dcaches, steps=s.steps + 1,
                drafted=s.drafted, accepted=s.accepted,
                failed=s.failed | bad)

        def spec_body(s):
            is_pf = s.done < s.plen
            start = jnp.where(is_pf, s.done, 0)
            chunk = jax.vmap(
                lambda p, st: jax.lax.dynamic_slice(p, (st,), (C,)))(
                    s.prompt, start)
            ntok_pf = jnp.minimum(C, s.plen - s.done)
            task = s.task if self.rt.tasked else None
            keys = jax.random.split(self._key_of(s), K + 3)
            base_mask = (sampling_lib.history_mask(s.out, s.widx, V)
                         if rp_on else None)
            zero = jnp.zeros_like(s.done)
            # --- drafter phase: K proposals + 1 write-only step landing
            # the last draft's KV (needed next round when all K are
            # accepted). Prefilling rows route their writes out of table.
            dc = s.dcaches
            tok_j = s.tok
            drafts, qs = [], []
            mask_j = base_mask
            for j in range(K + 1):
                dpos = jnp.where(is_pf, oob, s.done + j)
                lg, dc = transformer.paged_step(
                    dbase, self.cfg, self.rt.spec, dbc, dpl, tok_j, dc,
                    tables, dpos, zero, task=task, policy=self.policy)
                if j == K:
                    break
                d_j, q_j = self._propose(lg, mask_j, keys[1 + j])
                drafts.append(d_j)
                if q_j is not None:
                    qs.append(q_j)
                if rp_on:
                    oh = jax.nn.one_hot(d_j, V, dtype=jnp.bool_)
                    mask_j = oh if mask_j is None else (mask_j | oh)
                tok_j = d_j[:, None]
            d = jnp.stack(drafts, axis=1)                   # (B, K)
            # prefilling rows also feed the prompt chunk through the
            # DRAFTER so its cache tracks the prompt; decoding rows'
            # pad columns route out of table (protecting d_1..d_K).
            # cond-gated: pure decode iterations skip the whole pass.
            dec_pad = jnp.pad(s.tok, ((0, 0), (0, C - 1)))

            def sync(dcc):
                toks0 = jnp.where(is_pf[:, None], chunk, dec_pad)
                spos = jnp.where(is_pf, s.done, oob)
                _, dcc = transformer.paged_step(
                    dbase, self.cfg, self.rt.spec, dbc, dpl, toks0, dcc,
                    tables, spos, zero, task=task, policy=self.policy)
                return dcc

            dc = jax.lax.cond(jnp.any(is_pf), sync, lambda dcc: dcc, dc)
            # --- verify: ONE (B, C) pass — prompt chunk for prefilling
            # rows, [committed tok, drafts] for decoding rows
            dv = jnp.pad(jnp.concatenate([s.tok, d], axis=1),
                         ((0, 0), (0, C - (K + 1))))
            toks_v = jnp.where(is_pf[:, None], chunk, dv)
            L, caches = transformer.paged_step(
                base, self.cfg, self.rt.spec, bc, pl, toks_v, s.caches,
                tables, s.done, zero, task=task, policy=self.policy,
                all_logits=True)
            # NaN guard (DESIGN.md §13): poison chaos-injected rows,
            # then fail any row whose relevant logit columns are
            # non-finite — the verifier block for decoding rows, the
            # last-prompt column for prefilling rows
            inject = s.active & (nan_at >= 0) & (s.widx >= nan_at)
            L = jnp.where(inject[:, None, None], jnp.nan, L)
            # prefilling rows: baseline single-token emission off the
            # last real prompt column
            sel = jnp.clip(jnp.where(is_pf, ntok_pf - 1, 0), 0, C - 1)
            Lsel = L[rows, sel]
            fin_dec = jnp.all(jnp.isfinite(L[:, :K + 1]), axis=(1, 2))
            fin_pf = jnp.all(jnp.isfinite(Lsel), axis=-1)
            bad = s.active & ~jnp.where(is_pf, fin_pf, fin_dec)
            nxt_pf = sampling_lib.sample(Lsel, keys[K + 2], self.sampling,
                                         penalty_mask=base_mask)
            # decoding rows: accept/reject over the first K+1 columns
            q = jnp.stack(qs, axis=1) if qs else None
            emitted, n = self._spec_accept(L[:, :K + 1], d, q, base_mask,
                                           keys[K + 1])
            new_done_pf = s.done + ntok_pf
            produced_pf = s.active & (new_done_pf >= s.plen)
            m = jnp.where(is_pf, produced_pf.astype(jnp.int32),
                          jnp.where(s.active,
                                    jnp.minimum(n + 1, s.remaining), 0))
            m = jnp.where(bad, 0, m)    # a failing row commits nothing
            em = jnp.where(is_pf[:, None],
                           jnp.broadcast_to(nxt_pf[:, None],
                                            emitted.shape), emitted)
            cols = jnp.arange(K + 1)[None, :]
            outcol = jnp.where(cols < m[:, None], s.widx[:, None] + cols,
                               self.out_cap)
            out = s.out.at[rows[:, None], outcol].set(em, mode="drop")
            last = jnp.take_along_axis(
                em, jnp.maximum(m - 1, 0)[:, None], axis=1)
            tok = jnp.where((m > 0)[:, None], last, s.tok)
            new_done = jnp.where(is_pf, new_done_pf, s.done + m)
            dec_act = s.active & ~is_pf
            nact = jnp.sum(dec_act.astype(jnp.int32))
            return PagedState(
                tok=tok, prompt=s.prompt, plen=s.plen, done=new_done,
                remaining=s.remaining - m,
                active=(s.active & ((s.remaining > m) | (m == 0))
                        & ~bad),
                widx=s.widx + m, out=out, task=s.task,
                key=self._wrap_key(keys[0]), caches=caches, dcaches=dc,
                steps=s.steps + 1, drafted=s.drafted + K * nact,
                accepted=s.accepted + jnp.sum(jnp.where(dec_act, n, 0)),
                failed=s.failed | bad)

        return jax.lax.while_loop(
            cond, spec_body if self._spec_on else body, state)

    # ------------------------------------------------------------------
    # base-weight snapshot (quantized serving restarts, DESIGN.md §8)
    # ------------------------------------------------------------------

    @property
    def base_weights(self):
        """The base pytree the step graphs actually read — with
        weights=int8 these are the packed ``{"q8", "scale"}`` leaves."""
        return self._weights[0]

    def save_base_snapshot(self, path: str) -> str:
        """Snapshot the (possibly int8-quantized) serving base to one
        ``.npz`` so a restart loads packed weights instead of
        re-quantizing the fp base (checkpoint/ckpt.py)."""
        return ckpt_lib.save_base_snapshot(path, self._weights[0])

    def load_base_snapshot(self, path: str) -> None:
        """Replace the serving base with a snapshot saved by an engine of
        the same model/quant configuration (the current base is the
        structure/dtype template)."""
        base = ckpt_lib.load_base_snapshot(path, self._weights[0])
        self._weights = (base,) + self._weights[1:]

    # ------------------------------------------------------------------
    # host-side orchestration
    # ------------------------------------------------------------------

    def init_state(self, key) -> DecodeState:
        b, cap = self.max_batch, self.out_cap
        z = functools.partial(jnp.zeros, dtype=jnp.int32)
        return DecodeState(
            tok=z((b, 1)), pos=z((b,)), remaining=z((b,)),
            active=jnp.zeros((b,), bool), widx=z((b,)), out=z((b, cap)),
            task=z((b,)), key=key,
            caches=transformer.init_caches(self.cfg, b, self.cache_len,
                                           self.cfg.compute_dtype),
            dcaches=(transformer.init_caches(
                self.cfg, b, self.cache_len, self.cfg.compute_dtype,
                num_super_blocks=self._nb_draft)
                if self._spec_on else None),
            steps=jnp.int32(0), drafted=jnp.int32(0),
            accepted=jnp.int32(0), failed=jnp.zeros((b,), bool))

    def _blank_paged_state(self, key, caches, dcaches) -> PagedState:
        """Zeroed slot state over ``caches`` — the slot axis spans ALL
        data replicas (``_slots = |data| * max_batch``); the PRNG key and
        loop counters gain a per-replica leading axis with |data| > 1."""
        b, cap = self._slots, self.out_cap
        z = functools.partial(jnp.zeros, dtype=jnp.int32)
        return PagedState(
            tok=z((b, 1)), prompt=z((b, self._lp)), plen=z((b,)),
            done=z((b,)), remaining=z((b,)),
            active=jnp.zeros((b,), bool), widx=z((b,)), out=z((b, cap)),
            task=z((b,)), key=self._fleet_key(key), caches=caches,
            dcaches=dcaches, steps=self._zero_ctr(),
            drafted=self._zero_ctr(), accepted=self._zero_ctr(),
            failed=jnp.zeros((b,), bool))

    def init_paged_state(self, key) -> PagedState:
        """Fresh per-slot state over the engine's PERSISTENT block pools
        (ownership of the pool buffers moves into the donated state; the
        host loop hands them back at the end of generate)."""
        caches, self._paged_caches = self._paged_caches, None
        dcaches = None
        if self._spec_on:
            dcaches, self._draft_pools = self._draft_pools, None
        return self._blank_paged_state(key, caches, dcaches)

    def _init_pf_state(self, key) -> PagedState:
        """Fresh PREFILL-WORKER slot state over the prefill pools
        (DESIGN.md §11) — structurally identical to the decode state, so
        every jitted step graph serves both workers from one trace."""
        caches, self._pf_caches = self._pf_caches, None
        dcaches = None
        if self._spec_on:
            dcaches, self._pf_draft_pools = self._pf_draft_pools, None
        return self._blank_paged_state(key, caches, dcaches)

    def _bucket(self, plen: int) -> int:
        for bkt in self.prompt_buckets:
            if bkt >= plen:
                return min(bkt, self.cache_len)
        # no bucket fits: next power of two keeps recompiles logarithmic
        n = 8
        while n < plen:
            n *= 2
        return min(n, self.cache_len)   # prefill cache is cache_len wide

    def _validate_request(self, req: Request):
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        plen = int(prompt.shape[0])
        if plen < 1:
            raise ValueError("empty prompt")
        if not 1 <= req.max_new_tokens <= self.out_cap:
            raise ValueError(
                f"max_new_tokens={req.max_new_tokens} not in [1, out_cap="
                f"{self.out_cap}]")
        if plen + req.max_new_tokens > self.cache_len:
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens ({req.max_new_tokens}) "
                f"exceeds cache_len={self.cache_len}")
        if self.sv.cache_mode == "paged":
            # reject what can NEVER be admitted: a request whose
            # worst-case page count exceeds the whole replica pool would
            # backpressure forever at the FIFO head and livelock the
            # queue behind it (strictly >: an exact fit drains the pool
            # and admits)
            total = -(-(plen + req.max_new_tokens) // self._page)
            if total > self._num_blocks:
                raise ValueError(
                    f"request needs {total} KV pages "
                    f"(ceil(({plen}+{req.max_new_tokens})/{self._page})) "
                    f"but a replica pool holds only {self._num_blocks} "
                    "blocks — it could never be admitted (raise "
                    "num_blocks or split the request)")
        self.rt.check_task(req.task)
        return prompt, plen

    def cancel(self, request_id) -> None:
        """Queue ``request_id`` (Request.request_id, default its batch
        index) for cancellation. Safe to call before generate (the
        request is dropped at submission) or from a chaos/audit hook
        mid-generate: the host loop aborts the request between jitted
        steps — blocks deref'd, adapter pin dropped, status CANCELLED
        with the tokens emitted so far (DESIGN.md §13)."""
        self._cancel_ids.add(request_id)

    def generate(self, requests: Sequence[Request], *,
                 key=None, chaos=None) -> List[np.ndarray]:
        """Serve ``requests`` through the slots; returns, per request, the
        generated token ids (np.ndarray — length max_new_tokens unless
        the request was cancelled / timed out / failed). Fills
        ``self.last_stats`` (tokens/sec, KV blocks in use, prefix-cache
        hit rate, admit/evict counts — serving/stats.py) and
        ``self.last_results`` (one RequestResult per request: tokens,
        terminal status, preemption count — DESIGN.md §13).

        ``chaos``: optional serving.chaos.ChaosInjector driving seeded
        fault injection (forced alloc failures, scatter failures,
        replica kill, NaN logits, scripted cancels) with per-step
        invariant audits.

        Without an explicit ``key`` the engine advances its own PRNG
        stream, so successive calls draw fresh samples under
        temperature/top-k (greedy is key-independent either way)."""
        for req in requests:
            self._validate_request(req)  # fail fast, before any decode work
        if key is None:
            self._key, key = jax.random.split(self._key)
        self.last_stats = self._new_stats(requests=len(requests))
        self._chaos = chaos
        # request lifecycle bookkeeping: rid -> indices (cancel handle),
        # absolute deadlines, terminal statuses, recompute carry-over
        self._rids = [req.request_id if req.request_id is not None
                      else idx for idx, req in enumerate(requests)]
        t0 = time.perf_counter()
        self._abs_deadline = {
            idx: (t0 + req.deadline_s if req.deadline_s is not None
                  else None)
            for idx, req in enumerate(requests)}
        self._req_status = {}
        self._req_preempts = {}
        try:
            if self.sv.cache_mode == "dense":
                results = self._generate_dense(requests, key)
            else:
                results = self._generate_paged(requests, key)
        finally:
            self._chaos = None
            self._cancel_ids.clear()
        st = self.last_stats
        st.wall_s = time.perf_counter() - t0
        st.tokens_generated = sum(len(r) for r in results)
        st.decode_traces = self._decode_traces
        st.prefill_traces = self._prefill_traces
        self.last_results = [
            RequestResult(tokens=r, status=self._req_status.get(i, FINISHED),
                          n_generated=len(r),
                          preemptions=self._req_preempts.get(i, 0))
            for i, r in enumerate(results)]
        return results

    # -- dense ---------------------------------------------------------

    def _admit_request(self, state: DecodeState, slot: int, req: Request,
                       task_ref: Optional[int] = None) -> DecodeState:
        """``task_ref``: the index the device graphs gather the adapter
        with — the registry's pool slot on paging engines (the pooled
        factors are indexed by slot), the task id itself otherwise."""
        prompt, plen = self._validate_request(req)
        t = req.task if task_ref is None else task_ref
        pb = self._bucket(plen)
        padded = jnp.zeros((1, pb), jnp.int32).at[0, :plen].set(prompt)
        task = jnp.int32(t) if self.rt.tasked else None
        last, caches1 = self._prefill(*self._weights, padded,
                                      jnp.int32(plen - 1), task)
        dcaches1 = jnp.int32(0)         # placeholder leaf when spec is off
        if self._spec_on:
            # drafter prefill through the SAME jitted fn (its own trace —
            # the drafter's cache template has nb_draft super-blocks)
            _, dcaches1 = self._prefill(*self._draft_weights, padded,
                                        jnp.int32(plen - 1), task)
        self.last_stats.admitted += 1
        return self._admit(state, jnp.int32(slot), caches1, dcaches1, last,
                           jnp.int32(plen), jnp.int32(req.max_new_tokens),
                           jnp.int32(t))

    def _generate_dense(self, requests, key) -> List[np.ndarray]:
        st = self.last_stats
        st.page_size = self.cache_len
        st.num_blocks = self.max_batch
        st.block_bytes = self._kv_bytes(self.cache_len)
        if self._spec_on:
            st.block_bytes += self._kv_bytes(
                self.cache_len, num_super_blocks=self._nb_draft)
        # dense reserves the whole max_batch × cache_len cache up front
        st.kv_blocks_peak = self.max_batch
        chaos = self._chaos
        state = self.init_state(key)
        pending = collections.deque(enumerate(requests))
        results: List[Optional[np.ndarray]] = [None] * len(requests)
        meta: List[Optional[int]] = [None] * self.max_batch
        nan_at = np.full((self.max_batch,), -1, np.int32)
        hstep = 0

        def abort_status(idx):
            if self._rids[idx] in self._cancel_ids:
                return CANCELLED
            dl = self._abs_deadline[idx]
            if dl is not None and time.perf_counter() >= dl:
                return TIMEOUT
            return None

        while pending or any(m is not None for m in meta):
            if chaos is not None:
                ev = chaos.tick(hstep)
                for rid in ev["cancels"]:
                    self._cancel_ids.add(rid)
            hstep += 1
            # ---- request lifecycle: cancels / deadlines (DESIGN.md §13)
            keep = collections.deque()
            for idx, req in pending:
                stt = abort_status(idx)
                if stt is None:
                    keep.append((idx, req))
                    continue
                results[idx] = np.zeros((0,), np.int32)
                self._req_status[idx] = stt
                if stt is CANCELLED:
                    st.cancelled += 1
                else:
                    st.timeouts += 1
            pending.clear()
            pending.extend(keep)
            for slot in range(self.max_batch):
                if meta[slot] is None:
                    continue
                idx = meta[slot]
                stt = abort_status(idx)
                if stt is None:
                    continue
                # harvest BEFORE the donating kill invalidates the state
                out = np.asarray(state.out)
                w = int(np.asarray(state.widx)[slot])
                results[idx] = out[slot, :w].copy()
                self._req_status[idx] = stt
                if stt is CANCELLED:
                    st.cancelled += 1
                else:
                    st.timeouts += 1
                state = self._kill(state, jnp.int32(slot))
                nan_at[slot] = -1
                if self._reg_on:
                    self.registries[0].release(requests[idx].task)
                meta[slot] = None
                st.evicted += 1
            # admit pending requests into free slots (dense mode has no
            # Scheduler, so the engine gates on adapter residency here:
            # a head whose task cannot get a pool slot waits for a
            # harvest to unpin one — in-flight slots guarantee progress)
            for slot in range(self.max_batch):
                if meta[slot] is None and pending:
                    idx, req = pending[0]
                    task_ref = None
                    if self._reg_on:
                        acq = self.registries[0].acquire(req.task)
                        if acq is None:
                            st.adapter_waits += 1
                            st.backpressure_waits += 1
                            break
                        if (acq.fault and chaos is not None
                                and chaos.fail_scatter()):
                            # simulated scatter failure: roll the pin
                            # back; the slot stays mapped-but-UNLOADED
                            # and the retry faults again
                            self.registries[0].release(req.task)
                            st.backpressure_waits += 1
                            break
                        if acq.fault:
                            st.adapter_faults += 1
                            if acq.evicted is not None:
                                st.adapter_evictions += 1
                            self._adapter_fault_in(0, acq.slot, req.task)
                        else:
                            st.adapter_hits += 1
                        task_ref = acq.slot
                    pending.popleft()
                    state = self._admit_request(state, slot, req, task_ref)
                    meta[slot] = idx
                    nan_at[slot] = (chaos.nan_for(self._rids[idx])
                                    if chaos is not None else -1)
            # decode every active slot until one finishes
            if bool(np.any(np.asarray(state.active))):
                state = self._decode(*self._step_weights, state,
                                     jnp.asarray(nan_at))
                st.decode_calls += 1
            # evict finished slots (also catches max_new_tokens == 1)
            active = np.asarray(state.active)
            out = np.asarray(state.out)
            widx = np.asarray(state.widx)
            failedv = np.asarray(state.failed)
            for slot in range(self.max_batch):
                if meta[slot] is not None and not active[slot]:
                    idx = meta[slot]
                    results[idx] = out[slot, : int(widx[slot])].copy()
                    if failedv[slot]:
                        # in-graph NaN guard tripped: fail the request
                        # with whatever it emitted before the fault
                        self._req_status[idx] = FAILED
                        st.failed_requests += 1
                        st.numerics_faults += 1
                    if self._reg_on:
                        self.registries[0].release(requests[idx].task)
                    meta[slot] = None
                    nan_at[slot] = -1
                    st.evicted += 1
        self._read_spec_stats(state, st)
        return results  # type: ignore[return-value]

    def _read_spec_stats(self, state, st) -> None:
        """Fold the loop-carried speculation counters into EngineStats.
        With |data| > 1 the counters are per-replica rows: the lockstep
        global loop predicate makes steps identical across replicas
        (max == any row), while drafted/accepted count each replica's
        own rows and sum."""
        st.spec_k = self.spec.spec_k
        st.spec_steps = int(np.asarray(state.steps).max())
        st.draft_tokens = int(np.asarray(state.drafted).sum())
        st.accepted_tokens = int(np.asarray(state.accepted).sum())

    # -- paged ---------------------------------------------------------

    def _generate_paged(self, requests, key) -> List[np.ndarray]:
        st = self.last_stats
        st.page_size = self._page
        st.num_blocks = (self._num_blocks * self._dp
                         * (2 if self._disagg else 1))
        st.block_bytes = self._block_bytes
        st.data_shards = self._dp
        chaos = self._chaos
        for sc in self.scheds + self._pf_scheds:
            sc.stats = st               # block/prefix counters land here
            sc.fault_hook = chaos.fail_alloc if chaos is not None else None
        # per-slot chaos NaN thresholds (-1 = never — the production
        # value; passed as a traced arg so decode_traces stays 1)
        self._nan_at = np.full((self._slots,), -1, np.int32)
        self._pf_nan = np.full((self._slots,), -1, np.int32)
        state = self.init_paged_state(key)
        self._tables[:] = self._num_blocks
        pf_state = None
        if self._disagg:
            pf_state = self._init_pf_state(jax.random.fold_in(key, 1))
            self._pf_tables[:] = self._num_blocks
        # deterministic placement: the router stripes every request over
        # the data replicas up front (per-replica FIFO order = arrival
        # order), so dp decode is reproducible run to run. Queue entries
        # are dicts because the recompute path (preemption / failover)
        # re-enqueues a request with a GROWN prompt and a shrunk token
        # budget (prompt' = prompt + generated, max_new' = max_new - n).
        pendings = [collections.deque() for _ in range(self._dp)]
        rcost = {}
        for idx, req in enumerate(requests):
            prompt, plen = self._validate_request(req)
            cost = plen + req.max_new_tokens
            r = self.router.route(cost)
            rcost[idx] = (r, cost)
            pendings[r].append(dict(idx=idx, req=req, prompt=prompt,
                                    plen=plen,
                                    max_new=req.max_new_tokens,
                                    task=req.task))
        results: List[Optional[np.ndarray]] = [None] * len(requests)
        try:
            state, pf_state = self._paged_loop(state, pf_state, pendings,
                                               rcost, results, st)
        except BaseException:
            self._reset_paged_pool()    # slot refs / donated pool are gone
            raise
        self._paged_caches = state.caches
        if self._spec_on:
            self._draft_pools = state.dcaches
        if self._disagg:
            self._pf_caches = pf_state.caches
            if self._spec_on:
                self._pf_draft_pools = pf_state.dcaches
        self._read_spec_stats(state, st)
        return results  # type: ignore[return-value]

    def _paged_loop(self, state, pf_state, pendings, rcost, results, st):
        """Host half of fleet serving: per-replica admission (straight
        into decode slots, or into the prefill worker under
        disaggregation), the prefill→decode block handoff, stepping the
        worker loops, and harvesting finished slots. Returns the final
        (decode, prefill) states so generate can hand the pool buffers
        back.

        Each iteration additionally runs the request-lifecycle machinery
        (DESIGN.md §13): chaos events, cancel/deadline sweeps that abort
        slots between jitted steps (harvest -> register safe prefix ->
        deref blocks -> drop pin -> in-graph kill), recompute preemption
        of the youngest running request when the FIFO head has been
        backpressured ``preempt_after`` consecutive iterations, and
        replica failover (drain a marked-down replica through the same
        recompute re-admission). While the loop runs, its bookkeeping is
        published on ``self._live`` for ``serving.chaos.audit``."""
        R, B = self._dp, self.max_batch
        chaos = self._chaos
        meta: List[Optional[dict]] = [None] * self._slots
        pf_meta: List[Optional[dict]] = [None] * self._slots
        handoffs = [collections.deque() for _ in range(R)]
        rstat = [dict(replica=r, admitted=0, evicted=0, queue_depth=0,
                      backpressure_waits=0, kv_blocks_peak=0)
                 for r in range(R)]
        pf_stat = (dict(replica=-1, admitted=0, evicted=0, queue_depth=0,
                        backpressure_waits=0, kv_blocks_peak=0,
                        handoffs=0) if self._disagg else None)
        ttft, tpot = [], []
        # idx -> tokens harvested before a preemption / failover kill;
        # the recompute re-admission carries them in the grown prompt
        # and ``finish`` prepends them to the final output
        prior: dict = {}
        # consecutive iterations each replica's FIFO head was blocked
        blocked = [0] * R
        # admission order; the preemption victim is the YOUNGEST running
        # request (max seq) — deterministic, vLLM-recompute style
        seq_ctr = [0]
        self._live = dict(meta=meta, pf_meta=pf_meta, handoffs=handoffs,
                          pendings=pendings, rcost=rcost, results=results)

        def note_peaks(r):
            """Per-replica and global peak-block accounting (manual here
            because handoff allocations bypass Scheduler.plan)."""
            rstat[r]["kv_blocks_peak"] = max(
                rstat[r]["kv_blocks_peak"], self.bms[r].used_blocks)
            if pf_stat is not None:
                pf_stat["kv_blocks_peak"] = max(
                    pf_stat["kv_blocks_peak"],
                    max(bm.used_blocks for bm in self._pf_bms))
            used = sum(bm.used_blocks
                       for bm in self.bms + self._pf_bms)
            st.kv_blocks_peak = max(st.kv_blocks_peak, used)

        def finish(idx, toks, status=None):
            """Terminal bookkeeping for one request: prepend any
            recompute carry-over, record the result + status, refund the
            router (no-op on a replica that was marked down)."""
            arr = np.array(toks, np.int32).reshape(-1)
            pr = prior.pop(idx, None)
            if pr:
                arr = np.concatenate([np.asarray(pr, np.int32), arr])
            results[idx] = arr
            if status is not None:
                self._req_status[idx] = status
            rr, cost = rcost[idx]
            self.router.complete(rr, cost)

        def abort_status(idx):
            if self._rids[idx] in self._cancel_ids:
                return CANCELLED
            dl = self._abs_deadline[idx]
            if dl is not None and time.perf_counter() >= dl:
                return TIMEOUT
            return None

        def count_status(status):
            if status is CANCELLED:
                st.cancelled += 1
            elif status is TIMEOUT:
                st.timeouts += 1

        def abort_decode_slot(slot, status, state):
            """Abort one in-flight decode slot with exact host unwind:
            harvest the output row FIRST (the kill donates the state),
            index the already-computed KV for prefix reuse (prompt +
            generated tokens whose cells are written — skipped when the
            KV is suspect, i.e. status FAILED), deref every block, drop
            the adapter pin, then mask the slot dead in-graph and
            sentinel its table row."""
            m = meta[slot]
            r = slot // B
            out = np.asarray(state.out)
            w = int(np.asarray(state.widx)[slot])
            done = int(np.asarray(state.done)[slot])
            gen = out[slot, :w].astype(np.int32)
            full = np.concatenate([np.asarray(m["prompt"], np.int32), gen])
            known = min(done, len(full))    # tokens with computed KV
            reg = (not self._disagg) and status is not FAILED
            self.scheds[r].release(
                full[:known], m["blocks"], namespace=m["ns"],
                register=reg,
                task=m["task"] if self._reg_on else None)
            state = self._pkill(state, jnp.int32(slot))
            self._tables[slot] = self._num_blocks
            self._nan_at[slot] = -1
            meta[slot] = None
            rstat[r]["evicted"] += 1
            finish(m["idx"], gen, status)
            return state

        def abort_pf_slot(slot, status, pf_state):
            """Abort one mid-prefill slot on the prefill worker: register
            the prompt prefix whose KV is already computed (unless
            FAILED), deref, unpin, kill."""
            m = pf_meta[slot]
            r = slot // B
            done = int(np.asarray(pf_state.done)[slot])
            prompt = np.asarray(m["prompt"], np.int32)
            known = min(done, len(prompt))
            reg = status is not FAILED
            self._pf_scheds[r].release(
                prompt[:known], m["blocks"], namespace=m["ns"],
                register=reg,
                task=m["task"] if self._reg_on else None)
            pf_state = self._pkill(pf_state, jnp.int32(slot))
            self._pf_tables[slot] = self._num_blocks
            self._pf_nan[slot] = -1
            pf_meta[slot] = None
            pf_stat["evicted"] += 1
            finish(m["idx"], [], status)
            return pf_state

        def sweep(state, pf_state):
            """Apply cancels and expired deadlines everywhere a request
            can live: queues, handoff buffers, prefill slots, decode
            slots."""
            swept = False
            for r in range(R):
                keep = collections.deque()
                for ent in pendings[r]:
                    stt = abort_status(ent["idx"])
                    if stt is None:
                        keep.append(ent)
                        continue
                    finish(ent["idx"], [], stt)
                    count_status(stt)
                    swept = True
                pendings[r].clear()
                pendings[r].extend(keep)
                keep = collections.deque()
                for h in handoffs[r]:
                    stt = abort_status(h["idx"])
                    if stt is None:
                        keep.append(h)
                        continue
                    # handoff entries hold PREFILL-pool blocks, already
                    # prefix-registered at pf harvest: deref only
                    self._pf_scheds[r].release(
                        h["prompt"], h["blocks"], namespace=h["ns"],
                        register=False,
                        task=h["task"] if self._reg_on else None)
                    finish(h["idx"], [h["t0"]], stt)
                    count_status(stt)
                    swept = True
                handoffs[r].clear()
                handoffs[r].extend(keep)
            for slot in range(self._slots):
                if meta[slot] is not None:
                    stt = abort_status(meta[slot]["idx"])
                    if stt is not None:
                        state = abort_decode_slot(slot, stt, state)
                        count_status(stt)
                        swept = True
                if pf_meta[slot] is not None:
                    stt = abort_status(pf_meta[slot]["idx"])
                    if stt is not None:
                        pf_state = abort_pf_slot(slot, stt, pf_state)
                        count_status(stt)
                        swept = True
            return state, pf_state, swept

        def preempt_one(r, state):
            """vLLM-recompute preemption: kill the youngest running
            request on replica ``r``, harvest its tokens, free its
            blocks (registering the computed KV so the recompute is a
            warm prefix hit), and re-enqueue it right behind the blocked
            head with prompt' = prompt + generated and the shrunk token
            budget. Deterministic: victim = max admission seq."""
            cand = [s for s in range(r * B, (r + 1) * B)
                    if meta[s] is not None]
            if not cand:
                return state, False
            victim = max(cand, key=lambda s: meta[s]["seq"])
            m = meta[victim]
            out = np.asarray(state.out)
            w = int(np.asarray(state.widx)[victim])
            done = int(np.asarray(state.done)[victim])
            gen = out[victim, :w].astype(np.int32)
            full = np.concatenate([np.asarray(m["prompt"], np.int32), gen])
            known = min(done, len(full))
            self.scheds[r].release(
                full[:known], m["blocks"], namespace=m["ns"],
                register=True,
                task=m["task"] if self._reg_on else None)
            state = self._pkill(state, jnp.int32(victim))
            self._tables[victim] = self._num_blocks
            self._nan_at[victim] = -1
            meta[victim] = None
            rstat[r]["evicted"] += 1
            prior.setdefault(m["idx"], []).extend(int(t) for t in gen)
            self._req_preempts[m["idx"]] = (
                self._req_preempts.get(m["idx"], 0) + 1)
            st.preemptions += 1
            ent = dict(idx=m["idx"], req=m["req"], prompt=full,
                       plen=len(full), max_new=m["max_new"] - w,
                       task=m["task"])
            pendings[r].insert(1, ent)  # right behind the blocked head
            return state, True

        def drain_replica(rdead, state, pf_state):
            """Replica failover (DESIGN.md §13): mark ``rdead`` down in
            the router, write off its device stripe, and push every
            request it held — in-flight decode slots (tokens harvested),
            prefill slots, handoff entries, queued requests — back
            through the router onto healthy replicas via the recompute
            re-admission path. The dead replica's host pools are rebuilt
            empty (its refcounts indexed KV that no longer serves)."""
            self.router.mark_down(rdead)
            st.replicas_lost += 1
            moved = []
            out = np.asarray(state.out)
            widx = np.asarray(state.widx)
            for slot in range(rdead * B, (rdead + 1) * B):
                m = meta[slot]
                if m is None:
                    continue
                w = int(widx[slot])
                gen = out[slot, :w].astype(np.int32)
                prior.setdefault(m["idx"], []).extend(int(t) for t in gen)
                newp = np.concatenate(
                    [np.asarray(m["prompt"], np.int32), gen])
                moved.append(dict(idx=m["idx"], req=m["req"], prompt=newp,
                                  plen=len(newp),
                                  max_new=m["max_new"] - w,
                                  task=m["task"]))
                state = self._pkill(state, jnp.int32(slot))
                self._tables[slot] = self._num_blocks
                self._nan_at[slot] = -1
                meta[slot] = None
                st.failover_requests += 1
            if self._disagg:
                for slot in range(rdead * B, (rdead + 1) * B):
                    m = pf_meta[slot]
                    if m is None:
                        continue
                    moved.append(dict(
                        idx=m["idx"], req=m["req"],
                        prompt=np.asarray(m["prompt"], np.int32),
                        plen=m["plen"], max_new=m["max_new"],
                        task=m["task"]))
                    pf_state = self._pkill(pf_state, jnp.int32(slot))
                    self._pf_tables[slot] = self._num_blocks
                    self._pf_nan[slot] = -1
                    pf_meta[slot] = None
                    st.failover_requests += 1
                for h in handoffs[rdead]:
                    prior.setdefault(h["idx"], []).append(int(h["t0"]))
                    newp = np.concatenate(
                        [np.asarray(h["prompt"], np.int32),
                         np.asarray([h["t0"]], np.int32)])
                    moved.append(dict(idx=h["idx"], req=h["req"],
                                      prompt=newp, plen=len(newp),
                                      max_new=h["max_new"] - 1,
                                      task=h["task"]))
                    st.failover_requests += 1
                handoffs[rdead].clear()
            while pendings[rdead]:
                moved.append(pendings[rdead].popleft())
                st.failover_requests += 1
            self._rebuild_replica_pools(rdead)
            if chaos is not None:
                self.scheds[rdead].fault_hook = chaos.fail_alloc
                if self._disagg:
                    self._pf_scheds[rdead].fault_hook = chaos.fail_alloc
            for ent in moved:
                cost = ent["plen"] + ent["max_new"]
                r2 = self.router.route(cost)    # raises when none are up
                rcost[ent["idx"]] = (r2, cost)
                pendings[r2].append(ent)
            return state, pf_state

        hstep = 0
        try:
            state, pf_state = self._paged_loop_iterations(
                state, pf_state, pendings, rcost, results, st, meta,
                pf_meta, handoffs, rstat, pf_stat, ttft, tpot, prior,
                blocked, seq_ctr, note_peaks, finish, sweep, preempt_one,
                drain_replica, hstep)
        finally:
            self._live = None
            for sc in self.scheds + self._pf_scheds:
                sc.fault_hook = None
        for r in range(R):
            rstat[r]["queue_depth"] = len(pendings[r])
        if ttft:
            st.ttft_s = sum(ttft) / len(ttft)
        if tpot:
            st.tpot_s = sum(tpot) / len(tpot)
        st.replica_stats = rstat + ([pf_stat] if pf_stat else [])
        return state, pf_state

    def _paged_loop_iterations(self, state, pf_state, pendings, rcost,
                               results, st, meta, pf_meta, handoffs,
                               rstat, pf_stat, ttft, tpot, prior, blocked,
                               seq_ctr, note_peaks, finish, sweep,
                               preempt_one, drain_replica, hstep):
        """The iteration body of ``_paged_loop`` (split out so the
        closure scaffolding above stays readable). One iteration =
        chaos events -> lifecycle sweep -> admission (+ preemption) ->
        handoff -> one jitted step per worker -> harvests -> audit."""
        R, B = self._dp, self.max_batch
        chaos = self._chaos

        while (any(pendings) or any(handoffs)
               or any(m is not None for m in meta)
               or any(m is not None for m in pf_meta)):
            progressed = False
            faults0 = (chaos.alloc_faults + chaos.scatter_faults
                       if chaos is not None else 0)
            # ---- chaos events: scripted cancels and the replica kill
            if chaos is not None:
                ev = chaos.tick(hstep)
                for rid in ev["cancels"]:
                    self._cancel_ids.add(rid)
                if ev["kill"] is not None:
                    state, pf_state = drain_replica(int(ev["kill"]),
                                                    state, pf_state)
                    progressed = True
            hstep += 1
            # ---- request lifecycle: cancels / expired deadlines
            state, pf_state, swept = sweep(state, pf_state)
            progressed = progressed or swept
            # ---- admission: pending -> prefill worker (disagg) or
            # straight into this replica's decode slots. Strict FIFO per
            # replica: a blocked head waits for evictions rather than
            # being overtaken (and, with preempt_after set, eventually
            # preempts the youngest running request).
            for r in range(R):
                if not self.router.is_up(r):
                    continue
                scheds = self._pf_scheds if self._disagg else self.scheds
                head_blocked = False
                admitted_any = False
                for slot in range(r * B, (r + 1) * B):
                    mrow = pf_meta if self._disagg else meta
                    if mrow[slot] is not None or not pendings[r]:
                        continue
                    ent = pendings[r][0]
                    prompt, plen = ent["prompt"], ent["plen"]
                    ns = ent["task"] if self._kv_tasked else None
                    # the prefill worker computes prompt KV only (its one
                    # emission needs no extra page), so plan with 0 new
                    # tokens there; decode-side pages come at handoff
                    plan = scheds[r].plan(
                        np.asarray(prompt).tolist(),
                        0 if self._disagg else ent["max_new"],
                        namespace=ns,
                        task=ent["task"] if self._reg_on else None)
                    if plan is None:    # backpressure: out of KV blocks
                        #                 or of adapter slots
                        (pf_stat if self._disagg
                         else rstat[r])["backpressure_waits"] += 1
                        head_blocked = True
                        break
                    if (self._reg_on and plan.adapter_fault
                            and chaos is not None
                            and chaos.fail_scatter()):
                        # simulated adapter-scatter failure BEFORE any
                        # device work: unwind the whole admission —
                        # deref the planned blocks, roll the pin back
                        # (the slot stays mapped-but-UNLOADED; the
                        # retry faults again), uncount the admission
                        for bid in plan.blocks:
                            scheds[r].bm.deref(bid)
                        self.registries[r].release(ent["task"])
                        st.admitted -= 1
                        (pf_stat if self._disagg
                         else rstat[r])["backpressure_waits"] += 1
                        st.backpressure_waits += 1
                        head_blocked = True
                        break
                    pendings[r].popleft()
                    progressed = True
                    admitted_any = True
                    # adapter paging (DESIGN.md §12): the device state
                    # carries the POOL-SLOT index (replica-offset into
                    # the dp-striped pool), never the task id; a cold
                    # task's slice is scattered in first
                    task_ref = ent["task"]
                    if self._reg_on:
                        if plan.adapter_fault:
                            self._adapter_fault_in(r, plan.adapter_slot,
                                                   ent["task"])
                        task_ref = (r * self.reg_cfg.max_resident_tasks
                                    + plan.adapter_slot)
                    target = pf_state if self._disagg else state
                    if plan.cow is not None:
                        target = self._pcow(
                            target, jnp.int32(plan.cow[0]),
                            jnp.int32(plan.cow[1]), jnp.int32(r))
                    tab = (self._pf_tables if self._disagg
                           else self._tables)
                    row = np.full((self._p_tab,), self._num_blocks,
                                  np.int32)
                    row[:len(plan.blocks)] = plan.blocks
                    tab[slot] = row
                    prow = np.zeros((self._lp,), np.int32)
                    prow[:plen] = prompt
                    target = self._padmit(
                        target, jnp.int32(slot), jnp.asarray(prow),
                        jnp.int32(plen), jnp.int32(plan.n_cached),
                        jnp.int32(1 if self._disagg
                                  else ent["max_new"]),
                        jnp.int32(task_ref), jnp.int32(0), jnp.int32(0))
                    seq_ctr[0] += 1
                    rid = self._rids[ent["idx"]]
                    nan_vec = self._pf_nan if self._disagg else self._nan_at
                    nan_vec[slot] = (chaos.nan_for(rid)
                                     if chaos is not None else -1)
                    mrow[slot] = dict(idx=ent["idx"], req=ent["req"],
                                      prompt=prompt,
                                      plen=plen, blocks=plan.blocks,
                                      ns=ns, task=ent["task"],
                                      task_ref=task_ref,
                                      max_new=ent["max_new"],
                                      seq=seq_ctr[0],
                                      t_admit=time.perf_counter(),
                                      t_first=None)
                    if self._disagg:
                        pf_state = target
                        pf_stat["admitted"] += 1
                    else:
                        state = target
                        rstat[r]["admitted"] += 1
                note_peaks(r)
                # ---- recompute preemption (DESIGN.md §13): the FIFO
                # head has been backpressured preempt_after consecutive
                # iterations — free the youngest running request so
                # mixed long/short workloads cannot livelock
                if head_blocked and not admitted_any:
                    blocked[r] += 1
                else:
                    blocked[r] = 0
                N = self.sv.preempt_after
                if (N and not self._disagg and blocked[r] >= N
                        and pendings[r]):
                    state, did = preempt_one(r, state)
                    if did:
                        blocked[r] = 0
                        progressed = True
            # ---- handoff: finished prefills -> decode slots ----
            if self._disagg:
                for r in range(R):
                    if not self.router.is_up(r):
                        continue
                    while handoffs[r]:
                        h = handoffs[r][0]
                        slot = next(
                            (s for s in range(r * B, (r + 1) * B)
                             if meta[s] is None), None)
                        total = -(-(h["plen"] + h["max_new"])
                                  // self._page)
                        if (slot is None
                                or self.bms[r].free_blocks < total):
                            rstat[r]["backpressure_waits"] += 1
                            st.backpressure_waits += 1
                            break       # retried after the next eviction
                        handoffs[r].popleft()
                        progressed = True
                        npf = len(h["blocks"])
                        pairs = self._pf_bms[r].migrate_to(
                            self.bms[r], h["blocks"])
                        assert pairs is not None    # free checked above
                        dst = ([d for _, d in pairs]
                               + [self.bms[r].alloc()
                                  for _ in range(total - npf)])
                        src_ids = np.full((self._p_tab,),
                                          self._num_blocks, np.int32)
                        dst_ids = src_ids.copy()
                        src_ids[:npf] = h["blocks"]
                        dst_ids[:npf] = dst[:npf]
                        state = self._pmigrate(
                            state, pf_state.caches,
                            (pf_state.dcaches if self._spec_on
                             else jnp.int32(0)),
                            jnp.asarray(src_ids), jnp.asarray(dst_ids),
                            jnp.int32(r))
                        row = np.full((self._p_tab,), self._num_blocks,
                                      np.int32)
                        row[:total] = dst
                        self._tables[slot] = row
                        prow = np.zeros((self._lp,), np.int32)
                        prow[:h["plen"]] = h["prompt"]
                        # done0 == plen and w0 = 1: the slot decodes
                        # immediately from the migrated prompt KV, with
                        # the prefill-emitted t0 already in the output
                        state = self._padmit(
                            state, jnp.int32(slot), jnp.asarray(prow),
                            jnp.int32(h["plen"]), jnp.int32(h["plen"]),
                            jnp.int32(h["max_new"] - 1),
                            jnp.int32(h["task_ref"]), jnp.int32(h["t0"]),
                            jnp.int32(1))
                        rstat[r]["admitted"] += 1
                        pf_stat["handoffs"] += 1
                        # the adapter pin taken at prefill admission rides
                        # the handoff (pf + decode share the replica's
                        # registry) and is released at decode harvest
                        meta[slot] = dict(
                            idx=h["idx"], req=h["req"],
                            prompt=h["prompt"], plen=h["plen"],
                            blocks=dst, ns=h["ns"], task=h["task"],
                            task_ref=h["task_ref"],
                            max_new=h["max_new"], seq=h["seq"],
                            t_admit=h["t_admit"], t_first=h["t_first"])
                        # the NaN-injection threshold follows the
                        # request onto its decode slot
                        self._nan_at[slot] = (
                            chaos.nan_for(self._rids[h["idx"]])
                            if chaos is not None else -1)
                    note_peaks(r)
            # ---- step the worker loops until some slot finishes ----
            stepped = False
            if (self._disagg
                    and bool(np.any(np.asarray(pf_state.active)))):
                pf_state = self._pdecode(*self._step_weights, pf_state,
                                         jnp.asarray(self._pf_tables),
                                         jnp.asarray(self._pf_nan))
                st.decode_calls += 1
                stepped = True
            if bool(np.any(np.asarray(state.active))):
                state = self._pdecode(*self._step_weights, state,
                                      jnp.asarray(self._tables),
                                      jnp.asarray(self._nan_at))
                st.decode_calls += 1
                stepped = True
            # ---- harvest prefill completions -> handoff queue ----
            if self._disagg:
                pactive = np.asarray(pf_state.active)
                pout = np.asarray(pf_state.out)
                pfailed = np.asarray(pf_state.failed)
                t = time.perf_counter()
                for slot in range(self._slots):
                    m = pf_meta[slot]
                    if m is None or pactive[slot]:
                        continue
                    progressed = True
                    r = slot // B
                    t0 = int(pout[slot, 0])
                    if pfailed[slot]:
                        # in-graph NaN guard tripped during prefill: the
                        # KV is suspect — fail the request, index nothing
                        self._pf_scheds[r].release(
                            m["prompt"], m["blocks"], namespace=m["ns"],
                            register=False,
                            task=m["task"] if self._reg_on else None)
                        self._pf_tables[slot] = self._num_blocks
                        self._pf_nan[slot] = -1
                        pf_meta[slot] = None
                        pf_stat["evicted"] += 1
                        st.failed_requests += 1
                        st.numerics_faults += 1
                        finish(m["idx"], [], FAILED)
                        continue
                    # prompt KV is complete: index it for prefix reuse
                    # BEFORE the handoff derefs the slot's refs, so the
                    # cached entries stay pinned in the prefill pool
                    if self._pf_prefixes[r] is not None:
                        self._pf_prefixes[r].register(
                            m["prompt"], m["blocks"], namespace=m["ns"])
                    self._pf_tables[slot] = self._num_blocks
                    self._pf_nan[slot] = -1
                    pf_meta[slot] = None
                    pf_stat["evicted"] += 1
                    ttft.append(t - m["t_admit"])
                    if m["max_new"] == 1:
                        # the prefill emission IS the whole output
                        self._pf_scheds[r].release(
                            m["prompt"], m["blocks"], namespace=m["ns"],
                            register=False,
                            task=m["task"] if self._reg_on else None)
                        finish(m["idx"], [t0])
                        continue
                    handoffs[r].append(dict(
                        idx=m["idx"], req=m["req"], prompt=m["prompt"],
                        plen=m["plen"], blocks=m["blocks"], ns=m["ns"],
                        task=m["task"], task_ref=m["task_ref"],
                        max_new=m["max_new"], seq=m["seq"],
                        t0=t0, t_admit=m["t_admit"], t_first=t))
            # ---- harvest decode completions ----
            active = np.asarray(state.active)
            out = np.asarray(state.out)
            widx = np.asarray(state.widx)
            failedv = np.asarray(state.failed)
            t = time.perf_counter()
            for slot in range(self._slots):
                m = meta[slot]
                if m is None:
                    continue
                if m["t_first"] is None and widx[slot] > 0:
                    m["t_first"] = t
                    ttft.append(t - m["t_admit"])
                if active[slot]:
                    continue
                progressed = True
                r = slot // B
                ntok = int(widx[slot])
                bad = bool(failedv[slot])
                # prompt pages are fully computed now: index them for
                # prefix reuse (unless the prefill pool's cache already
                # did, or the NaN guard fired — suspect KV is never
                # indexed), return the rest to the free list
                self.scheds[r].release(m["prompt"], m["blocks"],
                                       namespace=m["ns"],
                                       register=not (self._disagg or bad),
                                       task=(m["task"] if self._reg_on
                                             else None))
                self._tables[slot] = self._num_blocks
                self._nan_at[slot] = -1
                rstat[r]["evicted"] += 1
                # phase split is resolvable only when the first token was
                # observed at an earlier loop exit than the completion
                if (m["t_first"] is not None and ntok > 1
                        and m["t_first"] < t):
                    tpot.append((t - m["t_first"]) / (ntok - 1))
                if bad:
                    st.failed_requests += 1
                    st.numerics_faults += 1
                    finish(m["idx"], out[slot, :ntok], FAILED)
                else:
                    finish(m["idx"], out[slot, :ntok])
                meta[slot] = None
            if not (progressed or stepped):
                faults1 = (chaos.alloc_faults + chaos.scatter_faults
                           if chaos is not None else 0)
                if faults1 > faults0:
                    # the stall was manufactured (injected alloc /
                    # scatter faults blocked every admission this
                    # iteration) — retry, this is not a real deadlock
                    continue
                # nothing decoded, admitted, handed off or harvested:
                # the queued work can never fit (classic case: a request
                # needing more KV blocks than the pool can ever free)
                raise RuntimeError(
                    "paged admission deadlock: request needs more KV "
                    "blocks (or adapter slots) than the pool can ever "
                    "free")
            if chaos is not None and chaos.audit_every_step:
                chaos_mod.audit(self)
        return state, pf_state


# ---------------------------------------------------------------------------
# single-shot helpers (the seed's one-request-shape-at-a-time path — the
# Engine above supersedes them for real serving; tests and benchmarks keep
# them as reference decoders).
# ---------------------------------------------------------------------------


def make_serve_step(cfg: ModelConfig, spec: peft_api.AdapterSpec,
                    *, with_enc: bool = False, kernels=None) -> Callable:
    """Single-token decode step (the decode_* dry-run entry point).

    fn(base, adapter, frozen, token (B,1), caches, pos[, enc_out][, task])
    -> (logits, caches). ``pos`` may be a scalar or a (B,) per-row vector;
    ``task`` a scalar or (B,) task-id vector (4+1d routing); ``kernels`` a
    KernelConfig routing the step through the fused Pallas kernels.
    """
    policy = kernel_dispatch.resolve(kernels)

    def step_fn(base, adapter, frozen, token, caches, pos, enc_out=None,
                task=None):
        bc, pl = peft_api.adapter_factors(spec, adapter, frozen)
        return transformer.decode_step(base, cfg, spec, bc, pl, token,
                                       caches, pos, enc_out=enc_out,
                                       task=task, policy=policy)

    return jax.jit(step_fn, donate_argnums=(4,))


def make_prefill(cfg: ModelConfig, spec: peft_api.AdapterSpec,
                 cache_len: int, *, kernels=None) -> Callable:
    """Prefill: run the full prompt, return (logits, caches padded to
    cache_len). Attention caches come back length-T from the forward pass
    and are placed into the fixed-size decode cache."""
    policy = kernel_dispatch.resolve(kernels)

    def prefill_fn(base, adapter, frozen, tokens, enc_embeds=None,
                   embeds=None, task=None):
        bc, pl = peft_api.adapter_factors(spec, adapter, frozen)
        out = transformer.forward(base, cfg, spec, bc, pl, tokens,
                                  embeds=embeds, enc_embeds=enc_embeds,
                                  task=task, policy=policy)
        caches = _pad_caches(out.caches, cfg, tokens.shape[0], cache_len)
        return out.logits, caches, out.enc_out

    return jax.jit(prefill_fn)
