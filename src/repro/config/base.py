"""Config system.

`ModelConfig` is expressive enough for all 10 assigned architectures plus the
paper's own RoBERTa targets; `ShapeConfig` is one input-shape cell of the
assignment grid; `RunConfig` bundles model + shape + adapter + mesh + trainer
knobs and is what the launcher consumes.

Layer heterogeneity (jamba's 1:7 mamba:attn interleave, xlstm's
sLSTM/mLSTM alternation, MoE-every-k) is expressed as a repeating
**super-block pattern**: `block_pattern` is a tuple of `(mixer, ffn)` pairs
and the model scans over `num_layers / len(block_pattern)` super-blocks.
This keeps the HLO O(pattern) instead of O(num_layers) — essential for
compiling 61-88 layer models at 512 devices (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional

import jax.numpy as jnp

MIXERS = ("attn", "mla", "mamba", "mlstm", "slstm", "none")
FFNS = ("dense", "moe", "none")


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rope scaling in the DeepSeek-V3 form (``rope_scaling`` of type
    "yarn"); models/attention.py::yarn_inv_freq and ``yarn_softmax_scale``
    write its equations down."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | vlm | audio | ssm | hybrid
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    block_pattern: tuple = (("attn", "dense"),)
    mlp: str = "swiglu"            # swiglu | geglu | gelu
    norm_kind: str = "rmsnorm"     # rmsnorm | layernorm
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_embeddings: bool = True
    # --- multi-head latent attention (mixer "mla", models/attention.py) ---
    q_lora_rank: int = 0           # query down-projection width
    kv_lora_rank: int = 0          # key/value latent width
    qk_nope_head_dim: int = 0      # per-head q/k dims without rope
    qk_rope_head_dim: int = 0      # per-head q dims with rope; k's is shared
    v_head_dim: int = 0
    rope_scaling: Optional[YaRN] = None
    # --- leading dense layers ahead of the scanned pattern ---
    first_dense_layers: int = 0    # layers 0..n-1: (mixer, dense SwiGLU)
    dense_d_ff: int = 0            # their width (d_ff is the expert width)
    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    num_shared_experts: int = 0    # kimi-style always-on shared expert(s)
    moe_capacity_factor: float = 2.0  # GShard capacity dispatch (models/moe.py)
    # the router (the published ``scoring_func``): "softmax" top-k,
    # renormalised; "sigmoid": DeepSeek-V3's noaux_tc, sigmoid scores chosen
    # with a per-expert correction bias (models/moe.py::sigmoid_router)
    moe_scoring: str = "softmax"
    # "capacity": GShard slots, overflow dropped; "dropless": every pair
    # computed through a grouped matmul (models/moe.py::dropless_block)
    moe_dispatch: str = "capacity"
    # the chosen experts' weights are scaled by it (routed_scaling_factor)
    moe_routed_scale: float = 1.0
    # the step also returns each expert layer's chosen experts
    # (``moe_routing``, models/moe.py::RECORDS)
    moe_record_routing: bool = False
    # the experts this chip holds: [expert_offset, expert_offset +
    # experts_held) of num_experts (0 -> all); the router still scores all
    experts_held: int = 0
    expert_offset: int = 0
    # load-balance/z losses train the ROUTER — which is frozen under PEFT, so
    # they only add compute + a 0.2TB/step probs gather (kimi dry-run, §Perf
    # iteration K3). 0 disables them; set >0 for full fine-tuning.
    moe_aux_weight: float = 0.0
    # --- mamba ---
    mamba_d_state: int = 16
    mamba_expand: int = 2
    mamba_dt_rank: int = 0         # 0 -> ceil(d_model / 16)
    mamba_conv: int = 4
    # --- enc-dec (whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 0           # fixed encoder length (1500 audio frames)
    # --- frontends (stubs per assignment) ---
    frontend: str = "none"         # none | patch_stub | audio_stub
    frontend_seq: int = 0          # patches/frames prepended to the text seq
    # --- dtypes ---
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    # ------------------------------------------------------------------
    @property
    def padded_vocab(self) -> int:
        """Embedding rows padded to a 128 multiple so the vocab dim shards
        cleanly on any 16-way mesh axis (whisper's 51866 otherwise forces
        fully-replicated multi-GB f32 logits — §Perf iteration W2). Padded
        ids are masked out of the loss; real token ids never touch them."""
        return -(-self.vocab_size // 128) * 128

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.resolved_head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.resolved_head_dim

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def resolved_dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.d_model // 16)

    @property
    def pattern_len(self) -> int:
        return len(self.block_pattern)

    @property
    def num_super_blocks(self) -> int:
        """Scanned super-blocks: the layers after the leading dense ones,
        over the pattern length."""
        n = self.num_layers - self.first_dense_layers
        if n % self.pattern_len:
            raise ValueError(
                f"{self.name}: {n} scanned layers not divisible by "
                f"pattern length {self.pattern_len}")
        return n // self.pattern_len

    @property
    def is_mla(self) -> bool:
        return any(m == "mla" for m, _ in self.block_pattern)

    @property
    def qk_head_dim(self) -> int:
        """MLA's per-head q/k width: the rope-free part plus the rope
        part."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def local_experts(self) -> int:
        """Experts whose weights this model holds."""
        return self.experts_held or self.num_experts

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def total_layers(self) -> int:
        """Adapter L axis: encoder layers (if any) + decoder layers."""
        return self.encoder_layers + self.num_layers

    def validate(self) -> "ModelConfig":
        for mixer, ffn in self.block_pattern:
            if mixer not in MIXERS or ffn not in FFNS:
                raise ValueError(f"bad block pattern entry {(mixer, ffn)}")
        _ = self.num_super_blocks
        if any(f == "moe" for _, f in self.block_pattern):
            if not (self.num_experts and self.experts_per_token):
                raise ValueError(f"{self.name}: moe blocks need num_experts")
        if self.moe_dispatch not in ("capacity", "dropless"):
            raise ValueError(f"moe_dispatch {self.moe_dispatch!r}")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_scoring {self.moe_scoring!r}")
        if self.expert_offset + self.local_experts > self.num_experts:
            raise ValueError(f"{self.name}: held experts out of range")
        if self.is_mla and not (self.q_lora_rank and self.kv_lora_rank
                                and self.qk_rope_head_dim
                                and self.v_head_dim):
            raise ValueError(f"{self.name}: mla needs its ranks and dims")
        if self.first_dense_layers and not self.dense_d_ff:
            raise ValueError(f"{self.name}: leading dense layers need "
                             "dense_d_ff")
        return self


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One cell of the assignment's shape grid."""
    name: str                      # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode

    @property
    def is_train(self) -> bool:
        return self.kind == "train"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Serving-side quantization of the FROZEN half of the model
    (kernels/quant.py, DESIGN.md §8). MetaTT freezes the base transformer
    by construction, so the base matmul weights and the KV cache are pure
    read-only bandwidth in the decode hot path — int8 halves that traffic
    while the trained TT/LoRA adapter factors stay full precision.

    weights: "none" | "int8" — symmetric int8 of the frozen base matrices
        (attention q/k/v/o and dense-FFN up/gate/down), one f32 scale per
        output channel, or per K-group when ``group_size`` > 0. The rank-r
        adapter epilogue runs in full precision either way.
    kv:      "none" | "int8" — int8 paged KV cache: quantized at write
        time per cache cell (token × kv-head, amax/127 over head_dim),
        scales stored in the SAME paged block layout as the cells, so
        prefix sharing and copy-on-write round-trip the quantized
        representation exactly. Paged cache mode only.
    group_size: K rows per weight-scale group; 0 = one scale per output
        channel (whole-K group). Multiples of 128 keep exactly one scale
        row per kernel K-tile; matrices whose K the group does not divide
        fall back to per-channel.
    """
    weights: str = "none"          # none | int8
    kv: str = "none"               # none | int8
    group_size: int = 0

    @property
    def any(self) -> bool:
        return self.weights != "none" or self.kv != "none"

    def validate(self) -> "QuantConfig":
        for name in ("weights", "kv"):
            v = getattr(self, name)
            if v not in ("none", "int8"):
                raise ValueError(
                    f"QuantConfig.{name}={v!r}; want none | int8")
        if self.group_size and self.group_size % 128 != 0:
            raise ValueError(
                f"QuantConfig.group_size={self.group_size} must be a "
                "multiple of the 128-lane MXU native size (one scale row "
                "per kernel K-tile)")
        return self


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Kernel-dispatch policy (DESIGN.md §5) — resolved once into a
    ``repro.kernels.dispatch.KernelPolicy`` and threaded through
    ``AdapterCtx`` into every hot-path call site.

    backend: "auto" (Pallas on TPU, reference XLA elsewhere), "pallas"
        (force the fused kernels — with ``interpret=True`` this is the
        CPU correctness path), or "ref" (force the reference XLA path).
    interpret: None -> interpret off-TPU only; explicit bool overrides
        (the parity tests run ``backend="pallas", interpret=True``).
    fuse_linear: route ``adapted_linear`` through the fused base-matmul +
        rank-r epilogue kernel (one HBM round-trip of the output instead
        of three) whenever the adapter folds to lora-form (A, B).
    flash: route attention through the Pallas flash kernels (blockwise
        online softmax for train/prefill, the decode-shaped variant for
        single-token cached decode).
    bm/bn/bk: tt_linear tile overrides (0 -> per-shape heuristic).
    bq/bkv:   flash-attention tile overrides (0 -> per-shape heuristic).
    quant:    frozen-base / KV quantization (QuantConfig); the serving
        engine reads ``quant.weights`` here to int8-quantize the base once
        at construction (ServeConfig.quant is the KV-side twin).
    """
    backend: str = "auto"          # auto | pallas | ref
    interpret: Optional[bool] = None
    fuse_linear: bool = True
    flash: bool = True
    bm: int = 0
    bn: int = 0
    bk: int = 0
    bq: int = 0
    bkv: int = 0
    quant: QuantConfig = QuantConfig()

    def validate(self) -> "KernelConfig":
        self.quant.validate()
        if self.backend not in ("auto", "pallas", "ref"):
            raise ValueError(f"unknown kernel backend {self.backend!r}; "
                             "want auto | pallas | ref")
        # bm tiles the sublane (row) axis — 8-multiples are legal (f32
        # sublane); tt_linear_batched_a's slot axis defaults to bm=8
        if self.bm and self.bm % 8 != 0:
            raise ValueError(
                f"tile override bm={self.bm} must be a multiple of the "
                "8-row f32 sublane")
        for name in ("bn", "bk", "bq", "bkv"):
            v = getattr(self, name)
            if v and v % 128 != 0:
                raise ValueError(
                    f"tile override {name}={v} must be a multiple of the "
                    "128-lane MXU native size")
        return self


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative multi-token decode (serving/speculative.py,
    DESIGN.md §10). The drafter is a RANK-TRUNCATED slice of the shared
    TT cores — TT bond ranks nest, so keeping the leading ``draft_rank``
    bond columns of G1 / C / G4 (or of the pre-folded lora-form A) yields
    a cheaper adapter that shares the frozen base, the KV layout and the
    task routing with the target model. Per engine step the drafter
    proposes ``spec_k`` tokens against a parallel draft KV region; the
    target model scores all k+1 positions in ONE co-batched pass (the
    chunked-prefill (B, C) path) and an in-graph accept rule commits the
    longest valid prefix — exact argmax match under greedy sampling,
    rejection sampling under temperature (output distribution provably
    unchanged). Rejected positions need no KV rollback: later steps
    overwrite their cells before any attention mask reaches them.

    spec_k: draft tokens proposed per engine step; 0 disables
        speculation (the default — the engine is then bit-identical in
        structure to the non-speculative one).
    draft_rank: TT bond rank of the drafter; 0 keeps the full rank
        (drafter == target adapter — useful to isolate the harness).
        Applies to metatt (live and lora-form) and plain lora runtimes;
        other adapter kinds fall back to the full-rank factors.
    draft_layer_stride: the drafter keeps every stride-th super-block of
        the frozen base (1 = all layers). The draft KV region shrinks by
        the same factor.
    """
    spec_k: int = 0
    draft_rank: int = 0
    draft_layer_stride: int = 1

    @property
    def enabled(self) -> bool:
        return self.spec_k > 0

    def validate(self) -> "SpecConfig":
        if self.spec_k < 0:
            raise ValueError(f"SpecConfig.spec_k={self.spec_k} must be >= 0")
        if self.draft_rank < 0:
            raise ValueError(
                f"SpecConfig.draft_rank={self.draft_rank} must be >= 0 "
                "(0 = full rank)")
        if self.draft_layer_stride < 1:
            raise ValueError(
                f"SpecConfig.draft_layer_stride={self.draft_layer_stride} "
                "must be >= 1")
        return self


@dataclasses.dataclass(frozen=True)
class RegistryConfig:
    """Paged adapter registry (serving/adapter_registry.py, DESIGN.md
    §12). MetaTT's task mode makes each task's marginal footprint one
    core slice, so the engine can serve an open-ended task population
    from a fixed device pool of ``max_resident_tasks`` slots, faulting
    task slices in host→device on demand (one jitted donated scatter, no
    retrace) and evicting idle residents — S-LoRA-style paging, but the
    unit is a TT core column instead of a whole adapter stack.

    max_resident_tasks: device task-slot pool size K per decode replica.
        0 (default) keeps the whole ``num_tasks`` axis device-resident —
        registry off, the pre-registry engine byte-for-byte. K may be
        smaller than the in-flight batch's distinct-task count only at
        the price of admission backpressure: a request whose task cannot
        get a slot waits until a harvest unpins one.
    eviction: idle-resident replacement policy — "lru" (default;
        recency refreshed on every admission hit) or "fifo" (load order
        only — cheaper bookkeeping, worse under skewed reuse).

    Requires a task-routed runtime (metatt 4+1d); the engine rejects the
    combination otherwise. Works in both cache modes and composes with
    quantization, the serve mesh (pool replicated; swaps happen outside
    shard_map), dp replicas (one registry per replica) and speculative
    decode (drafter slices page together with their target slices).
    """
    max_resident_tasks: int = 0
    eviction: str = "lru"          # lru | fifo

    @property
    def enabled(self) -> bool:
        return self.max_resident_tasks > 0

    def validate(self) -> "RegistryConfig":
        if self.max_resident_tasks < 0:
            raise ValueError(
                f"RegistryConfig.max_resident_tasks="
                f"{self.max_resident_tasks} must be >= 0 (0 = all tasks "
                "device-resident)")
        if self.eviction not in ("lru", "fifo"):
            raise ValueError(
                f"RegistryConfig.eviction={self.eviction!r}; want "
                "lru | fifo")
        return self


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving-engine knobs (repro/serving/engine.py).

    cache_mode: "paged" (default) — block/paged KV cache with a host-side
        BlockManager (free list, refcounts, copy-on-write), hash-keyed
        prefix sharing and chunked prefill folded into the one jitted
        decode loop; "dense" — the PR-1 layout (max_batch × cache_len
        reserved per slot, per-bucket prefill graphs), kept as the parity
        baseline.
    max_batch:  decode slots stepped together by the jitted loop.
    cache_len:  per-request bound on prompt_len + max_new_tokens (both
        modes; in paged mode it also sizes the block-table width).
    out_cap:    per-request bound on max_new_tokens.
    page_size:  tokens per KV block (paged mode). 8-multiples keep the
        Pallas paged-attention tile on the f32 sublane grid.
    num_blocks: total KV cache budget in blocks (paged mode); this — not
        the slot count — is what admission is gated on. 0 derives the
        dense-equivalent budget max_batch * ceil(cache_len / page_size).
    prefill_chunk: prompt tokens processed per decode-loop step while a
        slot is prefilling (chunked prefill co-batches with decode in the
        same fixed-shape graph, so there is no per-bucket prefill ladder).
    prefix_cache: share KV blocks between requests with a common prompt
        prefix (hash-chained at page granularity, partial last page
        included; divergence after a shared partial page copies-on-write).
    prompt_buckets: dense mode only — prefill pad buckets.
    quant: QuantConfig — ``quant.kv="int8"`` stores the paged KV pools as
        int8 with per-cell f32 scales in the same block layout (paged mode
        only); ``quant.weights`` here is honored too (merged with
        KernelConfig.quant by the engine).
    mesh_shape: tensor-parallel serving (DESIGN.md §9). Empty tuple
        (default) = single-device engine. A ``(data, model)`` pair builds
        a mesh (sharding/rules.py::serve_mesh) and the engine wraps its
        jitted step graphs in ``shard_map``: KV caches / paged pools
        shard on the kv-head axis over the "model" axis, attention runs
        per-shard on its local head group, the readout computes a
        per-shard vocab stripe and all-gathers the (B, V) logits for
        in-graph sampling. Everything else — TT cores, block table, slot
        state, sampling RNG — is replicated, so greedy decode is
        token-identical to the single-device engine. The "data" axis is
        reserved for replica DP (state is replicated across it today).
        num_heads, num_kv_heads and padded_vocab must each be divisible
        by the "model" axis size.
    tp_axis: mesh axis name the KV/head/vocab sharding applies to
        (default "model"; must be one of the serve-mesh axes).
    router: data-axis request placement policy (serving/router.py,
        DESIGN.md §11) — "least_loaded" (deterministic: fewest queued
        tokens, replica index breaks ties) or "round_robin". Only
        consulted when the mesh's data axis is > 1.
    disagg: split prefill from decode (DESIGN.md §11): a dedicated
        prefill worker pool fills paged KV and hands finished sequences
        to the decode replicas — a host-side block-table transfer plus a
        batched pool-to-pool block copy (BlockManager.migrate_to), no
        retrace. Paged mode only.
    row_parallel: shard the SECOND matmul of each pair — attention
        ``wo``, FFN ``wd`` (with ``wg``/``wu`` column-parallel) —
        row-parallel with a psum epilogue instead of all-gathering the
        activations (DESIGN.md §11). Partial-sum order differs per
        shard, so this trades the column-only mode's bit-exactness for
        one fewer all-gather: near-parity (~1e-3), asserted against the
        default mode as oracle. Needs a serve mesh; incompatible with
        grouped weight quantization (group_size > 0 — scale groups tile
        the K axis the row slice cuts).
    spec: SpecConfig — speculative multi-token decode with the
        rank-truncated TT self-drafter (spec.spec_k > 0 enables it;
        DESIGN.md §10). Works in both cache modes, composes with
        quantization and the serve mesh.
    registry: RegistryConfig — paged adapter registry (DESIGN.md §12).
        ``registry.max_resident_tasks=K`` serves any number of tasks
        from a K-slot device pool per replica, paging task slices on
        demand; 0 keeps every task resident (off).
    preempt_after: recompute preemption for forward progress
        (DESIGN.md §13). When the FIFO head of a replica's admission
        queue has been backpressured for this many CONSECUTIVE host-loop
        iterations, the engine preempts the youngest running request on
        that replica vLLM-recompute-style: its generated tokens are
        harvested, its blocks freed (prompt KV registered in the prefix
        cache, so recompute is cheap) and it re-enqueues behind the
        blocked head with prompt+generated as the new prompt. 0 (the
        default) disables preemption — the head waits for natural
        evictions. Paged, non-disaggregated engines only.

    Data parallelism (DESIGN.md §11): ``mesh_shape=(data, model)`` with
    data > 1 stripes decode slots AND paged-pool blocks across data
    replicas — max_batch and num_blocks are PER-REPLICA figures, each
    replica runs its own Scheduler/BlockManager over its local pool, and
    a front-end Router places requests deterministically, so dp=N greedy
    decode is token-identical to dp=1 on the same request set. Paged
    mode only (the dense layout has no block pool to stripe).
    """
    max_batch: int = 4
    cache_len: int = 64
    out_cap: int = 32
    cache_mode: str = "paged"      # paged | dense
    page_size: int = 16
    num_blocks: int = 0
    prefill_chunk: int = 8
    prefix_cache: bool = True
    prompt_buckets: tuple = ()
    quant: QuantConfig = QuantConfig()
    mesh_shape: tuple = ()         # () | (data, model)
    tp_axis: str = "model"
    router: str = "least_loaded"   # least_loaded | round_robin
    disagg: bool = False
    row_parallel: bool = False
    spec: SpecConfig = SpecConfig()
    registry: RegistryConfig = RegistryConfig()
    preempt_after: int = 0         # 0 = recompute preemption off

    @property
    def pages_per_request(self) -> int:
        """Block-table width: worst-case pages one request can touch."""
        return -(-self.cache_len // self.page_size)

    @property
    def resolved_num_blocks(self) -> int:
        return self.num_blocks or self.max_batch * self.pages_per_request

    def validate(self) -> "ServeConfig":
        if self.cache_mode not in ("paged", "dense"):
            raise ValueError(f"unknown cache_mode {self.cache_mode!r}; "
                             "want paged | dense")
        self.quant.validate()
        self.spec.validate()
        self.registry.validate()
        if self.spec.enabled and self.spec.spec_k + 1 > self.cache_len:
            raise ValueError(
                f"SpecConfig.spec_k={self.spec.spec_k}: the verifier "
                f"scores spec_k+1 positions per step, which must fit in "
                f"cache_len={self.cache_len}")
        if self.quant.kv == "int8" and self.cache_mode != "paged":
            raise ValueError(
                "kv=int8 quantization is implemented for the paged cache "
                "layout only (per-page scale pools); use "
                "cache_mode='paged'")
        for name in ("max_batch", "cache_len", "out_cap", "page_size",
                     "prefill_chunk"):
            if getattr(self, name) < 1:
                raise ValueError(f"ServeConfig.{name} must be >= 1")
        if self.mesh_shape:
            if len(self.mesh_shape) != 2 \
                    or any(int(s) < 1 for s in self.mesh_shape):
                raise ValueError(
                    f"ServeConfig.mesh_shape={self.mesh_shape!r} must be "
                    "a (data, model) pair of positive ints (empty for "
                    "single-device serving)")
            if self.tp_axis not in ("data", "model"):
                raise ValueError(
                    f"ServeConfig.tp_axis={self.tp_axis!r} must name a "
                    "serve-mesh axis (data | model)")
            if int(self.mesh_shape[0]) > 1 and self.cache_mode != "paged":
                raise ValueError(
                    "data-parallel serving (mesh_shape data axis > 1) "
                    "stripes the paged block pool across replicas; use "
                    "cache_mode='paged'")
        if self.router not in ("least_loaded", "round_robin"):
            raise ValueError(
                f"ServeConfig.router={self.router!r}; want "
                "least_loaded | round_robin")
        if self.disagg and self.cache_mode != "paged":
            raise ValueError(
                "disaggregated prefill/decode hands off paged KV blocks; "
                "use cache_mode='paged'")
        if self.row_parallel:
            if not self.mesh_shape:
                raise ValueError(
                    "row_parallel is a serve-TP variant; set mesh_shape")
            if self.quant.group_size:
                raise ValueError(
                    "row_parallel row-slices the K axis of wo/wd, which "
                    f"grouped quant scales (group_size="
                    f"{self.quant.group_size}) tile; use per-channel "
                    "scales (group_size=0)")
        if self.cache_mode == "paged" and self.page_size % 8 != 0:
            raise ValueError(
                f"page_size={self.page_size} must be a multiple of the "
                "8-row f32 sublane (the paged-attention kernel tiles "
                "(page, head_dim) blocks)")
        if self.preempt_after < 0:
            raise ValueError(
                f"ServeConfig.preempt_after={self.preempt_after} must be "
                ">= 0 (0 disables recompute preemption)")
        if self.preempt_after and self.cache_mode != "paged":
            raise ValueError(
                "recompute preemption frees paged KV blocks; it needs "
                "cache_mode='paged'")
        if self.preempt_after and self.disagg:
            raise ValueError(
                "preempt_after targets decode-side admission; the "
                "disaggregated prefill worker has its own pool and is "
                "not preemptible (set preempt_after=0 with disagg=True)")
        if self.cache_mode == "paged" \
                and self.resolved_num_blocks < self.pages_per_request:
            raise ValueError(
                f"num_blocks={self.resolved_num_blocks} cannot hold even "
                f"one worst-case request ({self.pages_per_request} pages "
                f"of {self.page_size} for cache_len={self.cache_len})")
        return self


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 1e-3               # paper's MetaTT grid: {1e-3, 5e-4}
    betas: tuple = (0.9, 0.999)
    eps: float = 1e-8
    weight_decay: float = 0.0      # paper App. D: weight_decay = 0.0
    warmup_ratio: float = 0.06     # paper App. A.3
    grad_clip: float = 3.0         # paper App. B: max grad norm 3.0
    schedule: str = "linear"       # linear | cosine | constant


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    microbatch: int = 0            # 0 -> no gradient accumulation
    remat: str = "block"           # none | block (checkpoint each super-block)
    seed: int = 42                 # one of the paper's seeds (App. D)
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: str = ""
    ckpt_keep: int = 3
    grad_compression: str = "none"  # none | int8 | topk
    train_base: bool = False       # True -> full fine-tuning baseline (FT row)
    # DMRG-in-training: transport AdamW moments through each sweep (warm
    # carry, core/dmrg.py) instead of the paper's cold re-initialization
    dmrg_warm_moments: bool = True


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    shape: tuple = (1,)
    axes: tuple = ("data",)
    multi_pod: bool = False


@dataclasses.dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    adapter_kind: str = "metatt"   # metatt | lora | vera | lotr | none
    adapter_variant: str = "4d"    # metatt only: 4d | 5d | 4+1d | 4+ed
    adapter_rank: int = 8
    adapter_alpha: float = 4.0
    adapter_matrices: tuple = ()   # () -> arch default
    num_tasks: int = 0
    optimizer: OptimizerConfig = OptimizerConfig()
    train: TrainConfig = TrainConfig()
    kernels: KernelConfig = KernelConfig()
