"""Unified model configuration for all assigned architectures.

One dataclass covers the LM family (dense / MoE / hybrid-SSM / xLSTM), the
cross-attn VLM and the enc-dec audio model; per-arch files under
``repro/configs/`` instantiate it with the exact published hyperparameters
and a ``reduced()`` smoke variant.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core.sparse_mlp import SparseInferConfig


@dataclasses.dataclass(frozen=True)
class SLATier:
    """One per-request service tier (DESIGN.md §5).

    The paper's alpha is a per-token knob (``core/predictor.py:margins``
    broadcasts batch alphas), so each request can pick its own point on the
    accuracy/sparsity curve: a tier maps to a per-slot alpha offset added to
    the per-layer schedule, and — when the controller runs — to a per-tier
    density target the feedback loop regulates independently.
    """

    name: str
    alpha_offset: float = 0.0   # added to every layer's schedule alpha
    target_scale: float = 1.0   # multiplies ControllerConfig.target_density
    # Preemption rank under pool pressure (DESIGN.md §11): LOWER priority is
    # parked first when the scheduler must relieve exhaustion, and only
    # strictly-lower tiers may be preempted on behalf of a deadline-pressed
    # queue head.  Ties break on fewest emitted tokens (least sunk work).
    priority: int = 1

    def target(self, base_density: float) -> float:
        return float(min(1.0, max(1e-3, base_density * self.target_scale)))


# Tier offsets are sized for the reduced CPU configs (margin thresholds move
# in counts of (alpha-1)*N_pos, so small d needs large offsets); paper-scale
# models would use offsets in the 0.01-0.05 band (§V-B).
DEFAULT_SLA_TIERS: tuple = (
    SLATier("latency", alpha_offset=-0.25, target_scale=0.6, priority=0),
    SLATier("balanced", priority=1),
    SLATier("quality", alpha_offset=0.25, target_scale=1.4, priority=2),
)


@dataclasses.dataclass(frozen=True)
class ControllerConfig:
    """Online adaptive-alpha controller for the serve path (DESIGN.md §4).

    The paper's alpha is "a control knob for optimizing LLM inference"
    (§V-B); this closes the loop at runtime: between decode steps the server
    nudges each layer's alpha so realized density tracks ``target_density``,
    with periodic masked-path audit steps bounding the false-negative rate.
    """

    enabled: bool = False
    target_density: float = 0.25   # per-layer realized density setpoint
    gain: float = 0.5              # integral gain on (density - target)
    ema: float = 0.4               # EMA weight of a new observation
    alpha_min: float = 0.25        # clamp floor (most aggressive skipping)
    alpha_max: float = 8.0         # clamp ceiling (most conservative)
    max_step: float = 0.25         # per-update |Δalpha| bound (slew limit)
    audit_period: int = 8          # masked-path audit every N decode steps
    fn_budget: float = 0.02        # tolerated active-but-skipped rate
    fn_gain: float = 4.0           # conservatism push per unit FN excess
    adapt_capacity: bool = False   # also re-size capacity from the observed
                                   # keep-rate; a capacity change is a re-jit,
                                   # so it applies between scheduler chunks
                                   # (runtime/server.py:maybe_adapt_capacity)
    per_tier: bool = False         # one (alpha vector, density target) per
                                   # ServeConfig.sla_tiers entry: state is
                                   # (T, L), telemetry aggregates per tier
                                   # (slot-refill scheduler, DESIGN.md §5)
    # --- per-shard adaptive capacity buckets (DESIGN.md §8) ---------------
    per_shard_buckets: bool = True  # under a sharded serve with a capacity
                                    # ladder, let each model shard pick its
                                    # OWN ladder bucket from the controller's
                                    # per-shard union-demand EMAs (a skewed
                                    # shard widens only its local bucket);
                                    # False = one global bucket, every shard
                                    # at C/ms (the pre-2D behavior)
    bucket_tuple_cap: int = 16      # bound on the per-shard bucket-tuple
                                    # ladder: len(ladder)**tp_shards distinct
                                    # pre-jittable executables; above the cap
                                    # the server falls back to uniform
                                    # tuples (with a warning) so the
                                    # executable count stays len(ladder)
    shard_slack: float = 1.3        # per-shard bucket hint headroom over the
                                    # observed shard-local union demand
    # --- sparse chunked prefill telemetry rider (DESIGN.md §9) ------------
    prefill_weight: float = 0.25    # weight of the prefill-density error in
                                    # the alpha update relative to the decode
                                    # density error: prefill chunks fold their
                                    # realized density into a separate EMA and
                                    # nudge alpha at this fraction of the
                                    # decode gain (0 = observe-only; prefill
                                    # telemetry never drives alpha)


@dataclasses.dataclass(frozen=True)
class PagedKVConfig:
    """Paged KV pool for the slot-refill serve path (DESIGN.md §10).

    Replaces the per-slot dense ``max_len`` KV buffers with a global block
    pool + per-slot block tables, so resident capacity is a function of
    *tokens resident* rather than slots × max_len: committed full blocks are
    deduplicated through a hash trie (shared system prompts and resumed
    session history admit by reference instead of re-prefilling), and
    diverging reuse is copy-on-write forked.
    """

    block_size: int = 16    # tokens per pool block; must divide
                            # ServeConfig.max_len, and (when chunked prefill
                            # is on) divide prefill_chunk so trie-aligned
                            # reuse lands on chunk boundaries
    pool_blocks: int = 0    # total pool blocks INCLUDING the two reserved
                            # blocks (null + trash); 0 = auto-size to the
                            # dense equivalent: batch * max_len/block_size
                            # + 2 — same pool bytes as the per-slot dense
                            # buffers it replaces
    prefix_cache: bool = True   # hash-trie admission of committed blocks
                                # (off: the pool still pages, but every
                                # prompt re-prefills from scratch)
    max_sessions: int = 64  # LRU cap on retained session chains; a retained
                            # session pins its blocks against eviction until
                            # the session itself is evicted


@dataclasses.dataclass(frozen=True)
class MetricsConfig:
    """First-class observability for the serve stack (DESIGN.md §12).

    Configures ``runtime/metrics.py:MetricsHub`` — the counter/gauge/
    histogram registry, span histograms, and structured sinks the
    server emits into.  Disabled (the default) the hub is a strict no-op:
    every emit method returns immediately and the serve path is
    bitwise-identical to a metrics-free build (pinned by
    tests/test_metrics.py).
    """

    enabled: bool = False
    jsonl_path: str = ""        # JSONL event-stream sink ("" = in-memory
                                # ring only; see MetricsHub.events())
    snapshot_path: str = ""     # write Prometheus-style exposition on flush()
    cadence: int = 8            # publish gauge families (controller/pool/
                                # shard state) every N decode steps — emission
                                # is cheap but per-step gauge refresh is
                                # redundant at EMA timescales
    hist_max_exact: int = 2048  # histogram observations kept exact (nearest-
                                # rank percentiles); past the cap values fold
                                # into the fixed bucket ladder (0 = exact
                                # forever — what throughput_report uses)
    hist_buckets: tuple = ()    # custom bucket upper bounds (seconds);
                                # () = metrics.DEFAULT_BUCKETS
    watchdog: bool = True       # hook jax compile events: any post-warmup
                                # retrace warns + counts (DESIGN.md §12)
    events_keep: int = 4096     # in-memory event ring size


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | hybrid | xlstm | vlm | encdec
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0            # 0 -> d_model // n_heads

    # attention flavor
    qkv_bias: bool = False                 # qwen1.5
    qk_norm: bool = False                  # qwen3
    attn_softcap: float = 0.0              # gemma2
    final_softcap: float = 0.0             # gemma2
    window: int = 0                        # sliding-window size (local layers)
    local_global_period: int = 0           # gemma2: alternate local/global
    rope_theta: float = 10000.0
    embed_scale: bool = False              # gemma: sqrt(d) embed multiplier
    tie_embeddings: bool = True
    activation: str = "silu"
    gated_mlp: bool = True
    norm: str = "rmsnorm"                  # layernorm for seamless
    post_block_norm: bool = False          # gemma2 pre+post norms

    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    first_dense_layers: int = 0            # deepseek: layer 0 dense
    capacity_factor: float = 1.25
    router_norm_topk: bool = True

    # SSM / hybrid
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_chunk: int = 128
    attn_every: int = 0                    # zamba2: shared attn period
    shared_lora_rank: int = 0              # zamba2 per-invocation LoRA
    slstm_every: int = 0                   # xlstm: sLSTM block period

    # VLM
    cross_every: int = 0                   # cross-attn layer period
    n_image_tokens: int = 0

    # enc-dec (audio)
    n_enc_layers: int = 0
    n_frames: int = 0                      # stub frontend frame embeddings

    # SparseInfer (the paper's technique — first-class config)
    sparse: SparseInferConfig = dataclasses.field(
        default_factory=SparseInferConfig)

    # numerics
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    kv_cache_dtype: str = "bfloat16"   # "int8": quantized KV (scales factored)
    paged_attn_kernel: bool = False    # paged decode attention through the
                                       # pallas page-gather kernel
                                       # (kernels/paged_attn.py) instead of
                                       # the jnp gather path; the jnp path
                                       # is the bitwise reference
                                       # (DESIGN.md §10)

    # execution
    max_seq: int = 4096
    remat: bool = True
    microbatches: int = 1        # grad-accumulation splits of the batch
    loss_chunk: int = 2048
    attn_chunk: int = 1024
    sp_activations: bool = True            # Megatron-SP residual sharding
    pure_fsdp_train: bool = False          # ZeRO-3-only training (no TP)
    seq_shard_kv: bool = False             # long-context decode mode
    weight_gather_serve: bool = False      # ZeRO-3 serving (>HBM archs)

    # ------------------------------------------------------------ derived
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        return -(-self.vocab // 256) * 256

    @property
    def is_decoder_only(self) -> bool:
        return self.family in ("dense", "moe", "hybrid", "xlstm")

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic decode (SSM/hybrid state) — runs long_500k."""
        return self.family in ("hybrid", "xlstm")

    @property
    def d_expert(self) -> int:
        return self.d_ff  # for MoE configs d_ff is the per-expert width

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks)."""
        d, v = self.d_model, self.vocab
        hd = self.resolved_head_dim
        attn = d * hd * (self.n_heads * 2 + self.n_kv_heads * 2)
        if self.family == "moe":
            ffn = 3 * d * self.d_ff * self.n_experts
            ffn += 3 * d * self.d_ff * self.n_shared_experts + d * self.n_experts
        elif self.family == "xlstm":
            di = 2 * d
            ffn = d * 2 * di + 3 * di * di + di * d
            attn = 0
        elif self.family == "hybrid":
            di = 2 * d
            ffn = d * (2 * di + 2 * self.ssm_state + di // self.ssm_head_dim) + di * d
            ffn += (attn + 3 * d * self.d_ff) / max(1, self.attn_every)
            attn = 0
        else:
            n_mats = 3 if self.gated_mlp else 2
            ffn = n_mats * d * self.d_ff
        layers = self.n_layers + self.n_enc_layers
        emb = v * d * (1 if self.tie_embeddings else 2)
        return int(layers * (attn + ffn) + emb)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: routed top-k + shared)."""
        if self.family != "moe":
            return self.param_count()
        d = self.d_model
        hd = self.resolved_head_dim
        attn = d * hd * (self.n_heads * 2 + self.n_kv_heads * 2)
        ffn = 3 * d * self.d_ff * (self.top_k + self.n_shared_experts)
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        return int(self.n_layers * (attn + ffn) + emb)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether an (arch × shape) cell runs, with the skip reason if not."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, ("full-attention KV decode at 500k is quadratic-cost "
                       "prefill / O(L) per-token reads; assignment restricts "
                       "long_500k to SSM/hybrid archs")
    return True, ""
