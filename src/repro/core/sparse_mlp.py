"""The SparseInfer gated MLP — the paper's technique as a composable module.

Four execution strategies (DESIGN.md §3):

``dense``   llama.cpp-equivalent baseline; also the training path.
``masked``  predict + zero-mask. No byte savings; bitwise-identical semantics
            to the paper's skip (used for accuracy studies on any backend).
``gather``  predict -> margin top-C capacity selection -> row-group gather ->
            compact GEMMs -> masked accumulate. XLA path whose HLO bytes
            scale with C: this is what the production dry-run lowers.
``pallas``  fused TPU kernel (scalar-prefetch gather, one HBM pass); validated
            in interpret mode on CPU. Same math as ``gather``.

Weights are neuron-major (DESIGN.md): ``wg_t, wu_t, wd_t ∈ R^{k×d}``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import predictor as P
from repro.core import selection as S
from repro.core.relufication import get_activation, is_sparsifiable


@dataclasses.dataclass(frozen=True)
class SparseInferConfig:
    """First-class framework config for the paper's technique."""

    enabled: bool = False
    strategy: str = "gather"          # dense | masked | gather | pallas
    activation: str = "relu"          # must be sparsifiable when enabled
    alpha_base: float = 1.0           # paper eq. (2)
    alpha_early: float = 1.03         # paper §V-B: early-layer conservatism
    alpha_early_frac: float = 0.5
    capacity_frac: float = 0.20       # C = frac*k (margin top-C), DESIGN.md §2
    group_size: int = 8               # TPU row-group granularity G
    use_actual_sparsity: bool = True  # paper's +AS
    sparse_max_batch: int = 16        # union-mask regime bound (per device)
    # Sequence-axis extension (DESIGN.md §9): apply the predictor during
    # chunked prefill too ("ReLU Strikes Back" — ReLU-fied models are sparse
    # in prefill as well).  Per-position margins reduce through the same
    # batch-union selection the decode strategies use (a chunk is just a
    # batch of token rows), so one group-union serves the whole chunk.
    sparse_prefill: bool = False
    # Per-device token bound for a sparse prefill chunk (the union loosens
    # with more rows, so bigger chunks than this run dense; mirrors
    # sparse_max_batch for the decode regime).
    prefill_max_tokens: int = 128
    fatrelu_threshold: float = 0.0
    local_selection: bool = True      # per-TP-shard top-C (no cross-shard
                                      # gather; EXPERIMENTS.md §Perf iter 2)
    # Capacity-bucket ladder (DESIGN.md §2): optional tuple of capacity
    # fractions the serve path pre-jits one decode step per bucket for; the
    # controller's capacity_hint picks a bucket BETWEEN decode steps (a host
    # dict lookup — no retrace stall).  Empty = static capacity_frac only.
    capacity_buckets: tuple = ()
    # Exact group-count override used by the per-bucket configs the server
    # builds (0 = derive from capacity_frac).  Not meant for user configs.
    capacity_override: int = 0
    # Tensor-parallel shard count over the FFN hidden dim k (DESIGN.md §8).
    # 0 = unsharded.  When > 0, the sparse decode strategies run the
    # SHARD-LOCAL formulation: each shard owns a contiguous k/tp_shards row
    # slice, runs its own batch-union + top-(C/tp_shards) selection, and the
    # partial down-projections / telemetry counts are combined across shards.
    # This field defines the *semantics*; execution placement is orthogonal:
    # under an active mesh with a 'model' axis of this size the computation
    # runs under shard_map (runtime/distributed.py), otherwise the same math
    # is emulated on one device — bitwise-identical either way, which is the
    # invariant the sharded parity tests pin.
    tp_shards: int = 0
    # Data-parallel shard count over the batch-slot dim (DESIGN.md §8).
    # 0 = unsharded (one batch union over the whole batch).  When > 0, the
    # B batch slots split into dp_shards contiguous blocks of B/dp_shards;
    # each block runs its OWN batch-union + capacity selection per model
    # shard, so a data shard's selection never depends on another data
    # shard's tokens (no cross-data communication beyond the output/
    # telemetry reassembly).  Like tp_shards this defines semantics only:
    # under a mesh whose 'data' axis divides it the blocks run shard_map-
    # partitioned, otherwise the identical math is emulated — bitwise
    # identical across placements.
    dp_shards: int = 0
    # Per-model-shard LOCAL selection capacities in groups (DESIGN.md §8):
    # len == tp_shards; shard s's union selection is clamped to
    # shard_bucket_caps[s] groups of its k/tp_shards rows.  The compiled
    # selection width is max(shard_bucket_caps) (one SPMD executable per
    # bucket TUPLE); narrower shards mask their tail via a count clamp that
    # is bitwise-equal to selecting at the narrow width directly
    # (core.selection.clamp_selection).  Empty = uniform shard_capacity.
    # Set by the server's per-shard bucket ladder; not a user knob.
    shard_bucket_caps: tuple = ()
    # Weight quantization for the sparse-MLP matrices (DESIGN.md §13):
    # "" = native fp weights; "int8" = symmetric per-group absmax int8,
    # applied at load time by ``prepare_sparse_params`` /
    # ``models.*.prepare_sparse``.  The predictor keeps consuming fp
    # sign-packs derived from the ORIGINAL weights at quantization time, so
    # predicted selection sets are identical fp-vs-int8 by construction.
    weight_dtype: str = ""
    # Quantization group width: wg/wu scales group along d, wd scales along
    # k; must divide both and be a multiple of group_size (so every
    # selection tile lies inside one wd quant row-group — the epilogue-
    # fusion precondition, core/quantize.py).
    quant_group_size: int = 128

    def alpha_schedule(self) -> P.AlphaSchedule:
        return P.AlphaSchedule(self.alpha_base, self.alpha_early,
                               self.alpha_early_frac)

    def capacity(self, k: int) -> int:
        g = self.group_size
        n_groups = k // g
        if self.capacity_override:
            return min(self.capacity_override, n_groups)
        cap = max(1, int(round(n_groups * self.capacity_frac)))
        # keep gather shapes MXU/VREG friendly
        mult = max(1, 128 // g)
        cap = int(-(-cap // mult) * mult)
        return min(cap, n_groups)

    def capacity_ladder(self, k: int) -> tuple:
        """MXU-aligned group counts for the bucket ladder (sorted, deduped;
        falls back to the single static capacity when no buckets are set)."""
        if not self.capacity_buckets:
            return (self.capacity(k),)
        g = self.group_size
        n_groups = k // g
        mult = max(1, 128 // g)
        caps = set()
        for frac in self.capacity_buckets:
            cap = max(1, int(round(n_groups * float(frac))))
            cap = int(-(-cap // mult) * mult)
            caps.add(min(cap, n_groups))
        return tuple(sorted(caps))

    def shard_capacity(self, k: int) -> int:
        """Per-shard selection capacity (groups) under ``tp_shards``.

        The global bucket capacity must split evenly so every shard's
        compiled grid has the same static shape (one executable per bucket,
        DESIGN.md §8).  With ``shard_bucket_caps`` (per-shard bucket tuple)
        this returns the compiled selection WIDTH, max over the tuple —
        per-shard effective capacities are applied as a count clamp by the
        sharded execution paths."""
        ms = max(1, self.tp_shards)
        if self.shard_bucket_caps:
            caps = tuple(int(c) for c in self.shard_bucket_caps)
            if len(caps) != ms:
                raise ValueError(
                    f"shard_bucket_caps has {len(caps)} entries but "
                    f"tp_shards={ms} (DESIGN.md §8)")
            n_local = (k // self.group_size) // ms
            if any(c < 1 or c > n_local for c in caps):
                raise ValueError(
                    f"shard_bucket_caps {caps} out of range [1, {n_local}] "
                    f"local groups for k={k}, tp_shards={ms}, "
                    f"group_size={self.group_size}")
            return max(caps)
        cap = self.capacity(k)
        if cap % ms or (k // self.group_size) % ms:
            raise ValueError(
                f"capacity {cap} groups / k={k} not divisible by "
                f"tp_shards={ms} (group_size={self.group_size}) — pick "
                "bucket fractions whose MXU-rounded group counts divide the "
                "shard count, or adjust group_size (DESIGN.md §8)")
        return cap // ms


def init_gated_mlp(key: jax.Array, d: int, k: int, dtype=jnp.bfloat16,
                   gated: bool = True) -> dict:
    """Neuron-major gated-MLP params. ``gated=False`` -> plain 2-matrix FFN."""
    kg, ku, kd = jax.random.split(key, 3)
    scale_in = d ** -0.5
    scale_out = k ** -0.5
    params = {
        "wg_t": (jax.random.normal(kg, (k, d)) * scale_in).astype(dtype),
        "wd_t": (jax.random.normal(kd, (k, d)) * scale_out).astype(dtype),
    }
    if gated:
        params["wu_t"] = (jax.random.normal(ku, (k, d)) * scale_in).astype(dtype)
    return params


def prepare_sparse_params(params: dict,
                          cfg: Optional[SparseInferConfig] = None) -> dict:
    """Offline step ① (paper Fig. 1): pack gate-weight sign bits at load
    time.  With ``cfg.weight_dtype == "int8"`` the fp MLP matrices are
    replaced by symmetric per-group int8 leaves + scales (DESIGN.md §13);
    the sign pack is derived from the ORIGINAL fp weights either way."""
    if cfg is not None and cfg.weight_dtype == "int8":
        from repro.core import quantize as Q
        return Q.quantize_mlp_node(params, cfg.quant_group_size,
                                   cfg.group_size)
    out = dict(params)
    out["sign_wg"] = P.pack_signs(params["wg_t"])
    return out


def _act(cfg: SparseInferConfig):
    if cfg.activation == "fatrelu" or cfg.fatrelu_threshold > 0.0:
        return get_activation("fatrelu", cfg.fatrelu_threshold)
    return get_activation(cfg.activation)


# Telemetry contract shared by all four strategies (DESIGN.md §4/§5): every
# ``return_stats=True`` call yields exactly these float32 arrays shaped like
# the TOKEN dims of the input (``x.shape[:-1]``), so the serve path can stack
# them per layer under scan — (L, B) per decode step — and aggregate per SLA
# tier on the host regardless of the strategy in use.  Quantities that only
# exist at batch/union granularity (gather's capacity clamp, the fused
# kernel's selection) are broadcast over the token axis.
MLP_STAT_KEYS = (
    "predicted_density",   # fraction of k the predictor keeps (margin <= 0)
    "realized_density",    # fraction of k this TOKEN got of its predicted
                           # set (post capacity clamp); batch-shared on paths
                           # without per-token accounting (see DESIGN.md §4)
    "actual_density",      # fraction of k truly active (gate > 0), measured
                           # on whatever rows this strategy computed
    "false_neg_rate",      # active-but-skipped fraction; exact on full-gate
                           # paths (dense/masked audits), in-union proxy on
                           # the pallas path's in-kernel telemetry
    "overflow_frac",       # predicted-active fraction dropped by the C clamp
    "union_demand_frac",   # fraction of k the BATCH-UNION selection demands
                           # (selected + clamp-dropped) — what capacity_hint
                           # must cover; 1.0 on dense
)


# Optional extra telemetry keys emitted by the sharded (``tp_shards > 0``)
# strategies, shaped token dims + (tp_shards,).  Not part of the
# MLP_STAT_KEYS contract — the serve path's DistributedController pops them
# for skew diagnosis / per-shard bucket hints before the per-tier / batch
# aggregation sees the dict (DESIGN.md §8).
SHARD_STAT_KEY = "shard_realized_density"
# per-shard union selection demand (selected + clamp-dropped groups of the
# shard's OWN rows, as a fraction of its local k) — what the per-shard
# bucket ladder must cover
SHARD_UNION_KEY = "shard_union_frac"
SHARD_RIDER_KEYS = (SHARD_STAT_KEY, SHARD_UNION_KEY)


# Sentinel alpha that makes ANY row predict all-sparse (margin strictly
# positive for every neuron), dropping it from the batch/chunk union
# selection.  The slot-refill scheduler drains finished or mid-prefill slots
# with it (runtime/server.py re-exports); the chunked-prefill path assigns it
# to pad positions so prompt padding never inflates the union (DESIGN.md §9).
DEAD_SLOT_ALPHA = -1e9


def zero_mlp_stats(shape: tuple = (), tp_shards: int = 0) -> dict:
    """Zero telemetry pytree.  ``tp_shards`` > 0 adds the per-shard keys so
    layers without a sparse MLP (MoE blocks) stack against sharded layers'
    stats under scan without a pytree-structure mismatch."""
    out = {k: jnp.zeros(shape, jnp.float32) for k in MLP_STAT_KEYS}
    if tp_shards:
        for k in SHARD_RIDER_KEYS:
            out[k] = jnp.zeros(shape + (tp_shards,), jnp.float32)
    return out


def _stats(shape: tuple = (), **kw) -> dict:
    out = zero_mlp_stats(shape)
    for k, v in kw.items():
        assert k in out, k
        out[k] = jnp.broadcast_to(jnp.asarray(v, jnp.float32), shape)
    return out


def dense_mlp(params: dict, x: jax.Array, cfg: SparseInferConfig,
              return_stats: bool = False):
    """Baseline gated MLP: (σ(x·Wg) ⊙ (x·Wu)) · Wd^T  (paper eq. 1)."""
    params = _dense_params(params)
    act = _act(cfg)
    g1 = act(x @ params["wg_t"].T.astype(x.dtype))
    h1 = g1
    if "wu_t" in params:
        h1 = h1 * (x @ params["wu_t"].T.astype(x.dtype))
    y = h1 @ params["wd_t"].astype(x.dtype)
    if return_stats:
        return y, _stats(x.shape[:-1],
                         predicted_density=1.0, realized_density=1.0,
                         actual_density=jnp.mean(g1 > 0, axis=-1),
                         union_demand_frac=1.0)
    return y


def _dense_params(params: dict) -> dict:
    """fp view of a (possibly int8-quantized) MLP node for the strategies
    that want plain matrices — dense prefill, the masked audit, the XLA
    gather (DESIGN.md §13).  fp nodes pass through untouched."""
    if "wg_q" not in params:
        return params
    from repro.core import quantize as Q
    return Q.dense_view(params)


def _margins(params: dict, x: jax.Array, alpha) -> jax.Array:
    d = x.shape[-1]
    sign_wg = params.get("sign_wg")
    if sign_wg is None:
        sign_wg = P.pack_signs(params["wg_t"])
    packed_x = P.pack_signs(x)
    return P.margins(sign_wg, packed_x, d, alpha)


def masked_mlp(params: dict, x: jax.Array, cfg: SparseInferConfig,
               alpha: float | jax.Array = 1.0,
               return_stats: bool = False):
    """Predict-and-mask path: exact paper semantics, any backend.

    This path computes the FULL gate matmul, so its stats include the exact
    false-negative rate (active neurons the predictor skipped) — the serve
    controller's periodic dense-audit steps run through here (DESIGN.md §4).
    ``alpha`` may be a scalar or an array broadcasting against the token
    dims of ``x`` (per-slot SLA alphas, DESIGN.md §5).
    """
    params = _dense_params(params)
    act = _act(cfg)
    with jax.named_scope("predict"):
        m = _margins(params, x, alpha)      # (..., k)
    keep = (m <= 0).astype(x.dtype)
    g1 = act(x @ params["wg_t"].T.astype(x.dtype))
    h1 = g1 * keep
    if "wu_t" in params:
        h1 = h1 * (x @ params["wu_t"].T.astype(x.dtype))
    y = h1 @ params["wd_t"].astype(x.dtype)
    if return_stats:
        active = g1 > 0
        k = m.shape[-1]
        union_keep = jnp.any((m <= 0).reshape(-1, k), axis=0)
        stats = _stats(
            x.shape[:-1],
            predicted_density=jnp.mean(keep, axis=-1),
            realized_density=jnp.mean(keep, axis=-1),  # every predicted row
            actual_density=jnp.mean(active, axis=-1),  # computed
            false_neg_rate=jnp.mean(active & (m > 0), axis=-1),
            union_demand_frac=jnp.mean(union_keep),    # no clamp: union keep
        )
        return y, stats
    return y


# The MLP matrices a decode layer scan may keep layer-stacked, (L, k, d),
# for ``gather_mlp(..., layer=)`` (DESIGN.md §3).
STACKED_MATRICES = ("wg_t", "wu_t", "wd_t")


def gathers_from_stack(cfg: SparseInferConfig, params: dict) -> bool:
    """Whether ``gather_mlp`` can take this MLP's row groups straight out of
    its layer-stacked matrices: the gather strategy on fp weights with the
    predictor's sign pack at hand, unsharded (no tp/dp shards, no
    tensor-parallel mesh axis).  A layer scan then keeps those matrices
    whole instead of slicing every layer's out of the stack; a slice of a
    whole matrix costs more bytes than the rows the gather reads."""
    from repro.sharding import rules as R
    mesh = R.current_mesh()
    return (cfg.enabled and cfg.strategy == "gather"
            and not (cfg.tp_shards or cfg.dp_shards)
            and "sign_wg" in params and "wg_q" not in params
            and (mesh is None or not R.tp_axis(mesh)
                 or R.axis_size(mesh, "model") == 1))


def own_layer(params: dict, layer: Optional[jax.Array]) -> dict:
    """The layer's own matrices out of a node whose ``STACKED_MATRICES`` are
    layer-stacked; ``layer=None`` means the node is one layer's already."""
    if layer is None:
        return params
    return {kk: (jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
                 if kk in STACKED_MATRICES else v)
            for kk, v in params.items()}


def gather_mlp(params: dict, x: jax.Array, cfg: SparseInferConfig,
               alpha: float | jax.Array = 1.0,
               return_stats: bool = False,
               layer: Optional[jax.Array] = None):
    """Capacity-gather path (the TPU-shaped algorithm, in XLA ops).

    x: (d,) | (B, d) with B <= sparse_max_batch (one union mask), or
    (G, B, d) grouped: per-group union + per-group selection/gather — this
    is the production decode layout (one group per data shard, so each
    device gathers only the rows ITS tokens need; weights are replicated
    across data so the batched gather partitions on the index operand).

    ``layer``: the node's ``STACKED_MATRICES`` are layer-stacked (L, k, d)
    and this (traced) layer's row groups are gathered straight out of the
    stack, as rows ``layer * k/g + group`` of its (L*k/g, g, d) view, so
    no whole matrix is sliced (``gathers_from_stack`` says when that
    holds); ``sign_wg`` is the layer's own.
    """
    params = _dense_params(params)
    act = _act(cfg)
    squeeze = x.ndim == 1
    xb = x[None] if squeeze else x
    grouped_in = xb.ndim == 3
    xg = xb if grouped_in else xb[None]           # (G, B, d)
    ngrp, b, d = xg.shape
    k = params["wg_t"].shape[-2]
    g = cfg.group_size
    cap = cfg.capacity(k)

    # per-TP-shard "local selection" (beyond-paper; EXPERIMENTS.md §Perf):
    # each model-shard runs top-(C/ms) over ITS k/ms neurons, so weight-row
    # gathers never cross shards (the global-selection variant makes GSPMD
    # psum the gathered rows). ms=1 degenerates to global selection.
    from repro.sharding import rules as R
    mesh = R.current_mesh()
    ms = 1
    if cfg.local_selection and mesh is not None and R.tp_axis(mesh):
        msz = R.axis_size(mesh, "model")
        if (k // g) % msz == 0 and cap % msz == 0:
            ms = msz
    if layer is not None and ms > 1:
        raise ValueError("a layer-stacked gather runs unsharded: "
                         f"{ms} tensor-parallel shards (DESIGN.md §8)")

    with jax.named_scope("predict"):
        m_tok = _margins(params, xg, alpha)       # (G, B, k) per-token
    with jax.named_scope("select"):
        m = jax.vmap(S.union_margin)(m_tok)       # (G, k) batch union
        gm = jax.vmap(lambda mm: S.group_margins(mm, g))(m)   # (G, k/g)
        gm = gm.reshape(ngrp, ms, (k // g) // ms)  # (G, ms, k/g/ms)
        gm = R.shard(gm, None, "model", None)
        sel, sstats = jax.vmap(jax.vmap(
            lambda mm: S.capacity_select_with_stats(mm, cap // ms)))(gm)
        if ms > 1:
            sel = S.Selection(R.shard(sel.indices, None, "model", None),
                              R.shard(sel.valid, None, "model", None),
                              sel.count)
    cl = cap // ms                                # local capacity per shard
    rows = sel.indices
    if layer is not None:                         # row of the stacked view
        rows = rows + layer * (k // g)

    @jax.named_scope("gather")
    def take_rows(w_t):
        w_grouped = w_t.reshape(ms, -1, g, d)     # (ms, [L*]k/g/ms, g, d)
        w_grouped = R.shard(w_grouped, "model", None, None, None)
        # vmap over shards (operand+indices aligned) then over groups
        out = jax.vmap(jax.vmap(S.take_row_groups, in_axes=(0, 0)),
                       in_axes=(None, 0))(w_grouped, rows)
        # constrain BEFORE merging (Cl, g): the gather output must stay
        # ms-sharded or the reshape constraint forces an all-gather
        out = R.shard(out, None, "model", None, None, None)
        out = out.reshape(ngrp, ms, cl * g, d)    # (G, ms, Cl*g, d)
        return R.shard(out, None, "model", None, None)

    wg = take_rows(params["wg_t"]).astype(xg.dtype)
    wd = take_rows(params["wd_t"]).astype(xg.dtype)
    vmask = jnp.repeat(sel.valid, g, axis=-1).astype(xg.dtype)  # (G,ms,Cl*g)

    g1 = act(jnp.einsum("gbd,gmnd->gbmn", xg, wg)) * vmask[:, None]
    h1 = g1
    if "wu_t" in params:
        wu = take_rows(params["wu_t"]).astype(xg.dtype)
        h1 = h1 * jnp.einsum("gbd,gmnd->gbmn", xg, wu)
    if cfg.use_actual_sparsity:
        # paper's +AS: rows whose gate is exactly zero contribute nothing to
        # the down-proj; zeroing here lets XLA skip their FLOPs in fused form.
        h1 = jnp.where(h1 != 0, h1, jnp.zeros_like(h1))
    # contraction over (ms, n): shard-partial sums -> the TP all-reduce a
    # dense down-proj would have paid anyway
    y = jnp.einsum("gbmn,gmnd->gbd", h1, wd)      # (G, B, d)
    if not grouped_in:
        y = y[0]
    if squeeze:
        y = y[0]
    if return_stats:
        # Per-token stats (contract: token dims of the input).  Counts are
        # in row-group units (a group survives if ANY member does, so
        # group-granularity predicted over-counts the per-neuron rate).
        # Realized density is TRUE PER SLOT (same contract as the pallas
        # kernel's in-kernel counter): the token's own predicted groups that
        # made it into the batch-union selection — NOT the batch-level
        # selection fraction the pre-PR-4 path broadcast, which collapsed
        # per-tier density feedback through this strategy.  Only the union
        # demand remains a batch/union quantity (broadcast over tokens).
        grp_keep = jnp.any(m_tok.reshape(ngrp, b, k // g, g) <= 0, axis=-1)
        sel_mask = jax.vmap(jax.vmap(
            lambda idx, val: jnp.zeros(((k // g) // ms,), jnp.bool_)
            .at[idx].max(val)))(sel.indices, sel.valid)    # (G, ms, k/g/ms)
        sel_mask = sel_mask.reshape(ngrp, k // g)
        pred_frac = jnp.mean(grp_keep, axis=-1)                       # (G,B)
        real_frac = jnp.sum(grp_keep & sel_mask[:, None], axis=-1,
                            dtype=jnp.float32) * g / k                # (G,B)
        sel_frac = sel.count.astype(jnp.float32).sum(-1) * g / k      # (G,)
        over_frac = sstats.overflow.astype(jnp.float32).sum(-1) * g / k
        stats = _stats(
            (ngrp, b),
            predicted_density=pred_frac,
            realized_density=real_frac,
            actual_density=jnp.sum(g1 > 0, axis=(-2, -1)) / k,
            overflow_frac=jnp.maximum(pred_frac - real_frac, 0.0),
            union_demand_frac=(sel_frac + over_frac)[:, None],
        )
        if not grouped_in:
            stats = {kk: v[0] for kk, v in stats.items()}
        if squeeze:
            stats = {kk: v[0] for kk, v in stats.items()}
        # legacy scalar keys kept for examples/notebooks
        n_sel = sel.count.astype(jnp.float32).sum() / ngrp
        stats["capacity"] = cap * g
        stats["selected"] = (n_sel * g).astype(jnp.int32)
        stats["density"] = n_sel * g / k
        return y, stats
    return y


def pallas_mlp(params: dict, x: jax.Array, cfg: SparseInferConfig,
               alpha: float | jax.Array = 1.0,
               interpret: bool | None = None,
               return_stats: bool = False):
    """Single-dispatch-pair fused pipeline (TPU target; interpret on CPU).

    Two Pallas dispatches per sparse MLP (DESIGN.md §2): ① the fused
    predictor (sign-pack + XOR/popcount + alpha margin + group-min in one
    kernel — no packed input or (B, k) count matrix in HBM) emits per-token
    per-group margins; the batch-union top-C selection is a tiny XLA
    epilogue; ② the fused MLP kernel computes the selected groups and, with
    ``return_stats``, accumulates per-token telemetry in-kernel (realized
    gate activity + in-union false-negative proxy), so ``MLP_STAT_KEYS``
    are populated natively PER SLOT — no masked-path audit fallback, and
    per-slot realized density through the union selection (DESIGN.md §4).
    """
    from repro.kernels import ops as kops  # local import: kernels optional
    squeeze = x.ndim == 1
    xb = x[None] if squeeze else x
    b, d = xb.shape
    quantized = "wg_q" in params               # int8 leaves (DESIGN.md §13)
    k = (params["wg_q"] if quantized else params["wg_t"]).shape[0]
    g = cfg.group_size
    cap = cfg.capacity(k)

    sign_wg = params.get("sign_wg")
    if sign_wg is None:
        sign_wg = P.pack_signs(params["wg_t"])
    a = jnp.broadcast_to(jnp.asarray(alpha, jnp.float32), (b,))
    # chunk-token regime (sequence-axis prefill, DESIGN.md §9): beyond the
    # decode MLP kernel's resident-batch budget, its row-tiled form takes
    # over — identical contract, bitwise-equal per-row results (the
    # predictor tiles tokens itself)
    chunked = b > cfg.sparse_max_batch
    with jax.named_scope("predict"):
        gm_tok, pred_cnt = kops.predict_group_margins(
            sign_wg, xb, d, a, group_size=g, interpret=interpret)
    with jax.named_scope("select"):
        gm = S.union_margin(gm_tok)               # (k/g,) batch/chunk union
        sel, sstats = S.capacity_select_with_stats(gm, cap)

    if quantized:
        fused = (kops.fused_sparse_mlp_chunk_q if chunked
                 else kops.fused_sparse_mlp_q)
        out = fused(
            xb, params["wg_q"], params["wg_s"], params.get("wu_q"),
            params.get("wu_s"), params["wd_q"], params["wd_s"],
            sel.indices, sel.count, gm_tok if return_stats else None,
            group_size=g, activation=cfg.activation,
            fatrelu_threshold=cfg.fatrelu_threshold,
            collect_stats=return_stats, interpret=interpret,
        )
    else:
        fused = (kops.fused_sparse_mlp_chunk if chunked
                 else kops.fused_sparse_mlp)
        out = fused(
            xb, params["wg_t"], params.get("wu_t"), params["wd_t"],
            sel.indices, sel.count, gm_tok if return_stats else None,
            group_size=g, activation=cfg.activation,
            fatrelu_threshold=cfg.fatrelu_threshold,
            collect_stats=return_stats, interpret=interpret,
        )
    if not return_stats:
        return out[0] if squeeze else out
    y, tel = out
    tel = tel.astype(jnp.float32)                 # (B, 3): actual, fn, real
    kf = jnp.float32(k)
    predicted = pred_cnt.astype(jnp.float32) * g / kf
    realized = tel[:, 2] / kf
    stats = _stats(
        xb.shape[:-1],
        predicted_density=predicted,
        realized_density=realized,
        actual_density=tel[:, 0] / kf,
        false_neg_rate=tel[:, 1] / kf,
        # per-slot clamp drops: the token's predicted groups not selected
        overflow_frac=jnp.maximum(predicted - realized, 0.0),
        union_demand_frac=sstats.predicted.astype(jnp.float32) * g / kf,
    )
    y = y[0] if squeeze else y
    if squeeze:
        stats = {kk: v[0] for kk, v in stats.items()}
    return y, stats


def apply(params: dict, x: jax.Array, cfg: SparseInferConfig,
          alpha: jax.Array | float | None = None,
          layer_idx: int = 0, num_layers: int = 1,
          strategy: Optional[str] = None,
          layer: Optional[jax.Array] = None, **kw) -> Any:
    """Dispatch the SparseInfer MLP by strategy with the per-layer alpha.

    ``layer``: ``params`` holds layer-stacked matrices (``own_layer``);
    the gather strategy reads its rows from the stack, the others the
    layer's own matrices."""
    strategy = strategy or (cfg.strategy if cfg.enabled else "dense")
    if strategy != "dense" and not is_sparsifiable(cfg.activation):
        raise ValueError(
            f"SparseInfer needs a ReLU-fied activation, got {cfg.activation!r}"
            " — run relufication first (repro.core.relufication.relufy)")
    if alpha is None:
        alpha = cfg.alpha_schedule().alpha_for_layer(layer_idx, num_layers)
    sharded = bool(cfg.tp_shards or cfg.dp_shards) and strategy in (
        "masked", "gather", "pallas")
    if strategy != "gather" or sharded:
        params, layer = own_layer(params, layer), None
    if sharded:
        # Shard-local 2D (data × model) formulation (DESIGN.md §8): under an
        # active mesh this runs shard_map over the ('data', 'model') axes;
        # without one the identical math is emulated on a single device.
        # Local import: runtime imports core, not vice versa.
        from repro.runtime import distributed as DD
        return DD.sharded_apply(params, x, cfg, alpha, strategy=strategy,
                                **kw)
    if strategy == "dense":
        return dense_mlp(params, x, cfg, **kw)
    if strategy == "masked":
        return masked_mlp(params, x, cfg, alpha, **kw)
    if strategy == "gather":
        return gather_mlp(params, x, cfg, alpha, layer=layer, **kw)
    if strategy == "pallas":
        return pallas_mlp(params, x, cfg, alpha, **kw)
    raise ValueError(f"unknown strategy {strategy!r}")
