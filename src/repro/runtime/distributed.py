"""Distributed sparse decode: shard_map execution of the SparseInfer MLP
over a 2D ``(data, model)`` mesh (DESIGN.md §8).

Semantics are defined by TWO config fields, independent of placement:

``SparseInferConfig.tp_shards`` (ms) — the FFN hidden dim ``k`` splits into
ms contiguous row slices.  Each model shard

  * holds its slice of the sign-packed predictor weights and the three
    neuron-major matrices — margins need NO communication (sign bits pack
    along ``d``, the reduction axis, which stays whole);
  * computes its (B, k/G/ms) group-margin slice, its own batch-union and
    its own capacity selection.  The selection width is uniform
    (``shard_capacity``) or, under the per-shard bucket ladder, a
    per-shard effective capacity (``shard_bucket_caps``): the compiled
    width is max over the bucket tuple and each shard clamps its count to
    its own bucket (``core.selection.clamp_selection`` — bitwise-equal to
    selecting at the narrow width directly);
  * produces a partial down-projection and its telemetry in NEURON-COUNT
    units.

``SparseInferConfig.dp_shards`` (ds) — the B batch slots split into ds
contiguous blocks of B/ds.  Each data block runs its OWN batch-union +
selection per model shard, so a block's selection never depends on another
block's tokens.  ds=0/1 degenerates to the single global union.

Execution placement is orthogonal: under a mesh whose ``data`` / ``model``
axes EVENLY DIVIDE (ds, ms), each device loops over its assigned semantic
tiles inside one shard_map body; without a mesh (or with axes of size 1)
the identical static loop runs on one device (``emulated_apply``).  The
telemetry epilogue is a two-axis reduction: ONE psum of the per-token count
matrix over ``model`` (integer-valued float32 — exact under any reduction
order) while the ``data`` out_spec reassembles the (B, n) rows, so the
controller receives the exact ``(L, B)`` matrices it already consumes; the
output combine is one all_gather over ``model`` carrying the partials plus
the per-shard realized/union count columns, followed by a FIXED-ORDER sum
over the full ms-length semantic shard axis — never a psum of f32 partials
— so this combine is BITWISE identical across every placement of the same
(ds, ms) semantics.  Whole decode steps are too where the rest of the step
rounds the same (XLA:CPU, or float32 on a TPU; the invariant
tests/test_distributed.py and the tests/test_mesh_properties.py property
suite pin); in bf16 on a TPU the partitioned attention rounds differently
and tokens depend on placement (DESIGN.md §8).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P_

from repro.core import predictor as P
from repro.core import selection as S
from repro.core import sparse_mlp as SM
from repro.sharding import rules as R
from repro.sharding import sparse as SS

# psum'd count columns, in order (all (B,) float32 neuron counts;
# overflow_frac is derived as predicted - realized in the epilogue)
COUNT_COLS = ("predicted", "realized", "actual", "false_neg", "union")

# trailing rider columns packed next to the output partials so ONE
# all_gather moves the partials and both per-shard skew signals
_RIDER_COLS = 2   # realized, union


def semantic_grid(cfg: SM.SparseInferConfig) -> tuple[int, int]:
    """The (ds, ms) semantic shard grid of a config (1 = unsharded axis)."""
    return max(1, cfg.dp_shards or 1), max(1, cfg.tp_shards or 1)


def shard_caps(cfg: SM.SparseInferConfig, k: int) -> tuple[tuple, int]:
    """Per-model-shard effective group capacities and the compiled
    selection width (max over the tuple).  Uniform configs return
    ``((cap_l,) * ms, cap_l)``."""
    _, ms = semantic_grid(cfg)
    cap_l = cfg.shard_capacity(k)
    if cfg.shard_bucket_caps:
        return tuple(int(c) for c in cfg.shard_bucket_caps), cap_l
    return (cap_l,) * ms, cap_l


def _hidden_rows(params: dict) -> int:
    """FFN hidden dim k of an MLP node, fp or int8-quantized (§13)."""
    w = params.get("wg_t")
    if w is None:
        w = params["wg_q"]
    return w.shape[0]


# ------------------------------------------------------- shard-local math --

def _take_groups(w_t, sel: S.Selection, g: int):
    """Gather the selected row-groups of one local (k_l, d) matrix —
    ``core.selection.take_row_groups``, the same gather the XLA gather
    strategy uses."""
    k_l, d = w_t.shape
    out = S.take_row_groups(w_t.reshape(k_l // g, g, d), sel.indices)
    return out.reshape(sel.indices.shape[0] * g, d)


def _local_mlp(sign_l, params_l, x, cfg: SM.SparseInferConfig, alpha,
               strategy: str, cap_l: int, cap_eff, collect: bool,
               interpret: Optional[bool]):
    """One (data block × model shard) tile's partial MLP.

    ``cap_l`` is the compiled selection width; ``cap_eff`` (None = no
    clamp) is the shard's effective bucket capacity — a python int in the
    emulation, a constant-indexed scalar in the SPMD shard_map body.

    Returns ``(y_partial (B, d) float32, counts | None)`` where counts maps
    ``COUNT_COLS`` to (B,) float32 NEURON counts over the shard's k/ms rows
    (group-granularity rows for the union strategies, matching the
    single-device telemetry contract of each strategy).
    """
    act = SM._act(cfg)
    b, d = x.shape
    k_l = _hidden_rows(params_l)
    g = cfg.group_size
    a = jnp.broadcast_to(jnp.asarray(alpha, jnp.float32), (b,))
    quantized = "wg_q" in params_l              # int8 leaves (DESIGN.md §13)
    gated = ((params_l.get("wu_t") is not None)
             or (params_l.get("wu_q") is not None))

    if strategy == "pallas":
        from repro.kernels import ops as kops
        gm_tok, pred_cnt = kops.predict_group_margins(
            sign_l, x, d, a, group_size=g, interpret=interpret)
        gm = S.union_margin(gm_tok)
        sel, sstats = S.capacity_select_with_stats(gm, cap_l)
        if cap_eff is not None:
            sel, sstats = S.clamp_selection(sel, sstats, cap_eff)
        if quantized:
            out = kops.fused_sparse_mlp_q(
                x, params_l["wg_q"], params_l["wg_s"],
                params_l.get("wu_q"), params_l.get("wu_s"),
                params_l["wd_q"], params_l["wd_s"],
                sel.indices, sel.count, gm_tok if collect else None,
                group_size=g, activation=cfg.activation,
                fatrelu_threshold=cfg.fatrelu_threshold,
                collect_stats=collect, interpret=interpret)
        else:
            out = kops.fused_sparse_mlp(
                x, params_l["wg_t"], params_l.get("wu_t"),
                params_l["wd_t"],
                sel.indices, sel.count, gm_tok if collect else None,
                group_size=g, activation=cfg.activation,
                fatrelu_threshold=cfg.fatrelu_threshold,
                collect_stats=collect, interpret=interpret)
        if not collect:
            return out, None
        y, tel = out
        tel = tel.astype(jnp.float32)           # (B, 3): actual, fn, real
        gf = jnp.float32(g)
        counts = {
            "predicted": pred_cnt.astype(jnp.float32) * gf,
            "realized": tel[:, 2],
            "actual": tel[:, 0],
            "false_neg": tel[:, 1],
            "union": jnp.broadcast_to(
                sstats.predicted.astype(jnp.float32) * gf, (b,)),
        }
        return y, counts

    if quantized:
        # masked/gather want plain matrices: dequantized f32 view, pinned
        # op order (core/quantize.py) so values match every other consumer
        from repro.core import quantize as Q
        params_l = Q.dense_view(params_l)

    m_tok = P.margins(sign_l, P.pack_signs(x), d, a)          # (B, k_l)

    if strategy == "masked":
        keep = m_tok <= 0
        g1 = act(x @ params_l["wg_t"].T.astype(x.dtype))
        h1 = g1 * keep.astype(x.dtype)
        if gated:
            h1 = h1 * (x @ params_l["wu_t"].T.astype(x.dtype))
        y = (h1 @ params_l["wd_t"].astype(x.dtype)).astype(jnp.float32)
        if not collect:
            return y, None
        active = g1 > 0
        kept = jnp.sum(keep, axis=-1, dtype=jnp.float32)
        counts = {
            "predicted": kept,
            "realized": kept,                   # no clamp on this path
            "actual": jnp.sum(active, axis=-1, dtype=jnp.float32),
            "false_neg": jnp.sum(active & (m_tok > 0), axis=-1,
                                 dtype=jnp.float32),
            "union": jnp.broadcast_to(jnp.sum(
                jnp.any(keep, axis=0), dtype=jnp.float32), (b,)),
        }
        return y, counts

    assert strategy == "gather", strategy
    gm_tok = S.group_margins(m_tok, g)                        # (B, k_l/G)
    gm = S.union_margin(gm_tok)
    sel, sstats = S.capacity_select_with_stats(gm, cap_l)
    if cap_eff is not None:
        sel, sstats = S.clamp_selection(sel, sstats, cap_eff)
    wg = _take_groups(params_l["wg_t"], sel, g).astype(x.dtype)
    wd = _take_groups(params_l["wd_t"], sel, g).astype(x.dtype)
    vmask = jnp.repeat(sel.valid, g).astype(x.dtype)          # (cap_l*G,)
    g1 = act(x @ wg.T) * vmask[None]
    h1 = g1
    if gated:
        wu = _take_groups(params_l["wu_t"], sel, g).astype(x.dtype)
        h1 = h1 * (x @ wu.T)
    if cfg.use_actual_sparsity:
        h1 = jnp.where(h1 != 0, h1, jnp.zeros_like(h1))
    y = (h1 @ wd).astype(jnp.float32)
    if not collect:
        return y, None
    grp_keep = gm_tok <= 0                                    # (B, k_l/G)
    sel_mask = jnp.zeros((k_l // g,), jnp.bool_).at[sel.indices].max(
        sel.valid)
    gf = jnp.float32(g)
    counts = {
        "predicted": jnp.sum(grp_keep, axis=-1, dtype=jnp.float32) * gf,
        "realized": jnp.sum(grp_keep & sel_mask[None], axis=-1,
                            dtype=jnp.float32) * gf,
        "actual": jnp.sum(g1 > 0, axis=-1, dtype=jnp.float32),
        "false_neg": jnp.zeros((b,), jnp.float32),
        "union": jnp.broadcast_to(
            (sel.count + sstats.overflow).astype(jnp.float32) * gf, (b,)),
    }
    return y, counts


# ----------------------------------------------------- combine + epilogue --

def _pack_partial(y, counts):
    """(B, d) partial + (realized, union) columns -> (B, d+2) so ONE
    all_gather moves the output partials and both per-shard skew signals."""
    return jnp.concatenate(
        [y, counts["realized"][:, None], counts["union"][:, None]], axis=-1)


def _combine_gathered(gathered, collect: bool, k_l: int):
    """Fixed-order shard combine, shared verbatim by the shard_map body and
    the emulation: sum over the leading FULL (ms) semantic axis — NOT a
    psum — so every execution placement reduces in the same order (bitwise
    parity)."""
    if not collect:
        return gathered.sum(axis=0)
    y = gathered[..., :-_RIDER_COLS].sum(axis=0)
    shard_real = gathered[..., -2].T / jnp.float32(k_l)       # (B, ms)
    shard_union = gathered[..., -1].T / jnp.float32(k_l)      # (B, ms)
    return y, shard_real, shard_union


def _finalize_stats(totals: dict, shard_real, shard_union, k: int,
                    tp_shards: int) -> dict:
    """Summed neuron counts -> the MLP_STAT_KEYS per-token contract.  The
    per-shard riders are emitted only for tensor-sharded configs (data-only
    sharding has no model axis to diagnose)."""
    kf = jnp.float32(k)
    p = totals["predicted"] / kf
    r = totals["realized"] / kf
    stats = SM._stats(
        p.shape,
        predicted_density=p,
        realized_density=r,
        actual_density=totals["actual"] / kf,
        false_neg_rate=totals["false_neg"] / kf,
        overflow_frac=jnp.maximum(p - r, 0.0),
        union_demand_frac=totals["union"] / kf,
    )
    if tp_shards:
        stats[SM.SHARD_STAT_KEY] = shard_real
        stats[SM.SHARD_UNION_KEY] = shard_union
    return stats


# sliceable MLP leaves: each row count is PROPORTIONAL to k (fp matrices
# and quant int8 tiles have k rows; wd scales have k/qg rows), so a shard's
# slice of every leaf is rows [s·r, (s+1)·r) with r = rows // ms
_SLICE_KEYS = ("wg_t", "wu_t", "wd_t",
               "wg_q", "wg_s", "wu_q", "wu_s", "wd_q", "wd_s")


def _slice_params(params: dict, sign_wg, s: int, ms: int) -> tuple:
    k = _hidden_rows(params)
    k_l = k // ms
    local = {}
    for name in _SLICE_KEYS:
        w = params.get(name)
        if w is None:
            continue
        r = w.shape[0] // ms
        local[name] = w[s * r:(s + 1) * r]
    return sign_wg[s * k_l:(s + 1) * k_l], local


def _count_matrix(counts_by_shard: list) -> jax.Array:
    """Stack one data block's per-shard count dicts into (ms, B, n) and sum
    the shard axis — same stacked-sum every placement performs."""
    cmat = jnp.stack(
        [jnp.stack([c[col] for col in COUNT_COLS], axis=-1)
         for c in counts_by_shard], axis=0)                   # (ms, B, n)
    return cmat.sum(axis=0)                                   # (B, n)


# ------------------------------------------------------------ public API --

def emulated_apply(params: dict, x: jax.Array, cfg: SM.SparseInferConfig,
                   alpha, *, strategy: str, return_stats: bool = False,
                   interpret: Optional[bool] = None):
    """The (ds, ms) semantics on ONE device: a static loop over data blocks
    and shard slices through the same ``_local_mlp`` + the same combine the
    shard_map path uses.  This is the parity reference — and the execution
    path when no mesh is active (so a sharded config runs anywhere)."""
    ds, ms = semantic_grid(cfg)
    k = _hidden_rows(params)
    caps, cap_l = shard_caps(cfg, k)
    clamp = bool(cfg.shard_bucket_caps)
    sign_wg = params.get("sign_wg")
    if sign_wg is None:
        sign_wg = P.pack_signs(params["wg_t"])
    b = x.shape[0]
    if b % ds:
        raise ValueError(
            f"batch {b} not divisible by dp_shards={ds} (DESIGN.md §8)")
    bt = b // ds
    a = jnp.broadcast_to(jnp.asarray(alpha, jnp.float32), (b,))
    y_blocks, tot_blocks, real_blocks, union_blocks = [], [], [], []
    for db in range(ds):
        x_t = x[db * bt:(db + 1) * bt]
        a_t = a[db * bt:(db + 1) * bt]
        parts = []
        counts = []
        for s in range(ms):
            sign_l, params_l = _slice_params(params, sign_wg, s, ms)
            cap_eff = caps[s] if clamp else None
            y_s, c_s = _local_mlp(sign_l, params_l, x_t, cfg, a_t, strategy,
                                  cap_l, cap_eff, return_stats, interpret)
            parts.append(_pack_partial(y_s, c_s) if return_stats else y_s)
            if return_stats:
                counts.append(c_s)
        gathered = jnp.stack(parts, axis=0)                   # (ms,bt,d[+2])
        if not return_stats:
            y_blocks.append(_combine_gathered(gathered, False, k // ms))
            continue
        y_t, real_t, union_t = _combine_gathered(gathered, True, k // ms)
        y_blocks.append(y_t)
        tot_blocks.append(_count_matrix(counts))
        real_blocks.append(real_t)
        union_blocks.append(union_t)
    y = y_blocks[0] if ds == 1 else jnp.concatenate(y_blocks, axis=0)
    if not return_stats:
        return y
    totals_mat = (tot_blocks[0] if ds == 1
                  else jnp.concatenate(tot_blocks, axis=0))   # (B, n)
    totals = {col: totals_mat[..., i] for i, col in enumerate(COUNT_COLS)}
    shard_real = (real_blocks[0] if ds == 1
                  else jnp.concatenate(real_blocks, axis=0))
    shard_union = (union_blocks[0] if ds == 1
                   else jnp.concatenate(union_blocks, axis=0))
    return y, _finalize_stats(totals, shard_real, shard_union, k,
                              cfg.tp_shards)


def shard_map_apply(params: dict, x: jax.Array, cfg: SM.SparseInferConfig,
                    alpha, *, mesh, strategy: str,
                    return_stats: bool = False,
                    interpret: Optional[bool] = None):
    """The same math under shard_map over the mesh's ('data', 'model')
    axes.  A mesh axis may be SMALLER than the semantic shard count as long
    as it divides it — each device then loops over its contiguous semantic
    tiles, which is what keeps results placement-invariant.  Two-axis
    telemetry epilogue: one psum of the count matrix over 'model', the
    'data' out_spec reassembling the (B, ·) rows."""
    ds, ms = semantic_grid(cfg)
    k = _hidden_rows(params)
    caps, cap_l = shard_caps(cfg, k)
    clamp = bool(cfg.shard_bucket_caps)
    axes = R.mesh_axes(mesh)
    m_mesh = R.axis_size(mesh, "model") if "model" in axes else 1
    d_mesh = R.axis_size(mesh, "data") if "data" in axes else 1
    per_m, per_d = ms // m_mesh, ds // d_mesh
    mname = "model" if "model" in axes else None
    dname = "data" if "data" in axes else None
    sign_wg = params.get("sign_wg")
    if sign_wg is None:
        sign_wg = P.pack_signs(params["wg_t"])
    b = x.shape[0]
    if b % ds:
        raise ValueError(
            f"batch {b} not divisible by dp_shards={ds} (DESIGN.md §8)")
    bt = b // ds
    k_l = k // ms
    gated = ((params.get("wu_t") is not None)
             or (params.get("wu_q") is not None))
    a = jnp.broadcast_to(jnp.asarray(alpha, jnp.float32), (b,))
    # weight operand list: every leaf row-sharded over 'model' (each row
    # count proportional to k — see _SLICE_KEYS); ungated configs pass
    # 0-row stubs so the operand tuple keeps one static arity per layout
    if "wg_q" in params:                        # int8 leaves (DESIGN.md §13)
        wnames = ("wg_q", "wg_s", "wu_q", "wu_s", "wd_q", "wd_s")
        w_ops = tuple(
            params[n] if (gated or not n.startswith("wu_"))
            else (params["wg_q"][:0] if n == "wu_q" else params["wg_s"][:0])
            for n in wnames)
    else:
        wnames = ("wg_t", "wu_t", "wd_t")
        w_ops = (params["wg_t"],
                 params["wu_t"] if gated else params["wg_t"][:0],
                 params["wd_t"])
    caps_vec = jnp.asarray(caps, jnp.int32)

    row = P_(mname, None)                      # weight row sharding
    in_specs = ((row,) * (1 + len(w_ops))
                + (P_(dname, None), P_(dname)))
    if return_stats:
        out_specs = (P_(dname, None), P_(dname, None), P_(dname, None),
                     P_(dname, None))
    else:
        out_specs = P_(dname, None)

    def body(sign_l, *rest):
        # x_l: (b/d_mesh, d) = per_d semantic data blocks of bt rows;
        # weights: per-device per_m semantic shard slices (row counts
        # proportional to the leaf's global k-proportional height)
        w_ls = rest[:len(wnames)]
        x_l, a_l = rest[len(wnames):]
        m_base = (jax.lax.axis_index(mname) * per_m if mname is not None
                  else jnp.int32(0))
        y_rows, tot_rows, real_rows, union_rows = [], [], [], []
        for db in range(per_d):
            x_t = x_l[db * bt:(db + 1) * bt]
            a_t = a_l[db * bt:(db + 1) * bt]
            parts = []
            counts = []
            for mt in range(per_m):
                params_t = {}
                for n, w in zip(wnames, w_ls):
                    if w.shape[0] == 0:
                        continue
                    r = w.shape[0] // per_m
                    params_t[n] = w[mt * r:(mt + 1) * r]
                cap_eff = caps_vec[m_base + mt] if clamp else None
                y_s, c_s = _local_mlp(sign_l[mt * k_l:(mt + 1) * k_l],
                                      params_t, x_t, cfg, a_t,
                                      strategy, cap_l, cap_eff,
                                      return_stats, interpret)
                parts.append(_pack_partial(y_s, c_s)
                             if return_stats else y_s)
                if return_stats:
                    counts.append(c_s)
            local = jnp.stack(parts, axis=0)          # (per_m, bt, d[+2])
            if mname is not None:
                gathered = jax.lax.all_gather(local, mname, axis=0)
                gathered = gathered.reshape((ms,) + local.shape[1:])
            else:
                gathered = local
            if not return_stats:
                y_rows.append(_combine_gathered(gathered, False, k_l))
                continue
            y_t, real_t, union_t = _combine_gathered(gathered, True, k_l)
            cm = _count_matrix(counts)                        # (bt, n)
            if mname is not None:
                cm = jax.lax.psum(cm, mname)   # exact: integer counts
            y_rows.append(y_t)
            tot_rows.append(cm)
            real_rows.append(real_t)
            union_rows.append(union_t)

        def cat(rows):
            return rows[0] if per_d == 1 else jnp.concatenate(rows, axis=0)

        if not return_stats:
            return cat(y_rows)
        return cat(y_rows), cat(tot_rows), cat(real_rows), cat(union_rows)

    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    with R.shard_local():   # the body works on per-shard values: no nested
        out = fn(sign_wg, *w_ops, x, a)
    if not return_stats:
        return out
    y, totals_mat, shard_real, shard_union = out
    totals = {col: totals_mat[..., i] for i, col in enumerate(COUNT_COLS)}
    return y, _finalize_stats(totals, shard_real, shard_union, k,
                              cfg.tp_shards)


def selection_masks(params: dict, x: jax.Array, cfg: SM.SparseInferConfig,
                    alpha, *, strategy: str = "gather") -> jax.Array:
    """(ds, k/G) bool — which row-groups each data block's shard-local
    union selection keeps (concatenated over the ms model shards).  The
    margins/selection pipeline is the exact one ``_local_mlp`` runs for the
    gather strategy (the pallas predictor is bitwise-identical to it), so
    the property suite and the bench occupancy rows can observe selection
    SETS without duplicating the implementation."""
    if strategy not in ("gather", "pallas"):
        raise ValueError(
            f"selection_masks is defined for the capacity-selected union "
            f"strategies, got {strategy!r}")
    ds, ms = semantic_grid(cfg)
    k = _hidden_rows(params)
    g = cfg.group_size
    caps, cap_l = shard_caps(cfg, k)
    clamp = bool(cfg.shard_bucket_caps)
    sign_wg = params.get("sign_wg")
    if sign_wg is None:
        sign_wg = P.pack_signs(params["wg_t"])
    b = x.shape[0]
    if b % ds:
        raise ValueError(
            f"batch {b} not divisible by dp_shards={ds} (DESIGN.md §8)")
    bt = b // ds
    a = jnp.broadcast_to(jnp.asarray(alpha, jnp.float32), (b,))
    rows = []
    for db in range(ds):
        x_t = x[db * bt:(db + 1) * bt]
        a_t = a[db * bt:(db + 1) * bt]
        per_shard = []
        for s in range(ms):
            sign_l, _ = _slice_params(params, sign_wg, s, ms)
            m_tok = P.margins(sign_l, P.pack_signs(x_t), x.shape[-1], a_t)
            gm = S.union_margin(S.group_margins(m_tok, g))
            sel, sstats = S.capacity_select_with_stats(gm, cap_l)
            if clamp:
                sel, sstats = S.clamp_selection(sel, sstats, caps[s])
            mask = jnp.zeros(((k // g) // ms,), jnp.bool_)
            per_shard.append(mask.at[sel.indices].max(sel.valid))
        rows.append(jnp.concatenate(per_shard))
    return jnp.stack(rows, axis=0)


def shard_gauge_rows(density_ema, union_ema=None):
    """Per-(layer, shard) gauge rows for metrics export (DESIGN.md §12):
    yields ``(layer, shard, density, union)`` tuples from the (L, ms)
    shard EMAs ``runtime.controller.DistributedController`` keeps; the
    union column is None when no union-demand EMA was tracked.  Host-side
    iteration over already-materialized numpy state — no device reads."""
    import numpy as np
    d = np.asarray(density_ema, np.float32)
    u = None if union_ema is None else np.asarray(union_ema, np.float32)
    for layer in range(d.shape[0]):
        for shard in range(d.shape[1]):
            yield (layer, shard, float(d[layer, shard]),
                   None if u is None else float(u[layer, shard]))


def sharded_apply(params: dict, x: jax.Array, cfg: SM.SparseInferConfig,
                  alpha, *, strategy: str, return_stats: bool = False,
                  interpret: Optional[bool] = None):
    """Dispatch for sharded configs (called from ``core.sparse_mlp.apply``):
    shard_map when the ambient mesh's axes evenly divide the (ds, ms)
    semantic grid, bitwise-identical single-device emulation otherwise."""
    squeeze = x.ndim == 1
    xb = x[None] if squeeze else x
    if xb.ndim != 2:
        raise ValueError(
            f"tp_shards decode expects (B, d) tokens, got {x.shape} — the "
            "dp-grouped (G, B, d) gather layout composes with GSPMD data "
            "sharding, not with the shard_map TP path (DESIGN.md §8)")
    ds, ms = semantic_grid(cfg)
    mesh = R.current_mesh()
    use_mesh = False
    if mesh is not None:
        axes = R.mesh_axes(mesh)
        m_mesh = R.axis_size(mesh, "model") if "model" in axes else 1
        d_mesh = R.axis_size(mesh, "data") if "data" in axes else 1
        if m_mesh > 1 or d_mesh > 1:
            if ms % m_mesh:
                raise ValueError(
                    f"tp_shards={cfg.tp_shards} but the active mesh's "
                    f"'model' axis has {m_mesh} devices — the mesh axis "
                    "must evenly divide the semantic shard count "
                    "(DESIGN.md §8)")
            if ds % d_mesh:
                raise ValueError(
                    f"dp_shards={cfg.dp_shards} but the active mesh's "
                    f"'data' axis has {d_mesh} devices — the mesh axis "
                    "must evenly divide the semantic shard count "
                    "(DESIGN.md §8)")
            use_mesh = True
    if use_mesh:
        out = shard_map_apply(params, xb, cfg, alpha, mesh=mesh,
                              strategy=strategy, return_stats=return_stats,
                              interpret=interpret)
    else:
        out = emulated_apply(params, xb, cfg, alpha, strategy=strategy,
                             return_stats=return_stats, interpret=interpret)
    if not squeeze:
        return out
    if return_stats:
        y, stats = out
        return y[0], {kk: v[0] for kk, v in stats.items()}
    return out[0]
