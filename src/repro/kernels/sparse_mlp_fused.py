"""Pallas TPU kernel: capacity-gathered fused sparse gated MLP.

This is the TPU-native form of the paper's sparse GEMV + kernel fusion
(§IV-B3/B4), extended to fuse the down-projection too (DESIGN.md §2):

  grid step i handles surviving neuron-group ``sel[i]`` (G consecutive rows).
  Scalar-prefetched indices drive the BlockSpec ``index_map`` so the DMA
  engine fetches *only surviving row-groups* of all three weight matrices —
  the byte savings happen at the HBM→VMEM boundary, the TPU equivalent of the
  CUDA warp's early return.

  per step:   g = act(x @ Wg[sel]ᵀ);  u = x @ Wu[sel]ᵀ;  h = g ⊙ u
              y += h @ Wd[sel]           (VMEM accumulator, no atomics)

The paper's "+actual sparsity" falls out of ``h`` being exactly zero for
false-positive rows: their down-proj contribution vanishes. Steps past
``count`` (capacity padding) are masked with ``pl.when``; their DMAs fetch
group 0 harmlessly (capacity slack is a DSE knob, DESIGN.md §2).

In-kernel telemetry (``collect_stats=True``, DESIGN.md §4): alongside the
accumulator the kernel folds three per-token int32 counters over the grid —
``TELEMETRY_COLS = (actual, false_neg, realized)`` — reading the token's own
group margin for the step's group out of a resident (B, C) block of the
margins in selection order.  ``actual`` counts computed rows whose gate
fired (paper's realized gate activity), ``false_neg`` is the in-union
false-negative proxy (gate fired but THIS token's margin said skip — rows it
only got because a co-resident token kept them), ``realized`` counts the
token's own predicted rows that survived the capacity clamp.  This populates
``MLP_STAT_KEYS`` natively on the pallas path — per-slot, with no masked-path
audit fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.relufication import get_activation

# columns of the telemetry output, in order (per-token int32 row counts)
TELEMETRY_COLS = ("actual", "false_neg", "realized")


def _telemetry_delta(ga, keep):
    """Per-step telemetry delta (B, 3): gate activity ``ga`` (B, G) and the
    token's own keep decision for this group ``keep`` (B, 1) bool."""
    live = ga > 0
    gsz = live.shape[-1]
    return jnp.concatenate([
        jnp.sum(live, axis=-1, dtype=jnp.int32, keepdims=True),
        jnp.sum(live & jnp.logical_not(keep), axis=-1, dtype=jnp.int32,
                keepdims=True),
        keep.astype(jnp.int32) * gsz,
    ], axis=-1)


def _qdot(x, wq, s, qgs: int):
    """Quantized row-group dot with epilogue dequant (DESIGN.md §13):
    ``sum_q (x[:, q] @ wq[:, q]ᵀ) * s[:, q]`` accumulated in ascending
    quant-group order.  ``x`` (B, d) f32, ``wq`` (G, d) int8, ``s`` (G,
    d/qgs) f32 → (B, G) f32.  The jnp oracle calls this SAME helper, so
    pallas-vs-ref parity is bitwise by construction."""
    nq = s.shape[-1]
    acc = None
    for q in range(nq):
        sl = slice(q * qgs, (q + 1) * qgs)
        part = jax.lax.dot_general(
            x[:, sl], wq[:, sl].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (B, G)
        term = part * s[:, q][None, :]
        acc = term if acc is None else acc + term
    return acc


def _make_kernel(activation: str, fatrelu_threshold: float, gated: bool,
                 collect_stats: bool, groups_per_step: int, qgs: int,
                 per: int):
    """Kernel body for the 2-D grid (row_blocks, cap/gps): the selection
    walks grid axis 1 (the fast axis), so each row block's accumulator sees
    i==0 (init) at its first visit and folds the selected groups in
    selection order — per-row results do not depend on the row tiling.
    ``per`` is the number of tile operands of one selected group.

    ``qgs`` > 0 selects the int8 path (DESIGN.md §13): each weight tile
    arrives with its f32 scale tile, gate/up dequantize via :func:`_qdot`,
    and the down-proj dequant is a pure ``(h @ Wq) * s_row`` epilogue.  The
    (k/qg, d) down-proj scale matrix is one resident block; the step reads
    the row covering its selection group (``qg % G == 0`` pins the group
    inside one quant row-group).

    With ``collect_stats`` the token's own margin for the step's group is
    picked out of a resident (rows, cap) block of selection-ordered margins
    (a lane select over the capacity — exact, and legal on the TPU where a
    one-lane (rows, 1) block of the (rows, k/G) margins is not)."""
    act = get_activation(
        "fatrelu" if (activation == "fatrelu" or fatrelu_threshold > 0.0)
        else activation, fatrelu_threshold)
    quant = qgs > 0

    def kernel(sel_ref, cnt_ref, *refs):
        x_ref = refs[0]
        tiles = refs[1:1 + groups_per_step * per]
        rest = list(refs[1 + groups_per_step * per:])
        wds_ref = rest.pop(0) if quant else None
        gm_ref = rest.pop(0) if collect_stats else None
        y_ref = rest.pop(0)
        tel_ref = rest.pop(0) if collect_stats else None
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            y_ref[...] = jnp.zeros_like(y_ref)
            if collect_stats:
                tel_ref[...] = jnp.zeros_like(tel_ref)

        # sequential sub-steps over the tile's groups_per_step selected
        # groups: the accumulation order is identical to the one-group-per-
        # step grid, so per-bucket tiling never changes results (bitwise)
        for j in range(groups_per_step):
            step = tiles[j * per:(j + 1) * per]
            n = i * groups_per_step + j

            @pl.when(n < cnt_ref[0])
            def _step(step=step, n=n):
                if quant:
                    wgq, wgs = step[0], step[1]
                    x = x_ref[...].astype(jnp.float32)       # (B, d)
                    ga = act(_qdot(x, wgq[...], wgs[...], qgs))
                    h = (ga * _qdot(x, step[2][...], step[3][...], qgs)
                         if gated else ga)
                    yd = jax.lax.dot_general(
                        h, step[-1][...].astype(jnp.float32),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)  # (B, d)
                    row = sel_ref[n] // (qgs // step[0].shape[0])
                    y_ref[...] += yd * wds_ref[pl.ds(row, 1), :]
                else:
                    x = x_ref[...]                           # (B, d)
                    g = jax.lax.dot_general(
                        x, step[0][...], (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)  # (B, G)
                    ga = act(g)
                    if gated:
                        u = jax.lax.dot_general(
                            x, step[1][...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        h = ga * u                           # (B, G)
                    else:
                        h = ga
                    y_ref[...] += jax.lax.dot_general(
                        h.astype(x.dtype), step[-1][...],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)  # (B, d)
                if collect_stats:
                    gm = gm_ref[...]                         # (B, cap)
                    lane = jax.lax.broadcasted_iota(jnp.int32, gm.shape, 1)
                    own = jnp.min(jnp.where(lane == n, gm, jnp.inf),
                                  axis=-1, keepdims=True)    # (B, 1)
                    tel_ref[...] += _telemetry_delta(ga, own <= 0)
    return kernel


def mlp_groups_per_step(cap_groups: int, group_size: int) -> int:
    """Per-bucket weight-tile height for the fused MLP (DESIGN.md §2/§8):
    how many SELECTED groups one grid step fetches and computes.  Wide
    buckets amortize grid/DMA overhead over a (gps·G, d) effective tile;
    narrow buckets keep the single-group tile (a big tile over a short
    selection would mask most sub-steps).  Must divide the bucket's
    capacity so the grid is exact."""
    for gps in (4, 2, 1):
        if (cap_groups % gps == 0 and cap_groups >= 4 * gps
                and gps * group_size <= 64):
            return gps
    return 1


def choose_block_rows(b: int, d: int, max_vmem: int = 4 * 1024 * 1024) -> int:
    """Row-block height for a prefill chunk: largest divisor of ``b`` whose
    (bt, d) f32 accumulator stays under ~``max_vmem``.  Raises
    ``ValueError`` when that divisor is neither the whole chunk nor a
    multiple of 8 sublanes (the TPU block-shape rule)."""
    if b <= 0 or d <= 0:
        raise ValueError(f"chunk MLP tiling needs b,d > 0, got b={b} d={d}")
    budget = max(1, max_vmem // (4 * d))
    bt = min(b, budget, 128)
    while bt > 1 and b % bt:
        bt -= 1
    if bt != b and bt % 8:
        raise ValueError(
            f"chunk MLP rows b={b} at d={d}: largest row block under the "
            f"VMEM budget is {bt}, not a multiple of 8")
    return bt


def _fused_call(x, weights, wd_s, sel_indices, sel_count, gm_tok, *,
                group_size, activation, fatrelu_threshold, collect_stats,
                interpret, groups_per_step, block_rows):
    """Build and run the fused pallas_call.  ``weights`` is the per-step
    tile operand list of one selected group — ``(wg, wu, wd)`` (fp) or
    ``(wg_q, wg_s, wu_q, wu_s, wd_q)`` (int8, with ``wd_s`` given); ungated
    MLPs drop the up-projection entries."""
    b, d = x.shape
    k = weights[0].shape[0]
    g = group_size
    assert k % g == 0
    cap = sel_indices.shape[0]
    quant = wd_s is not None
    qgs = d // weights[1].shape[1] if quant else 0
    if collect_stats:
        assert gm_tok is not None and gm_tok.shape == (b, k // g), (
            "collect_stats needs per-token group margins (B, k/G)")
    gps = groups_per_step or mlp_groups_per_step(cap, g)
    if cap % gps:
        raise ValueError(
            f"groups_per_step={gps} must divide the selection capacity "
            f"{cap} (per-bucket tiling, DESIGN.md §2)")
    bt = block_rows or b
    if b % bt:
        raise ValueError(f"block_rows={bt} must divide the rows {b}")

    cnt = jnp.reshape(sel_count.astype(jnp.int32), (1,))
    in_specs = [pl.BlockSpec((bt, d), lambda r, i, sel, cnt: (r, 0))]
    operands = [x]
    for j in range(gps):
        for w in weights:
            in_specs.append(pl.BlockSpec(
                (g, w.shape[1]),
                lambda r, i, sel, cnt, j=j: (sel[i * gps + j], 0)))
            operands.append(w)
    if quant:
        in_specs.append(pl.BlockSpec(wd_s.shape,
                                     lambda r, i, sel, cnt: (0, 0)))
        operands.append(wd_s)
    if collect_stats:
        in_specs.append(pl.BlockSpec((bt, cap),
                                     lambda r, i, sel, cnt: (r, 0)))
        operands.append(jnp.take(gm_tok.astype(jnp.float32), sel_indices,
                                 axis=1))
    out_specs = pl.BlockSpec((bt, d), lambda r, i, sel, cnt: (r, 0))
    out_shape = jax.ShapeDtypeStruct((b, d), jnp.float32)
    if collect_stats:
        ncol = len(TELEMETRY_COLS)
        out_specs = [out_specs,
                     pl.BlockSpec((bt, ncol), lambda r, i, sel, cnt: (r, 0))]
        out_shape = (out_shape, jax.ShapeDtypeStruct((b, ncol), jnp.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b // bt, cap // gps),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    gated = len(weights) == (5 if quant else 3)
    kernel = _make_kernel(activation, fatrelu_threshold, gated,
                          collect_stats, gps, qgs, len(weights))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="fused_sparse_mlp",
    )(sel_indices.astype(jnp.int32), cnt, *operands)


_STATIC = ("group_size", "activation", "fatrelu_threshold", "collect_stats",
           "interpret", "groups_per_step", "block_rows")


@functools.partial(jax.jit, static_argnames=_STATIC)
def fused_sparse_mlp(x: jax.Array,
                     wg_t: jax.Array,
                     wu_t: jax.Array | None,
                     wd_t: jax.Array,
                     sel_indices: jax.Array,
                     sel_count: jax.Array,
                     gm_tok: jax.Array | None = None,
                     *,
                     group_size: int = 8,
                     activation: str = "relu",
                     fatrelu_threshold: float = 0.0,
                     collect_stats: bool = False,
                     interpret: bool = True,
                     groups_per_step: int = 0,
                     block_rows: int = 0):
    """x: (B, d); w*_t: (k, d) neuron-major; sel_indices: (C,) group ids.

    Returns y: (B, d) float32 (one fused HBM pass over selected groups).
    With ``collect_stats`` also requires ``gm_tok`` (B, k/G) per-token group
    margins and returns ``(y, telemetry)`` with telemetry (B, 3) int32
    (``TELEMETRY_COLS`` row counts accumulated in-kernel).

    ``groups_per_step`` (0 = auto via :func:`mlp_groups_per_step`) is the
    per-bucket weight-tile height: each grid step scalar-prefetches that
    many selected groups of every matrix, so wide capacity buckets get a
    taller effective tile.  ``block_rows`` (0 = all rows, the decode case)
    tiles the rows of a prefill chunk (DESIGN.md §9): one chunk-union
    selection drives the weight DMAs of every row block.  Results are
    bitwise-independent of both (the sub-steps accumulate in selection
    order).
    """
    weights = (wg_t, wd_t) if wu_t is None else (wg_t, wu_t, wd_t)
    return _fused_call(
        x, weights, None, sel_indices, sel_count, gm_tok,
        group_size=group_size, activation=activation,
        fatrelu_threshold=fatrelu_threshold, collect_stats=collect_stats,
        interpret=interpret, groups_per_step=groups_per_step,
        block_rows=block_rows)


@functools.partial(jax.jit, static_argnames=_STATIC)
def fused_sparse_mlp_q(x: jax.Array,
                       wg_q: jax.Array,
                       wg_s: jax.Array,
                       wu_q: jax.Array | None,
                       wu_s: jax.Array | None,
                       wd_q: jax.Array,
                       wd_s: jax.Array,
                       sel_indices: jax.Array,
                       sel_count: jax.Array,
                       gm_tok: jax.Array | None = None,
                       *,
                       group_size: int = 8,
                       activation: str = "relu",
                       fatrelu_threshold: float = 0.0,
                       collect_stats: bool = False,
                       interpret: bool = True,
                       groups_per_step: int = 0,
                       block_rows: int = 0):
    """int8-weight twin of :func:`fused_sparse_mlp` (DESIGN.md §13).

    ``w*_q``: int8 (k, d) neuron-major; ``wg_s``/``wu_s``: f32 (k, d/qg)
    row-grouped scales; ``wd_s``: f32 (k/qg, d) column-grouped scales.
    Each grid step DMAs the selected int8 row-groups PLUS their gate/up
    scale tiles; the down-proj scales stay resident.  Dequant happens in
    the accumulator epilogue; HBM weight traffic is ~1 byte/elt + the thin
    scale stream (see :func:`kernel_hbm_bytes`).
    """
    d = x.shape[1]
    k = wg_q.shape[0]
    g = group_size
    nq = wg_s.shape[1]
    assert d % nq == 0
    qg = d // nq
    assert k % g == 0 and qg % g == 0 and k % qg == 0, (
        f"bad quant tiling: k={k} d={d} g={g} qg={qg} (DESIGN.md §13)")
    weights = ((wg_q, wg_s, wd_q) if wu_q is None
               else (wg_q, wg_s, wu_q, wu_s, wd_q))
    return _fused_call(
        x, weights, wd_s, sel_indices, sel_count, gm_tok,
        group_size=group_size, activation=activation,
        fatrelu_threshold=fatrelu_threshold, collect_stats=collect_stats,
        interpret=interpret, groups_per_step=groups_per_step,
        block_rows=block_rows)


def kernel_hbm_bytes(b: int, d: int, k: int, cap_groups: int, group_size: int,
                     gated: bool = True, weight_bytes: int = 2,
                     collect_stats: bool = True, *,
                     weight_dtype: str = "", quant_group_size: int = 128,
                     act_bytes: int | None = None) -> dict:
    """Analytic HBM traffic model for the two-dispatch pipeline vs dense.

    Models the single-dispatch predictor (packed weight signs + raw input
    read; per-token group margins written once, re-read by the selection
    epilogue and the MLP kernel's telemetry prefetch) and the fused MLP at
    the given capacity bucket, including the telemetry outputs.  The
    previous model undercounted predictor traffic (it ignored the raw-input
    read and the margin round-trip) and overstated the reduction.

    Weight traffic is itemized per weight dtype (DESIGN.md §13):
    ``weight_dtype="int8"`` streams 1-byte tiles plus the f32 scale vectors
    (row-grouped ``(rows, d/qg)`` for gate/up per selected group, the
    resident ``(k/qg, d)`` down-proj matrix once per call); activation traffic uses ``act_bytes``
    (defaults to ``weight_bytes`` for back-compat with the fp model, where
    weights and activations share a dtype).
    """
    n_mats = 3 if gated else 2
    w_words = -(-d // 32)
    n_groups = max(1, k // group_size)
    cap_groups = min(cap_groups, n_groups)
    sel_rows = cap_groups * group_size
    ab = weight_bytes if act_bytes is None else act_bytes

    if weight_dtype == "int8":
        qg = quant_group_size
        n_row_mats = n_mats - 1          # row-grouped (wg + optional wu)
        dense_w = n_mats * k * d
        dense_s = n_row_mats * k * (d // qg) * 4 + (k // qg) * d * 4
        fused_w = n_mats * sel_rows * d
        # per selected group: (G, d/qg) gate/up scale tiles; the whole
        # (k/qg, d) down-proj scale matrix is read once (resident block)
        fused_s = (n_row_mats * sel_rows * (d // qg) * 4
                   + (k // qg) * d * 4)
    else:
        dense_w = n_mats * k * d * weight_bytes
        dense_s = 0
        fused_w = n_mats * sel_rows * d * weight_bytes
        fused_s = 0

    dense = dense_w + dense_s + b * d * ab * 2

    # dispatch 1 — fused predictor: packed W signs + raw x in; per-token
    # group margins + per-slot counts out (packed x never touches HBM)
    margins_bytes = b * n_groups * 4
    predictor = (k * w_words * 4            # packed sign matrix read
                 + b * d * ab               # raw input read (packed in VMEM)
                 + margins_bytes            # (B, k/G) margins written
                 + b * 4)                   # per-slot predicted counts
    # XLA selection epilogue re-reads the margins (union + top-C)
    selection = margins_bytes + cap_groups * 8

    # dispatch 2 — fused MLP: selected row-groups (+ scales) + x in, y out;
    # telemetry adds the per-step own-margin prefetch and the (B, 3)
    # counters
    fused = (fused_w + fused_s
             + b * d * ab                   # x read again by the MLP kernel
             + b * d * 4)                   # f32 accumulator written
    telemetry = (b * cap_groups * 4 + b * len(TELEMETRY_COLS) * 4
                 if collect_stats else 0)

    total = fused + predictor + selection + telemetry
    return {
        "dense_bytes": dense,
        "fused_bytes": fused,
        "fused_weight_bytes": fused_w,
        "fused_scale_bytes": fused_s,
        "predictor_bytes": predictor,
        "selection_bytes": selection,
        "telemetry_bytes": telemetry,
        "total_sparse_bytes": total,
        "reduction": dense / total,
        "dispatches": 2,
        "cap_groups": cap_groups,
        "weight_dtype": weight_dtype or f"fp{8 * weight_bytes}",
    }
