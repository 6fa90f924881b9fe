"""Jitted public wrappers for the Pallas kernels with backend dispatch.

``interpret=None`` (default) resolves to ``True`` unless running on a TPU
backend: on the CPU (tests, ``JAX_PLATFORMS=cpu``) the kernels run in the
Pallas interpreter; on a TPU they compile to Mosaic kernels.  A shape a
kernel cannot tile (``choose_block_k`` / ``choose_block_rows`` /
``check_quant_dims`` / ``check_tiling`` raise ``ValueError``) runs the jnp
oracle from ``ref.py`` in interpret mode only; compiled, the wrapper raises
with the shape, so a TPU run never silently executes something else.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import predictor as P
from repro.kernels import ref
from repro.kernels import predict as _predict
from repro.kernels import paged_attn as _paged
from repro.kernels import sparse_mlp_fused as _fused


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _refuse_compiled(interp: bool, err: ValueError, kernel: str,
                     **shape) -> None:
    """Raise when a compiled kernel cannot tile ``shape``; return (so the
    caller runs the jnp oracle) in interpret mode."""
    if not interp:
        dims = ", ".join(f"{k}={v}" for k, v in shape.items())
        raise ValueError(f"{kernel}: no TPU tiling for {dims}: {err}") \
            from err


def predict_counts(packed_w: jax.Array, packed_x: jax.Array, *,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Negative-product counts: ((k,w), (...,w)) -> (..., k) int32."""
    interp = _resolve_interpret(interpret)
    lead = packed_x.shape[:-1]
    flat = packed_x.reshape(-1, packed_x.shape[-1])
    k, w = packed_w.shape
    try:
        bk = _predict.choose_block_k(k, w, flat.shape[0])
    except ValueError as e:
        _refuse_compiled(interp, e, "predict_counts", k=k, w=w,
                         b=flat.shape[0])
        out = ref.predict_counts_ref(packed_w, flat)
    else:
        out = _predict.predict_counts(packed_w, flat, interpret=interp,
                                      block_k=bk)
    return out.reshape(lead + (k,))


def predict_margins(packed_w: jax.Array, packed_x: jax.Array, d_valid: int,
                    alpha: float | jax.Array = 1.0, *,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Kernel-backed version of core.predictor.margins (paper eq. 2)."""
    n_neg = predict_counts(packed_w, packed_x, interpret=interpret)
    n_neg = n_neg.astype(jnp.float32)
    n_pos = jnp.float32(d_valid) - n_neg
    return n_neg - jnp.asarray(alpha, jnp.float32) * n_pos


def predict_group_margins(packed_w: jax.Array, x: jax.Array, d_valid: int,
                          alpha: float | jax.Array = 1.0, *,
                          group_size: int = 8,
                          interpret: Optional[bool] = None):
    """Fused predictor (DESIGN.md §2): raw input (B, d) -> per-token
    per-group margins (B, k/G) + per-token predicted counts (B,), for a
    decode batch or a prefill chunk alike.

    The input is sign-packed in XLA (``core.predictor.pack_signs``, B·d/32
    words); XOR/popcount, the alpha margin and the group-min run in one
    Pallas kernel, so the (B, k) count matrix never round-trips HBM.
    Bitwise-identical to the ``core.predictor`` epilogue composition it
    replaces.
    """
    interp = _resolve_interpret(interpret)
    k, w = packed_w.shape
    b = x.shape[0]
    a = jnp.broadcast_to(jnp.asarray(alpha, jnp.float32), (b,))
    try:
        bk = _predict.choose_block_k(k, w, b, group_size)
    except ValueError as e:
        _refuse_compiled(interp, e, "predict_group_margins", k=k, w=w, b=b,
                         group_size=group_size)
        return ref.predict_group_margins_ref(packed_w, x, d_valid, a,
                                             group_size)
    return _predict.predict_group_margins(
        packed_w, P.pack_signs(x), a, d_valid=d_valid,
        group_size=group_size, interpret=interp, block_k=bk)


def fused_sparse_mlp(x: jax.Array,
                     wg_t: jax.Array,
                     wu_t: Optional[jax.Array],
                     wd_t: jax.Array,
                     sel_indices: jax.Array,
                     sel_count: jax.Array,
                     gm_tok: Optional[jax.Array] = None,
                     *,
                     group_size: int = 8,
                     activation: str = "relu",
                     fatrelu_threshold: float = 0.0,
                     collect_stats: bool = False,
                     interpret: Optional[bool] = None,
                     groups_per_step: int = 0):
    """Capacity-gathered fused sparse gated MLP: (B, d) -> (B, d) f32.

    With ``collect_stats`` (needs ``gm_tok`` per-token group margins) the
    kernel also accumulates per-token telemetry in-kernel and returns
    ``(y, telemetry)`` — see kernels.sparse_mlp_fused.TELEMETRY_COLS.
    ``groups_per_step`` 0 = auto per-bucket tile height
    (``mlp_groups_per_step``); results are bitwise-independent of it.
    """
    interp = _resolve_interpret(interpret)
    return _fused.fused_sparse_mlp(
        x, wg_t, wu_t, wd_t, sel_indices, sel_count, gm_tok,
        group_size=group_size, activation=activation,
        fatrelu_threshold=fatrelu_threshold, collect_stats=collect_stats,
        interpret=interp, groups_per_step=groups_per_step)


def fused_sparse_mlp_chunk(x: jax.Array,
                           wg_t: jax.Array,
                           wu_t: Optional[jax.Array],
                           wd_t: jax.Array,
                           sel_indices: jax.Array,
                           sel_count: jax.Array,
                           gm_tok: Optional[jax.Array] = None,
                           *,
                           group_size: int = 8,
                           activation: str = "relu",
                           fatrelu_threshold: float = 0.0,
                           collect_stats: bool = False,
                           interpret: Optional[bool] = None,
                           groups_per_step: int = 0):
    """Row-tiled fused sparse MLP for prefill chunks (DESIGN.md §9): one
    chunk-union selection drives every row block; per-row outputs and
    telemetry are bitwise-equal to :func:`fused_sparse_mlp` on the same
    selection.
    """
    interp = _resolve_interpret(interpret)
    try:
        bt = _fused.choose_block_rows(x.shape[0], x.shape[1])
    except ValueError as e:
        _refuse_compiled(interp, e, "fused_sparse_mlp_chunk", b=x.shape[0],
                         d=x.shape[1])
        return ref.fused_sparse_mlp_chunk_ref(
            x, wg_t, wu_t, wd_t, sel_indices, sel_count, gm_tok,
            group_size=group_size, activation=activation,
            fatrelu_threshold=fatrelu_threshold, collect_stats=collect_stats)
    return _fused.fused_sparse_mlp(
        x, wg_t, wu_t, wd_t, sel_indices, sel_count, gm_tok,
        group_size=group_size, activation=activation,
        fatrelu_threshold=fatrelu_threshold, collect_stats=collect_stats,
        interpret=interp, groups_per_step=groups_per_step, block_rows=bt)


def fused_sparse_mlp_q(x: jax.Array,
                       wg_q: jax.Array,
                       wg_s: jax.Array,
                       wu_q: Optional[jax.Array],
                       wu_s: Optional[jax.Array],
                       wd_q: jax.Array,
                       wd_s: jax.Array,
                       sel_indices: jax.Array,
                       sel_count: jax.Array,
                       gm_tok: Optional[jax.Array] = None,
                       *,
                       group_size: int = 8,
                       activation: str = "relu",
                       fatrelu_threshold: float = 0.0,
                       collect_stats: bool = False,
                       interpret: Optional[bool] = None,
                       groups_per_step: int = 0):
    """int8-weight fused sparse MLP (DESIGN.md §13): same contract as
    :func:`fused_sparse_mlp` with int8 tiles + per-group f32 scales
    (``wg_s``/``wu_s`` (k, d/qg) row-grouped, ``wd_s`` (k/qg, d) column-
    grouped).  Tilings the quant layout can't honor (qg not dividing d/k,
    or not a multiple of the selection group) raise when compiled and run
    the bitwise jnp oracle in interpret mode.
    """
    from repro.core.quantize import check_quant_dims
    interp = _resolve_interpret(interpret)
    d = x.shape[1]
    k = wg_q.shape[0]
    qg = d // wg_s.shape[1]
    try:
        check_quant_dims(d, k, group_size, qg)
    except ValueError as e:
        _refuse_compiled(interp, e, "fused_sparse_mlp_q", d=d, k=k,
                         group_size=group_size, qg=qg)
        return ref.fused_sparse_mlp_q_ref(
            x, wg_q, wg_s, wu_q, wu_s, wd_q, wd_s, sel_indices, sel_count,
            gm_tok, group_size=group_size, activation=activation,
            fatrelu_threshold=fatrelu_threshold, collect_stats=collect_stats)
    return _fused.fused_sparse_mlp_q(
        x, wg_q, wg_s, wu_q, wu_s, wd_q, wd_s, sel_indices, sel_count,
        gm_tok, group_size=group_size, activation=activation,
        fatrelu_threshold=fatrelu_threshold, collect_stats=collect_stats,
        interpret=interp, groups_per_step=groups_per_step)


def fused_sparse_mlp_chunk_q(x: jax.Array,
                             wg_q: jax.Array,
                             wg_s: jax.Array,
                             wu_q: Optional[jax.Array],
                             wu_s: Optional[jax.Array],
                             wd_q: jax.Array,
                             wd_s: jax.Array,
                             sel_indices: jax.Array,
                             sel_count: jax.Array,
                             gm_tok: Optional[jax.Array] = None,
                             *,
                             group_size: int = 8,
                             activation: str = "relu",
                             fatrelu_threshold: float = 0.0,
                             collect_stats: bool = False,
                             interpret: Optional[bool] = None,
                             groups_per_step: int = 0):
    """Row-tiled int8 fused sparse MLP for prefill chunks (DESIGN.md
    §9/§13), with the wrapper contract of :func:`fused_sparse_mlp_q`."""
    from repro.core.quantize import check_quant_dims
    interp = _resolve_interpret(interpret)
    d = x.shape[1]
    k = wg_q.shape[0]
    qg = d // wg_s.shape[1]
    try:
        check_quant_dims(d, k, group_size, qg)
        bt = _fused.choose_block_rows(x.shape[0], d)
    except ValueError as e:
        _refuse_compiled(interp, e, "fused_sparse_mlp_chunk_q",
                         b=x.shape[0], d=d, k=k, group_size=group_size,
                         qg=qg)
        return ref.fused_sparse_mlp_chunk_q_ref(
            x, wg_q, wg_s, wu_q, wu_s, wd_q, wd_s, sel_indices, sel_count,
            gm_tok, group_size=group_size, activation=activation,
            fatrelu_threshold=fatrelu_threshold, collect_stats=collect_stats)
    return _fused.fused_sparse_mlp_q(
        x, wg_q, wg_s, wu_q, wu_s, wd_q, wd_s, sel_indices, sel_count,
        gm_tok, group_size=group_size, activation=activation,
        fatrelu_threshold=fatrelu_threshold, collect_stats=collect_stats,
        interpret=interp, groups_per_step=groups_per_step, block_rows=bt)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    table: jax.Array, lengths: jax.Array,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None, *,
                    softcap: float = 0.0, window: int = 0,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Paged decode attention over KV-pool pages (DESIGN.md §10):
    q (B, H, hd) × pages (N, block, K, hd) + table (B, nbps) + lengths (B,)
    -> normalized context (B, H, hd) f32.  int8 pools (factored scales) and
    shapes the kernel can't hold resident run the dense gather oracle in
    interpret mode and raise when compiled."""
    interp = _resolve_interpret(interpret)
    try:
        if k_scale is not None or k_pages.dtype == jnp.int8:
            raise ValueError("int8 pools have no kernel path (the factored "
                             "scale epilogue is jnp-side)")
        _paged.check_tiling(k_pages.shape[0], k_pages.shape[1],
                            k_pages.shape[2], k_pages.shape[3],
                            k_pages.dtype.itemsize, q.shape[1])
    except ValueError as e:
        _refuse_compiled(interp, e, "paged_attention",
                         pages=tuple(k_pages.shape), dtype=k_pages.dtype,
                         heads=q.shape[1])
        return ref.paged_attention_ref(q, k_pages, v_pages, table, lengths,
                                       k_scale, v_scale, softcap=softcap,
                                       window=window)
    return _paged.paged_attention(q, k_pages, v_pages, table, lengths,
                                  softcap=softcap, window=window,
                                  interpret=interp)


def paged_kv_write(pages: jax.Array, vals: jax.Array, blocks: jax.Array,
                   offsets: jax.Array, *,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Scatter one row per slot into pool pages (the paged decode's KV
    write); bitwise-equal to the jnp scatter oracle."""
    interp = _resolve_interpret(interpret)
    try:
        _paged.check_tiling(pages.shape[0], pages.shape[1], 1, 1,
                            pages.dtype.itemsize, 1)
    except ValueError as e:
        _refuse_compiled(interp, e, "paged_kv_write",
                         pages=tuple(pages.shape))
        return ref.paged_kv_write_ref(pages, vals, blocks, offsets)
    return _paged.paged_kv_write(pages, vals, blocks, offsets,
                                 interpret=interp)


class BlockPlan(NamedTuple):
    """Per-(shard, bucket) kernel tiling plan (DESIGN.md §2/§8)."""

    block_k: int     # fused-predictor k-tile over the shard's LOCAL rows
    mlp_groups: int  # fused-MLP selected-groups per grid step (tile height
                     # gps·G×d — wide buckets get taller tiles)


def choose_blocks(k: int, w: int, b: int, *, group_size: int = 8,
                  n_shards: int = 1, capacity_groups: int = 0) -> BlockPlan:
    """Shard-local, per-bucket kernel grid sizing (DESIGN.md §8).

    Under ``tp_shards`` tensor parallelism each shard's fused-predictor
    kernel tiles its LOCAL ``k / n_shards`` rows, so tiling feasibility must
    be judged at the local dims — a k that tiles fine unsharded can leave a
    degenerate per-shard grid.  ``capacity_groups`` is the bucket's LOCAL
    selection width, from which the fused-MLP tile height is chosen (0 =
    single-group tiles).  Returns a :class:`BlockPlan`; raises
    ``ValueError`` (same contract as ``choose_block_k``) when the split is
    invalid or the local predictor grid is degenerate — the serve path
    calls this per (bucket, shard) to refuse such a grid on the chip and
    to warn that interpret mode runs the jnp oracle for it.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if k % (n_shards * group_size):
        raise ValueError(
            f"k={k} not divisible by n_shards={n_shards} × "
            f"group_size={group_size}")
    bk = _predict.choose_block_k(k // n_shards, w, b, group_size)
    mlp = (_fused.mlp_groups_per_step(capacity_groups, group_size)
           if capacity_groups else 1)
    return BlockPlan(bk, mlp)


def count_pallas_dispatches(fn, *args, **kwargs) -> int:
    """Number of ``pallas_call`` dispatches one invocation of ``fn`` lowers
    to (recursing through nested jits/scans/conds).  Used by the dispatch-
    count regression tests and the kernel microbench — the decode-time
    sparse-MLP pipeline must stay at <= 2 (DESIGN.md §2)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)

    def walk(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += walk(sub)
        return n

    return walk(closed.jaxpr)
