"""Pure-jnp oracles for every Pallas kernel (allclose targets for tests)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import predictor as P
from repro.core.relufication import get_activation


def predict_counts_ref(packed_w: jax.Array, packed_x: jax.Array) -> jax.Array:
    """Oracle for kernels.predict.predict_counts: (B, k) neg-product counts."""
    return P.neg_counts(packed_w, packed_x)


def predict_group_margins_ref(packed_w: jax.Array, x: jax.Array,
                              d_valid: int, alpha: jax.Array,
                              group_size: int = 8):
    """Oracle for ``ops.predict_group_margins``: the multi-dispatch
    composition (pack -> margins -> group min) the fused kernel replaces.
    Row tiling never changes per-row results, so it serves decode batches
    and prefill chunks alike."""
    m = P.margins(packed_w, P.pack_signs(x), d_valid, alpha)     # (B, k)
    b, k = m.shape
    gm = m.reshape(b, k // group_size, group_size).min(-1)       # (B, k/G)
    cnt = jnp.sum(gm <= 0, axis=-1, dtype=jnp.int32)             # (B,)
    return gm, cnt


def fused_mlp_telemetry_ref(x: jax.Array,
                            wg_t: jax.Array,
                            sel_indices: jax.Array,
                            sel_count: jax.Array,
                            gm_tok: jax.Array,
                            *,
                            group_size: int = 8,
                            activation: str = "relu",
                            fatrelu_threshold: float = 0.0) -> jax.Array:
    """Oracle for the fused kernel's in-kernel telemetry (B, 3) int32:
    (actual, false_neg_proxy, realized) row counts over the selected groups
    (kernels.sparse_mlp_fused.TELEMETRY_COLS)."""
    b, d = x.shape
    k = wg_t.shape[0]
    g = group_size
    cap = sel_indices.shape[0]
    act = get_activation(
        "fatrelu" if (activation == "fatrelu" or fatrelu_threshold > 0.0)
        else activation, fatrelu_threshold)
    valid = jnp.arange(cap) < sel_count                          # (C,)
    rows = jnp.take(wg_t.reshape(k // g, g, d), sel_indices,
                    axis=0).reshape(cap * g, d)
    ga = act(jnp.einsum("bd,nd->bn", x.astype(jnp.float32),
                        rows.astype(jnp.float32)))               # (B, C*g)
    vrow = jnp.repeat(valid, g)[None, :]
    live = (ga > 0) & vrow
    keep = (jnp.take(gm_tok, sel_indices, axis=-1) <= 0)         # (B, C)
    keep_row = jnp.repeat(keep, g, axis=-1)
    actual = jnp.sum(live, axis=-1, dtype=jnp.int32)
    fn = jnp.sum(live & ~keep_row, axis=-1, dtype=jnp.int32)
    realized = jnp.sum((keep & valid[None, :]).astype(jnp.int32),
                       axis=-1) * g
    return jnp.stack([actual, fn, realized], axis=-1)


def fused_sparse_mlp_ref(x: jax.Array,
                         wg_t: jax.Array,
                         wu_t: jax.Array | None,
                         wd_t: jax.Array,
                         sel_indices: jax.Array,
                         sel_count: jax.Array,
                         *,
                         group_size: int = 8,
                         activation: str = "relu",
                         fatrelu_threshold: float = 0.0) -> jax.Array:
    """Oracle for kernels.sparse_mlp_fused.fused_sparse_mlp.

    Computes the same capacity-gathered gated MLP in plain jnp: only the first
    ``sel_count`` groups contribute; padding entries are masked to zero.
    """
    b, d = x.shape
    k = wg_t.shape[0]
    g = group_size
    cap = sel_indices.shape[0]
    act = get_activation(
        "fatrelu" if (activation == "fatrelu" or fatrelu_threshold > 0.0)
        else activation, fatrelu_threshold)

    valid = (jnp.arange(cap) < sel_count)

    def take(w_t):
        grouped = w_t.reshape(k // g, g, d)
        return jnp.take(grouped, sel_indices, axis=0).reshape(cap * g, d)

    vmask = jnp.repeat(valid, g).astype(jnp.float32)
    gsel = act(jnp.einsum("bd,nd->bn", x.astype(jnp.float32),
                          take(wg_t).astype(jnp.float32)))
    h = gsel * vmask
    if wu_t is not None:
        h = h * jnp.einsum("bd,nd->bn", x.astype(jnp.float32),
                           take(wu_t).astype(jnp.float32))
    y = jnp.einsum("bn,nd->bd", h, take(wd_t).astype(jnp.float32))
    return y.astype(jnp.float32)


def fused_sparse_mlp_chunk_ref(x: jax.Array,
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
                               collect_stats: bool = False):
    """Oracle for ``ops.fused_sparse_mlp_chunk``: per-row
    math is row-tiling-invariant, so it composes the untiled MLP oracle with
    the telemetry oracle (matching the chunked kernel's (y, tel) contract
    when ``collect_stats``)."""
    y = fused_sparse_mlp_ref(x, wg_t, wu_t, wd_t, sel_indices, sel_count,
                             group_size=group_size, activation=activation,
                             fatrelu_threshold=fatrelu_threshold)
    if not collect_stats:
        return y
    tel = fused_mlp_telemetry_ref(x, wg_t, sel_indices, sel_count, gm_tok,
                                  group_size=group_size,
                                  activation=activation,
                                  fatrelu_threshold=fatrelu_threshold)
    return y, tel


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "activation", "fatrelu_threshold",
                     "collect_stats"))
def fused_sparse_mlp_q_ref(x: jax.Array,
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
                           collect_stats: bool = False):
    """Oracle for kernels.sparse_mlp_fused.fused_sparse_mlp_q — BITWISE.

    Unlike the fp oracle (one big einsum, allclose target), this one
    replays the kernel's exact op order: a ``fori_loop`` over selection
    steps, each step gathering the int8 tiles + scale tiles and running
    the SAME :func:`_qdot` / epilogue-scale / telemetry helpers the pallas
    kernel runs — so pallas-vs-ref parity is bitwise by construction
    (DESIGN.md §13).  Steps past ``sel_count`` keep the accumulator
    untouched via ``jnp.where(valid, y + step, y)`` (matching ``pl.when``:
    no -0.0/+0.0 drift from adding a masked step).  The whole oracle is
    jitted: bitwise parity only holds compiled-vs-compiled (the eager
    per-op path contracts FMAs differently).
    """
    from repro.kernels.sparse_mlp_fused import _qdot, _telemetry_delta

    b, d = x.shape
    k = wg_q.shape[0]
    g = group_size
    qg = d // wg_s.shape[1]
    qpg = qg // g                       # selection groups per wd row-group
    cap = sel_indices.shape[0]
    act = get_activation(
        "fatrelu" if (activation == "fatrelu" or fatrelu_threshold > 0.0)
        else activation, fatrelu_threshold)
    assert k % g == 0 and qg % g == 0 and k % qg == 0

    sel = sel_indices.astype(jnp.int32)
    cnt = sel_count.astype(jnp.int32)
    xf = x.astype(jnp.float32)
    gmf = gm_tok.astype(jnp.float32) if gm_tok is not None else None

    def step(n, carry):
        y, tel = carry
        idx = sel[n]

        def tile(w, s=None):
            t = jax.lax.dynamic_slice_in_dim(w, idx * g, g, axis=0)
            if s is None:
                return t
            return t, jax.lax.dynamic_slice_in_dim(s, idx * g, g, axis=0)

        ga = act(_qdot(xf, *tile(wg_q, wg_s), qg))
        h = ga * _qdot(xf, *tile(wu_q, wu_s), qg) if wu_q is not None else ga
        yd = jax.lax.dot_general(
            h, tile(wd_q).astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        wds_row = jax.lax.dynamic_slice_in_dim(wd_s, idx // qpg, 1, axis=0)
        valid = n < cnt
        y = jnp.where(valid, y + yd * wds_row, y)
        if collect_stats:
            gm_col = jax.lax.dynamic_slice_in_dim(gmf, idx, 1, axis=1)
            tel = jnp.where(valid,
                            tel + _telemetry_delta(ga, gm_col <= 0), tel)
        return y, tel

    # fori_loop (not a python loop): one compiled step body keeps the jit
    # cost O(1) in the capacity, and jitting is what makes the parity
    # BITWISE — the eager per-op path contracts FMAs differently than the
    # compiled kernel (same caveat as predict_group_margins_ref's tests)
    y, tel = jax.lax.fori_loop(
        0, cap, step, (jnp.zeros((b, d), jnp.float32),
                       jnp.zeros((b, 3), jnp.int32)))
    if collect_stats:
        return y, tel
    return y


def fused_sparse_mlp_chunk_q_ref(*args, **kw):
    """Oracle for ``ops.fused_sparse_mlp_chunk_q``: row tiling never
    changes per-row math, so the decode oracle IS the chunk oracle."""
    return fused_sparse_mlp_q_ref(*args, **kw)


# ------------------------------------------------------- paged attention --

_NEG_INF = -1e30     # matches layers/attention.py NEG_INF (mask parity)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        table: jax.Array, lengths: jax.Array,
                        k_scale: jax.Array | None = None,
                        v_scale: jax.Array | None = None, *,
                        softcap: float = 0.0, window: int = 0) -> jax.Array:
    """Dense oracle for kernels.paged_attn.paged_attention: gather the pool
    pages into the per-slot dense (B, S, K, hd) view, then run the decode
    softmax at full cache width — the identical operation sequence as
    ``layers.attention.decode_attend_partial`` + normalize, so it is pinned
    BITWISE against the dense per-slot decode path (stale lanes in recycled
    pages sit behind the NEG_INF mask with softmax weight exactly +0.0 —
    the kv_pad-to-width denominator argument, DESIGN.md §9/§10).  int8
    pools pass the factored per-(B,S,K) scales."""
    b, h, hd = q.shape
    n, bs, kvh, _ = k_pages.shape
    nbps = table.shape[1]
    s_max = nbps * bs
    rep = h // kvh
    kk = k_pages[table].reshape(b, s_max, kvh, hd)
    vv = v_pages[table].reshape(b, s_max, kvh, hd)
    qg = q.reshape(b, kvh, rep, hd)
    qg = qg.astype(jnp.bfloat16 if kk.dtype == jnp.int8 else kk.dtype)
    s = jnp.einsum("bkrh,btkh->bkrt", qg, kk,
                   preferred_element_type=jnp.float32)
    # constants folded in python, matching the kernel (a chained
    # (s*scale)/softcap invites per-graph simplifier drift)
    if softcap > 0.0:
        s = jnp.tanh(s * ((hd ** -0.5) / softcap)) * softcap
    else:
        s = s * (hd ** -0.5)
    if k_scale is not None:
        ks = k_scale[table].reshape(b, s_max, kvh)
        s = s * ks.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
    kvp = jnp.arange(s_max, dtype=jnp.int32)
    mask = kvp[None, :] <= lengths[:, None]
    if window > 0:
        mask &= (lengths[:, None] - kvp[None, :]) < window
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    if v_scale is not None:
        vs = v_scale[table].reshape(b, s_max, kvh)
        pv = p * vs.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
        o = jnp.einsum("bkrt,btkh->bkrh", pv.astype(jnp.bfloat16), vv,
                       preferred_element_type=jnp.float32)
    else:
        o = jnp.einsum("bkrt,btkh->bkrh", p.astype(vv.dtype), vv,
                       preferred_element_type=jnp.float32)
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(b, h, hd)


def paged_kv_write_ref(pages: jax.Array, vals: jax.Array, blocks: jax.Array,
                       offsets: jax.Array) -> jax.Array:
    """Oracle for kernels.paged_attn.paged_kv_write (one scatter)."""
    return pages.at[blocks, offsets].set(vals.astype(pages.dtype))
