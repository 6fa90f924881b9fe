"""Attention: GQA/MQA, sliding-window, logit softcap, qk-norm, QKV bias,
cross-attention; flash-style chunked softmax for long sequences; KV-cache
decode including sequence-sharded (flash-decoding) partials.

Pure functional JAX; memory-bounded via lax.scan so 32k-prefill and
500k-decode lower with O(chunk) live buffers.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.layers.norms import init_rmsnorm, rmsnorm

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False          # qwen1.5
    qk_norm: bool = False           # qwen3
    softcap: float = 0.0            # gemma2 attn logit softcapping
    rope_theta: float = 10000.0
    window: int = 0                 # sliding window; 0 = full attention
    causal: bool = True
    cross: bool = False             # K/V from encoder states
    d_kv_input: int = 0             # encoder width for cross-attn (0 => d_model)
    paged_kernel: bool = False      # paged decode via the pallas page-gather
                                    # kernel (kernels/paged_attn.py); False =
                                    # the jnp gather path (bitwise reference)


def init_attention(key: jax.Array, cfg: AttentionConfig,
                   dtype=jnp.float32) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    d_kv_in = cfg.d_kv_input or d
    s = d ** -0.5
    params = {
        "wq": (jax.random.normal(kq, (d, h * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(kk, (d_kv_in, kvh * hd)) * d_kv_in ** -0.5).astype(dtype),
        "wv": (jax.random.normal(kv, (d_kv_in, kvh * hd)) * d_kv_in ** -0.5).astype(dtype),
        "wo": (jax.random.normal(ko, (h * hd, d)) * (h * hd) ** -0.5).astype(dtype),
    }
    if cfg.qkv_bias:
        params["bq"] = jnp.zeros((h * hd,), dtype)
        params["bk"] = jnp.zeros((kvh * hd,), dtype)
        params["bv"] = jnp.zeros((kvh * hd,), dtype)
    if cfg.qk_norm:
        params["q_norm"] = init_rmsnorm(hd, dtype)
        params["k_norm"] = init_rmsnorm(hd, dtype)
    return params


def _project_qkv(params: dict, x: jax.Array, cfg: AttentionConfig,
                 kv_x: Optional[jax.Array] = None):
    b = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    src = x if kv_x is None else kv_x
    q = x @ params["wq"].astype(x.dtype)
    k = src @ params["wk"].astype(x.dtype)
    v = src @ params["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, -1, h, hd)
    k = k.reshape(b, -1, kvh, hd)
    v = v.reshape(b, -1, kvh, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    return q, k, v


def _chunk_scores(q, k, cfg: AttentionConfig, q_pos, k_pos):
    """q: (B,Cq,H,hd), k: (B,Ck,K,hd) -> masked f32 scores (B,K,rep,Cq,Ck)."""
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    rep = h // kvh
    b, cq = q.shape[0], q.shape[1]
    qg = q.reshape(b, cq, kvh, rep, q.shape[-1])
    s = jnp.einsum("bqkrh,btkh->bkrqt", qg.astype(jnp.float32),
                   k.astype(jnp.float32))
    s = s * (cfg.head_dim ** -0.5)
    if cfg.softcap > 0.0:
        s = jnp.tanh(s / cfg.softcap) * cfg.softcap
    mask = jnp.ones((cq, k.shape[1]), jnp.bool_)
    if cfg.causal and not cfg.cross:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if cfg.window > 0 and not cfg.cross:
        mask &= (q_pos[:, None] - k_pos[None, :]) < cfg.window
    return jnp.where(mask[None, None, None], s, NEG_INF)


def flash_attention(q, k, v, cfg: AttentionConfig,
                    q_positions: jax.Array, k_positions: jax.Array,
                    q_chunk: int = 1024, kv_chunk: int = 1024) -> jax.Array:
    """Memory-bounded attention via scan over KV chunks with running max/sum.

    q: (B, Sq, H, hd); k, v: (B, Sk, K, hd). Returns (B, Sq, H, hd).
    """
    b, sq, h, hd = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    rep = h // kvh

    def fit(s, c):
        c = max(1, min(c, s))
        while s % c:
            c -= 1
        return c

    q_chunk = fit(sq, q_chunk)
    kv_chunk = fit(sk, kv_chunk)
    nq, nk = sq // q_chunk, sk // kv_chunk

    kc = k.reshape(b, nk, kv_chunk, kvh, hd)
    vc = v.reshape(b, nk, kv_chunk, kvh, hd)
    kpos_c = k_positions.reshape(nk, kv_chunk)

    def q_block(args):
        qb, qpos = args  # (B, q_chunk, H, hd), (q_chunk,)

        def kv_step(carry, xs):
            m, l, acc = carry
            kb, vb, kpos = xs
            s = _chunk_scores(qb, kb, cfg, qpos, kpos)  # (B,K,rep,Cq,Ck)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            pv = jnp.einsum("bkrqt,btkh->bkrqh", p, vb.astype(jnp.float32))
            acc = acc * corr[..., None] + pv
            return (m_new, l, acc), None

        m0 = jnp.full((b, kvh, rep, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, rep, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kvh, rep, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), kpos_c))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4).reshape(b, q_chunk, h, hd)

    qb = q.reshape(b, nq, q_chunk, h, hd).transpose(1, 0, 2, 3, 4)
    qpos_c = q_positions.reshape(nq, q_chunk)
    out = jax.lax.map(q_block, (qb, qpos_c))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


def attend(params: dict, x: jax.Array, cfg: AttentionConfig,
           positions: jax.Array, kv_x: Optional[jax.Array] = None,
           kv_positions: Optional[jax.Array] = None,
           q_chunk: int = 1024, kv_chunk: int = 1024,
           return_kv: bool = False, kv_pad_to: int = 0):
    """Full training/prefill attention (self or cross). x: (B, S, d).

    ``return_kv=True`` additionally returns the (roped) K/V for KV-cache
    seeding during prefill.

    ``kv_pad_to`` (prefill only; ignored for cross-attn): zero-pad the KV
    operand to this fixed width with causally-masked positions before the
    flash scan.  Softmax reductions on XLA are only bitwise-reproducible at
    a fixed width (a length-S and a length-max_len reduction of the same
    live values tree differently), so monolithic prefill pads to the cache
    width here and chunked prefill (``chunk_attend``) attends the cache at
    that same width — the bitwise-parity contract of DESIGN.md §9.  Masked
    pad lanes are exact +0.0 after exp and never perturb the live values.
    The returned K/V are unpadded.
    """
    from repro.layers.rope import apply_rope
    from repro.sharding import rules as R
    q, k, v = _project_qkv(params, x, cfg, kv_x)
    if kv_positions is None:
        kv_positions = positions
    if not cfg.cross:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    k, v = R.shard_heads(k), R.shard_heads(v)
    kv_ret = (k, v)
    s_kv = k.shape[1]
    if kv_pad_to and kv_pad_to > s_kv and not cfg.cross:
        pad = ((0, 0), (0, kv_pad_to - s_kv), (0, 0), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        # pad positions follow contiguously past the live ones, so they sit
        # strictly above every query position and the causal mask drops them
        kv_positions = jnp.concatenate([
            jnp.asarray(kv_positions, jnp.int32),
            jnp.asarray(kv_positions, jnp.int32)[-1] + 1
            + jnp.arange(kv_pad_to - s_kv, dtype=jnp.int32)])
    q = R.shard_heads(q)
    out = flash_attention(q, k, v, cfg, positions, kv_positions,
                          q_chunk, kv_chunk)
    b, s = x.shape[0], x.shape[1]
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    out = out @ params["wo"].astype(x.dtype)
    if return_kv:
        return out, kv_ret
    return out


# ---------------------------------------------------------------- decode ---

def init_kv_cache(batch: int, max_len: int, cfg: AttentionConfig,
                  dtype=jnp.bfloat16) -> dict:
    """KV cache. dtype=int8 -> quantized storage with per-(B,S,K) scales
    (halves HBM vs bf16; scales factor out of the attention dots so the
    cache is never dequantized in memory — DESIGN.md serving features)."""
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if dtype == jnp.int8:
        cache["k_scale"] = jnp.zeros(shape[:3], jnp.bfloat16)
        cache["v_scale"] = jnp.zeros(shape[:3], jnp.bfloat16)
    return cache


def _quantize_kv(x: jax.Array):
    """(B,S,K,hd) -> int8 values + per-(B,S,K) bf16 scale."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = (amax / 127.0 + 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


@jax.named_scope("kv_write")
def update_kv_cache(cache: dict, k_new: jax.Array, v_new: jax.Array,
                    index: jax.Array) -> dict:
    """Insert (B, S_new, K, hd) at sequence offset `index`.

    ``index`` may be a scalar (one shared offset, the chunked-scheduler
    layout) or (B,) — one offset per batch slot, the slot-refill continuous
    batching layout (DESIGN.md §5) where every slot sits at its own length.
    """
    idx = jnp.asarray(index).astype(jnp.int32)
    per_slot = idx.ndim == 1

    def put(buf, upd, seq_axis_rank):
        upd = upd.astype(buf.dtype)
        if not per_slot:
            starts = (0, idx) + (0,) * (seq_axis_rank - 2)
            return jax.lax.dynamic_update_slice(buf, upd, starts)
        one = lambda b, u, i: jax.lax.dynamic_update_slice(
            b, u, (i,) + (0,) * (seq_axis_rank - 2))
        return jax.vmap(one)(buf, upd, idx)

    out = dict(cache)
    if cache["k"].dtype == jnp.int8:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        out["k"] = put(cache["k"], kq, 4)
        out["v"] = put(cache["v"], vq, 4)
        out["k_scale"] = put(cache["k_scale"], ks, 3)
        out["v_scale"] = put(cache["v_scale"], vs, 3)
        return out
    out["k"] = put(cache["k"], k_new, 4)
    out["v"] = put(cache["v"], v_new, 4)
    return out


def decode_scores(q, cache_k, cfg: AttentionConfig, kv_positions):
    """q: (B,1,H,hd) vs cache (B,S,K,hd) -> f32 scores (B,K,rep,S) (unmasked).

    The cache operand stays in its storage dtype (a .astype(f32) here would
    materialize a full-cache f32 copy — gigabytes at 32k×128); the MXU does
    bf16×bf16 with f32 accumulation via preferred_element_type.
    """
    b, _, h, hd = q.shape
    kvh = cfg.n_kv_heads
    rep = h // kvh
    qg = q.reshape(b, kvh, rep, hd)
    qg = qg.astype(jnp.bfloat16 if cache_k.dtype == jnp.int8
                   else cache_k.dtype)
    s = jnp.einsum("bkrh,btkh->bkrt", qg, cache_k,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    if cfg.softcap > 0.0:
        s = jnp.tanh(s / cfg.softcap) * cfg.softcap
    return s


def decode_attend_partial(q, cache_k, cache_v, cfg: AttentionConfig,
                          kv_positions: jax.Array, q_position: jax.Array,
                          k_scale=None, v_scale=None):
    """Flash-decoding partial over a KV shard: returns (o_unnorm, l, m).

    kv_positions: (S,) — or (B,S) per-slot — global positions of cache slots
    (for masks); slots past the live length must carry position >
    q_position.  q_position: scalar, or (B,) when every batch slot decodes
    at its own length (slot-refill scheduler, DESIGN.md §5).
    int8 caches pass per-(B,S,K) scales; they factor out of both dots
    (applied to scores / folded into p) so nothing dequantizes in memory.
    """
    s = decode_scores(q, cache_k, cfg, kv_positions)         # (B,K,rep,S)
    if k_scale is not None:
        s = s * k_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
    q_pos = jnp.asarray(q_position)
    if q_pos.ndim:                                           # per-slot (B,)
        q_pos = q_pos[:, None]                               # vs (B,S) or (S,)
    mask = kv_positions <= q_pos
    if cfg.window > 0:
        mask &= (q_pos - kv_positions) < cfg.window
    mask = mask[:, None, None, :] if mask.ndim == 2 else mask[None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    m = s.max(-1)                                            # (B,K,rep)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    if v_scale is not None:
        pv = p * v_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
        o = jnp.einsum("bkrt,btkh->bkrh", pv.astype(jnp.bfloat16),
                       cache_v, preferred_element_type=jnp.float32)
    else:
        o = jnp.einsum("bkrt,btkh->bkrh", p.astype(cache_v.dtype), cache_v,
                       preferred_element_type=jnp.float32)
    return o, l, m


def combine_decode_partials(o, l, m, axis_name: str):
    """Combine (o_unnorm, l, m) across a sharded-KV mesh axis (flash-decode)."""
    m_g = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * corr, axis_name)
    o_g = jax.lax.psum(o * corr[..., None], axis_name)
    return o_g / jnp.maximum(l_g, 1e-30)[..., None]


def finalize_decode(o, l, params: dict, x_dtype, cfg: AttentionConfig):
    out = o / jnp.maximum(l, 1e-30)[..., None]
    b = out.shape[0]
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim).astype(x_dtype)
    return out @ params["wo"].astype(x_dtype)


def decode_attend(params: dict, x: jax.Array, cfg: AttentionConfig,
                  cache: dict, cache_len: jax.Array,
                  kv_positions: Optional[jax.Array] = None) -> tuple:
    """Single-token decode. x: (B, 1, d). Returns (out (B,1,d), new_cache).

    ``cache_len`` is a scalar (all slots at the same length) or (B,) — the
    slot-refill scheduler's layout where each batch slot holds its own
    request at its own position (DESIGN.md §5).
    """
    from repro.layers.rope import apply_rope
    cl = jnp.asarray(cache_len)
    per_slot = cl.ndim == 1
    # (B,1) per-slot positions or (1,1) shared — broadcasts against (B,1,H,hd)
    pos = cl[:, None] if per_slot else cl.reshape(1)[None]
    q, k, v = _project_qkv(params, x, cfg)
    if not cfg.cross:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    cache = update_kv_cache(cache, k, v, cl)
    s_max = cache["k"].shape[1]
    if kv_positions is None:
        kv_positions = jnp.arange(s_max)
    # dead slots (>= cache_len+1) get position s_max+pos -> masked out
    cmp = cl[:, None] if per_slot else cl
    live = kv_positions <= cmp                       # (S,) or (B,S)
    sent = q_pos_sentinel(s_max, cl)
    kvp = jnp.where(live, kv_positions, sent[:, None] if per_slot else sent)
    o, l, m = decode_attend_partial(q, cache["k"], cache["v"], cfg, kvp,
                                    cl, cache.get("k_scale"),
                                    cache.get("v_scale"))
    return finalize_decode(o, l, params, x.dtype, cfg), cache


def q_pos_sentinel(s_max: int, cache_len: jax.Array) -> jax.Array:
    return jnp.int32(s_max) + cache_len + 1


# ---------------------------------------------------------- paged decode ---
#
# Paged KV pool (DESIGN.md §10): the per-layer cache is a global block pool
# with leaves (N, block, K, hd) instead of per-slot (B, max_len, K, hd); a
# per-slot block table (B, max_len/block) maps each slot's logical sequence
# blocks onto pool blocks.  The decode step scatters the new token's K/V
# into the owning pool block, gathers the table back into the dense per-slot
# view, and runs the IDENTICAL attention math as ``decode_attend`` — same
# shapes, same reduction order, so greedy tokens and telemetry are bitwise
# equal to the dense path.  Stale content in recycled pool blocks sits on
# masked lanes only: after the NEG_INF mask its softmax weight is exactly
# +0.0, so it contributes nothing (the same kv_pad-to-width denominator
# argument chunked prefill uses, DESIGN.md §9).

@jax.named_scope("kv_write")
def paged_update_kv(pool: dict, k_new: jax.Array, v_new: jax.Array,
                    table: jax.Array, cache_len: jax.Array) -> dict:
    """Scatter one token per slot into the pool: (B,1,K,hd) K/V at per-slot
    position ``cache_len`` lands in block ``table[b, pos//block]`` at row
    ``pos % block``.  Slots parked on a shared write-off block (the
    scheduler points dead/pending slots' whole table row there) collide —
    harmless, that block is never gathered for a live slot."""
    idx = jnp.asarray(cache_len).astype(jnp.int32)        # (B,)
    bs = pool["k"].shape[1]
    blk = jnp.take_along_axis(table, (idx // bs)[:, None], axis=1)[:, 0]
    off = idx % bs

    def put(buf, upd):
        return buf.at[blk, off].set(upd[:, 0].astype(buf.dtype))

    out = dict(pool)
    if pool["k"].dtype == jnp.int8:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        out["k"] = put(pool["k"], kq)
        out["v"] = put(pool["v"], vq)
        out["k_scale"] = put(pool["k_scale"], ks)
        out["v_scale"] = put(pool["v_scale"], vs)
        return out
    out["k"] = put(pool["k"], k_new)
    out["v"] = put(pool["v"], v_new)
    return out


def paged_gather_kv(pool: dict, table: jax.Array) -> dict:
    """Gather per-slot dense views from the pool: leaves (N, block, ...) +
    table (B, nbps) -> (B, nbps*block, ...) — the exact shapes the dense
    decode attends, so downstream math is operation-for-operation the
    per-slot path."""
    b, nbps = table.shape

    def take(buf):
        g = buf[table]                                    # (B, nbps, bs, ...)
        return g.reshape((b, nbps * buf.shape[1]) + buf.shape[2:])

    return {k: take(v) for k, v in pool.items()}


def paged_decode_attend(params: dict, x: jax.Array, cfg: AttentionConfig,
                        pool: dict, cache_len: jax.Array,
                        table: jax.Array) -> tuple:
    """Single-token decode against a paged KV pool. x: (B, 1, d); ``pool``
    holds this layer's block-pool leaves; ``table`` (B, nbps) int32;
    ``cache_len`` (B,) per-slot lengths (the slot-refill layout — paged
    serving always runs per-slot).  Returns (out (B,1,d), new_pool).
    Bitwise-identical to ``decode_attend`` on the per-slot dense cache
    holding the same live tokens (see module comment)."""
    from repro.layers.rope import apply_rope
    from repro.sharding import rules as R
    cl = jnp.asarray(cache_len)
    if cl.ndim != 1:
        raise ValueError("paged decode runs per-slot: cache_len must be (B,)")
    pos = cl[:, None]
    q, k, v = _project_qkv(params, x, cfg)
    if not cfg.cross:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    if (cfg.paged_kernel and pool["k"].dtype != jnp.int8
            and R.current_mesh() is None):
        # pallas page-gather route (kernels/paged_attn.py): scatter + attend
        # straight off the pool pages, no dense gather materialized.  Bitwise
        # against the jnp path below (pinned in tests); int8 pools and mesh
        # runs stay on the jnp path (scale epilogue / GSPMD placement live
        # there).
        from repro.kernels import ops
        bs = pool["k"].shape[1]
        blk = jnp.take_along_axis(table, (cl // bs)[:, None], axis=1)[:, 0]
        off = cl % bs
        new_pool = dict(pool)
        with jax.named_scope("kv_write"):
            new_pool["k"] = ops.paged_kv_write(pool["k"], k[:, 0], blk, off)
            new_pool["v"] = ops.paged_kv_write(pool["v"], v[:, 0], blk, off)
        ctx = ops.paged_attention(q[:, 0], new_pool["k"], new_pool["v"],
                                  table, cl, softcap=cfg.softcap,
                                  window=cfg.window)
        b = x.shape[0]
        out = (ctx.reshape(b, 1, cfg.n_heads * cfg.head_dim).astype(x.dtype)
               @ params["wo"].astype(x.dtype))
        return out, new_pool
    pool = paged_update_kv(pool, k, v, table, cl)
    dense = paged_gather_kv(pool, table)
    # pin the gathered view to the dense cache's layout (S over 'model') so
    # the mesh path partitions the attention dots exactly like the per-slot
    # cache would — placement parity is what keeps tokens bitwise on the 2D
    # mesh (DESIGN.md §8/§10); no-op without a mesh
    dense = {kk: (R.shard_kv_cache(vv) if kk in ("k", "v")
                  else R.shard_kv_scale(vv)) for kk, vv in dense.items()}
    s_max = dense["k"].shape[1]
    kv_positions = jnp.arange(s_max)
    live = kv_positions <= cl[:, None]
    sent = q_pos_sentinel(s_max, cl)
    kvp = jnp.where(live, kv_positions, sent[:, None])
    o, l, m = decode_attend_partial(q, dense["k"], dense["v"], cfg, kvp,
                                    cl, dense.get("k_scale"),
                                    dense.get("v_scale"))
    return finalize_decode(o, l, params, x.dtype, cfg), pool


def chunk_attend(params: dict, x: jax.Array, cfg: AttentionConfig,
                 cache: dict, offset: jax.Array,
                 valid: Optional[jax.Array] = None,
                 q_chunk: int = 1024, kv_chunk: int = 1024):
    """Chunked-prefill attention: an S-token chunk at sequence ``offset``
    attends the KV cache (every previously written chunk plus itself).

    x: (B, S, d); ``offset`` is a scalar — the prefill scratch layout where
    all rows sit at the same chunk boundary.  ``valid`` (scalar or (B,)) is
    the total number of real prompt tokens; chunk rows at absolute position
    >= valid are padding.  Their K/V are zeroed before the cache write so
    the spliced cache stays bitwise-identical to a monolithic prefill (pad
    positions match the zero-initialized cache) — decode additionally masks
    them by cache_len, so correctness never depends on the zeroing, only
    the parity guarantee does.

    Chunks must be written in order from offset 0: positions beyond
    offset+S are excluded causally, so stale cache content is never
    attended and the scratch cache needs no re-zeroing between requests.

    Bitwise parity with monolithic ``attend`` relies on masked lanes being
    exact +0.0 after exp (NEG_INF scores) so they never perturb the flash
    accumulation, plus both paths seeing a single KV block (kv_chunk >=
    live length).  tests/test_prefill_chunked.py pins it.

    Returns (out (B, S, d), new_cache).
    """
    from repro.layers.rope import apply_rope
    from repro.sharding import rules as R
    b, s = x.shape[0], x.shape[1]
    off = jnp.asarray(offset, jnp.int32)
    q_pos = off + jnp.arange(s, dtype=jnp.int32)             # (S,)
    q, k, v = _project_qkv(params, x, cfg)
    if not cfg.cross:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)
    if valid is not None:
        vld = jnp.asarray(valid, jnp.int32)
        live = q_pos < (vld[:, None] if vld.ndim else vld)   # (B,S) or (S,)
        if live.ndim == 1:
            live = live[None, :]
        live = live[:, :, None, None]
        k = jnp.where(live, k, jnp.zeros_like(k))
        v = jnp.where(live, v, jnp.zeros_like(v))
    cache = update_kv_cache(cache, k, v, off)
    s_max = cache["k"].shape[1]
    k_pos = jnp.arange(s_max, dtype=jnp.int32)
    ck, cv = cache["k"], cache["v"]
    if ck.dtype == jnp.int8:
        ck = (ck.astype(jnp.float32)
              * cache["k_scale"].astype(jnp.float32)[..., None]).astype(x.dtype)
        cv = (cv.astype(jnp.float32)
              * cache["v_scale"].astype(jnp.float32)[..., None]).astype(x.dtype)
    else:
        ck, cv = ck.astype(x.dtype), cv.astype(x.dtype)
    q, ck, cv = R.shard_heads(q), R.shard_heads(ck), R.shard_heads(cv)
    out = flash_attention(q, ck, cv, cfg, q_pos, k_pos,
                          q_chunk=q_chunk, kv_chunk=kv_chunk)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    out = out @ params["wo"].astype(x.dtype)
    return out, cache


def cross_decode_attend(params: dict, x: jax.Array, cfg: AttentionConfig,
                        enc_k: jax.Array, enc_v: jax.Array) -> jax.Array:
    """Decode-time cross-attention against precomputed encoder K/V."""
    b = x.shape[0]
    q = x @ params["wq"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
    q = q.reshape(b, 1, cfg.n_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
    t = enc_k.shape[1]
    o, l, m = decode_attend_partial(
        q, enc_k, enc_v, dataclasses.replace(cfg, window=0),
        jnp.zeros((t,), jnp.int32), jnp.int32(0))
    return finalize_decode(o, l, params, x.dtype, cfg)


def precompute_cross_kv(params: dict, enc_out: jax.Array,
                        cfg: AttentionConfig) -> tuple:
    """Encoder K/V for cross-attn, computed once per request."""
    b, t = enc_out.shape[0], enc_out.shape[1]
    k = (enc_out @ params["wk"].astype(enc_out.dtype))
    v = (enc_out @ params["wv"].astype(enc_out.dtype))
    if cfg.qkv_bias:
        k = k + params["bk"].astype(enc_out.dtype)
        v = v + params["bv"].astype(enc_out.dtype)
    k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        k = rmsnorm(params["k_norm"], k)
    return k, v
