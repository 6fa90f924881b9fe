"""Decoder-only LM family: dense / MoE / hybrid (Mamba2+shared-attn) / xLSTM.

One builder covers all assigned decoder-only archs via a *group pattern*:
the layer stack is a ``lax.scan`` over groups of ``p`` blocks (compile-time
O(1) in depth), where the pattern encodes static per-position flavor —
e.g. gemma2 is ``p=2`` (local, global), zamba2 is shared-attn + ``p`` mamba
layers per group, xlstm is ``p=4`` (m, s, m, m).

Public API (same across model families):
  init_lm, forward, lm_loss, init_caches, prefill, decode_step,
  prepare_sparse (adds packed sign bits for SparseInfer serving).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import predictor as CP
from repro.layers import attention as A
from repro.layers import embeddings as E
from repro.layers import mamba2 as M2
from repro.layers import xlstm as XL
from repro.layers.mlp import init_mlp, mlp_apply
from repro.layers.moe import MoEConfig, init_moe, moe_apply
from repro.models import common as C
from repro.sharding import rules as R


# ------------------------------------------------------------------ config

def moe_cfg(cfg: ModelConfig) -> MoEConfig:
    return MoEConfig(
        d_model=cfg.d_model, d_expert=cfg.d_ff, n_experts=cfg.n_experts,
        top_k=cfg.top_k, n_shared=cfg.n_shared_experts,
        d_shared=cfg.d_ff * max(1, cfg.n_shared_experts),
        capacity_factor=cfg.capacity_factor,
        router_norm_topk=cfg.router_norm_topk,
        activation=cfg.sparse.activation if cfg.sparse.enabled else cfg.activation)


def mamba_cfg(cfg: ModelConfig) -> M2.Mamba2Config:
    return M2.Mamba2Config(d_model=cfg.d_model, d_state=cfg.ssm_state,
                           head_dim=cfg.ssm_head_dim, chunk=cfg.ssm_chunk)


def xlstm_cfg(cfg: ModelConfig) -> XL.XLSTMConfig:
    return XL.XLSTMConfig(d_model=cfg.d_model, n_heads=cfg.n_heads,
                          slstm_every=cfg.slstm_every or 4)


def _windows(cfg: ModelConfig) -> tuple:
    """Static per-pattern-position sliding windows."""
    if cfg.local_global_period:
        # gemma2: alternate local (window) and global
        return tuple(cfg.window if (i % 2 == 0) else 0
                     for i in range(cfg.local_global_period))
    return (cfg.window,)


def _act_name(cfg: ModelConfig) -> str:
    return cfg.sparse.activation if cfg.sparse.enabled else cfg.activation


def _mlp_sparse_cfg(cfg: ModelConfig):
    return dataclasses.replace(cfg.sparse, activation=_act_name(cfg))


def _alphas(cfg: ModelConfig) -> np.ndarray:
    return cfg.sparse.alpha_schedule().alphas(cfg.n_layers)


# -------------------------------------------------------------------- init

def _init_dense_block(key, cfg: ModelConfig, moe_block: bool):
    ka, km = jax.random.split(key)
    pd = C.param_dtype(cfg)
    blk = {
        "ln1": C.norm_init(cfg),
        "attn": A.init_attention(ka, C.attn_cfg(cfg), pd),
        "ln2": C.norm_init(cfg),
    }
    if moe_block:
        blk["moe"] = init_moe(km, moe_cfg(cfg), pd)
    else:
        blk["mlp"] = init_mlp(km, cfg.d_model, cfg.d_ff, cfg.gated_mlp, pd)
    if cfg.post_block_norm:
        blk["ln1_post"] = C.norm_init(cfg)
        blk["ln2_post"] = C.norm_init(cfg)
    return blk


def _hybrid_layout(cfg: ModelConfig):
    """zamba2: n_inv groups of (shared attn + attn_every mamba layers)."""
    ae = cfg.attn_every
    n_main = (cfg.n_layers // ae) * ae
    n_tail = cfg.n_layers - n_main
    return n_main // ae, n_main, n_tail


def init_lm(key: jax.Array, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, 8)
    pd = C.param_dtype(cfg)
    params: dict[str, Any] = {
        "embed": E.init_embedding(keys[0], cfg.vocab_padded, cfg.d_model, pd),
        "final_norm": C.norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = E.init_unembed(keys[1], cfg.vocab_padded,
                                           cfg.d_model, pd)
    fam = cfg.family
    if fam in ("dense", "moe"):
        p = cfg.local_global_period or 1
        n_moe = cfg.n_layers - cfg.first_dense_layers if fam == "moe" else 0
        n_main = (cfg.n_layers - cfg.first_dense_layers)
        assert n_main % p == 0, (cfg.n_layers, p)
        if cfg.first_dense_layers:
            params["first_blocks"] = C.stacked_init(
                lambda k: _init_dense_block(k, cfg, False), keys[2],
                cfg.first_dense_layers)
        params["blocks"] = C.stacked_init(
            lambda k: _init_dense_block(k, cfg, fam == "moe"), keys[3], n_main)
    elif fam == "hybrid":
        n_inv, n_main, n_tail = _hybrid_layout(cfg)
        params["mamba"] = C.stacked_init(
            lambda k: {"ln": C.norm_init(cfg),
                       "mixer": M2.init_mamba2(k, mamba_cfg(cfg), pd)},
            keys[2], n_main)
        if n_tail:
            params["mamba_tail"] = C.stacked_init(
                lambda k: {"ln": C.norm_init(cfg),
                           "mixer": M2.init_mamba2(k, mamba_cfg(cfg), pd)},
                keys[4], n_tail)
        params["shared"] = _init_dense_block(keys[3], cfg, False)
        r = cfg.shared_lora_rank
        if r:
            hq = cfg.n_heads * cfg.resolved_head_dim
            ka, kb = jax.random.split(keys[5])
            params["lora"] = {
                "lora_a": (jax.random.normal(ka, (n_inv, cfg.d_model, r))
                           * cfg.d_model ** -0.5).astype(pd),
                "lora_b_q": jnp.zeros((n_inv, r, hq), pd),
            }
    elif fam == "xlstm":
        xc = xlstm_cfg(cfg)
        p = xc.slstm_every
        assert cfg.n_layers % p == 0
        n_groups = cfg.n_layers // p
        params["mlstm"] = C.stacked_init(
            lambda k: {"ln": C.norm_init(cfg),
                       "cell": XL.init_mlstm(k, xc, pd)},
            keys[2], n_groups * (p - 1))
        params["slstm"] = C.stacked_init(
            lambda k: {"ln": C.norm_init(cfg),
                       "cell": XL.init_slstm(k, xc, pd)},
            keys[3], n_groups)
    else:
        raise ValueError(f"lm.py does not build family {fam!r}")
    return params


# --------------------------------------------------------- dense/moe fwd --

def _block_fwd(blk, x, cfg: ModelConfig, positions, window, aux,
               cache=None, lora=None, kv_pad_to: int = 0):
    """One transformer block (train/prefill). Returns (x, aux, kv or None).

    ``kv_pad_to``: prefill passes the cache width so the softmax reduces at
    the same fixed width as chunked prefill (bitwise parity, DESIGN.md §9);
    training leaves it 0."""
    h = C.norm_apply(cfg, blk["ln1"], x)
    acfg = C.attn_cfg(cfg, window=window)
    attn_params = blk["attn"]
    if lora is not None:
        attn_params = dict(attn_params)
        attn_params["wq"] = attn_params["wq"] + (
            lora["lora_a"] @ lora["lora_b_q"]).astype(attn_params["wq"].dtype)
    h, kv = A.attend(attn_params, h, acfg, positions,
                     q_chunk=cfg.attn_chunk, kv_chunk=cfg.attn_chunk,
                     return_kv=True, kv_pad_to=kv_pad_to)
    if cfg.post_block_norm:
        h = C.norm_apply(cfg, blk["ln1_post"], h)
    x = R.shard_activations(x + h, sp=cfg.sp_activations)
    h = C.norm_apply(cfg, blk["ln2"], x)
    if "moe" in blk:
        h, a = moe_apply(blk["moe"], h, moe_cfg(cfg))
        aux = aux + a
    else:
        h = mlp_apply(blk["mlp"], h, _mlp_sparse_cfg(cfg))
    if cfg.post_block_norm:
        h = C.norm_apply(cfg, blk["ln2_post"], h)
    x = R.shard_activations(x + h, sp=cfg.sp_activations)
    return x, aux, kv


def _block_decode(blk, x, cfg: ModelConfig, cache, cache_len, window, alpha,
                  lora=None, collect_stats: bool = False, block_table=None,
                  layer=None):
    """One transformer block, single-token decode with KV cache.

    Returns ``(x, cache, stats)``; ``stats`` is the MLP telemetry pytree
    (``SM.MLP_STAT_KEYS`` scalars) when ``collect_stats`` else ``None``.
    MoE blocks report zero stats (expert routing is its own control loop).
    ``block_table`` switches the attention onto the paged KV pool (``cache``
    is then this layer's pool leaves, DESIGN.md §10).  ``layer``: the MLP
    node holds the layer-stacked matrices and this traced index picks the
    layer's rows (``layers.mlp.mlp_apply``).
    """
    from repro.core import sparse_mlp as SM
    with jax.named_scope("attention"):
        h = C.norm_apply(cfg, blk["ln1"], x)
        acfg = C.attn_cfg(cfg, window=window)
        attn_params = blk["attn"]
        if lora is not None:
            attn_params = dict(attn_params)
            attn_params["wq"] = attn_params["wq"] + (
                lora["lora_a"] @ lora["lora_b_q"]).astype(
                    attn_params["wq"].dtype)
        if block_table is not None:
            h, cache = A.paged_decode_attend(attn_params, h, acfg, cache,
                                             cache_len, block_table)
        else:
            h, cache = A.decode_attend(attn_params, h, acfg, cache,
                                       cache_len)
        if cfg.post_block_norm:
            h = C.norm_apply(cfg, blk["ln1_post"], h)
    x = x + h
    stats = None
    with jax.named_scope("mlp"):
        h = C.norm_apply(cfg, blk["ln2"], x)
        if "moe" in blk:
            h, _ = moe_apply(blk["moe"], h, moe_cfg(cfg))
            if collect_stats:
                # tp_shards keeps the pytree structure aligned with sharded
                # sparse layers' stats (they carry the per-shard rider key)
                stats = SM.zero_mlp_stats((x.shape[0],),
                                          cfg.sparse.tp_shards)
        elif collect_stats:
            h, stats = mlp_apply(blk["mlp"], h, _mlp_sparse_cfg(cfg),
                                 decode=True, alpha=alpha, return_stats=True,
                                 layer=layer)
        else:
            h = mlp_apply(blk["mlp"], h, _mlp_sparse_cfg(cfg), decode=True,
                          alpha=alpha, layer=layer)
        if cfg.post_block_norm:
            h = C.norm_apply(cfg, blk["ln2_post"], h)
    return x + h, cache, stats


def _chunk_stat_mean(a, tok_mask):
    """Reduce one chunk's per-token MLP telemetry to per-slot (B, ...):
    strategy stats arrive per flattened token (B*S, ...) from the sparse
    paths or (B, S, ...) from dense; mask-weighted mean over the chunk's
    REAL prompt positions only (pad tokens carry dead-alpha'd garbage).
    Shared by every family's chunked prefill (lm / vision_lm / encdec)."""
    b, s = tok_mask.shape
    if a.shape[0] == b * s:
        a = a.reshape((b, s) + a.shape[1:])
    wm = tok_mask.astype(jnp.float32)
    wm = wm.reshape(wm.shape + (1,) * (a.ndim - 2))
    return (a * wm).sum(axis=1) / jnp.maximum(wm.sum(axis=1), 1.0)


def _block_chunk_fwd(blk, x, cfg: ModelConfig, cache, offset, valid, window,
                     alpha, tok_mask, collect_stats: bool = False):
    """One transformer block over a fixed-size prefill chunk, writing K/V
    into the decode cache at ``offset``.  Mirrors ``_block_fwd`` numerics
    (same residual sharding) so the dense chunked path is bitwise-identical
    to monolithic prefill, and ``_block_decode``'s cache/telemetry contract.

    ``tok_mask``: (B, S) — True on real prompt positions.  Pad tokens enter
    the sparse union with ``DEAD_SLOT_ALPHA`` (all-sparse prediction, out of
    the union — the same drain mechanism the scheduler uses for dead slots)
    and are excluded from the telemetry reduction.
    """
    from repro.core import sparse_mlp as SM
    b, s = x.shape[0], x.shape[1]
    with jax.named_scope("attention"):
        h = C.norm_apply(cfg, blk["ln1"], x)
        acfg = C.attn_cfg(cfg, window=window)
        h, cache = A.chunk_attend(blk["attn"], h, acfg, cache, offset, valid,
                                  q_chunk=cfg.attn_chunk,
                                  kv_chunk=cfg.attn_chunk)
        if cfg.post_block_norm:
            h = C.norm_apply(cfg, blk["ln1_post"], h)
    x = R.shard_activations(x + h, sp=cfg.sp_activations)
    wmean = lambda a: _chunk_stat_mean(a, tok_mask)
    stats = None
    with jax.named_scope("mlp"):
        h = C.norm_apply(cfg, blk["ln2"], x)
        if "moe" in blk:
            h, _ = moe_apply(blk["moe"], h, moe_cfg(cfg))
            if collect_stats:
                stats = SM.zero_mlp_stats((b,), cfg.sparse.tp_shards)
        else:
            al = jnp.asarray(alpha, jnp.float32)
            if al.ndim == 1:                               # per-slot (B,)
                al = al[:, None]
            a_tok = jnp.where(tok_mask, al, SM.DEAD_SLOT_ALPHA).reshape(-1)
            if collect_stats:
                h, st = mlp_apply(blk["mlp"], h, _mlp_sparse_cfg(cfg),
                                  prefill=True, alpha=a_tok,
                                  return_stats=True)
                stats = jax.tree.map(wmean, st)
            else:
                h = mlp_apply(blk["mlp"], h, _mlp_sparse_cfg(cfg),
                              prefill=True, alpha=a_tok)
        if cfg.post_block_norm:
            h = C.norm_apply(cfg, blk["ln2_post"], h)
    x = R.shard_activations(x + h, sp=cfg.sp_activations)
    return x, cache, stats


def _dense_stack_chunk(params, x, cfg: ModelConfig, caches, offset, valid,
                       tok_mask, alphas=None, collect_stats: bool = False):
    """Chunked-prefill pass over the grouped layer scan (decode cache
    layout).  Same alpha plumbing as ``_dense_stack_decode``."""
    windows = _windows(cfg)
    p = len(windows)
    if alphas is None:
        alphas = jnp.asarray(_alphas(cfg))
    else:
        alphas = jnp.asarray(alphas, jnp.float32)

    def run(stacked, caches_s, alphas_s, n):
        grouped = jax.tree.map(
            lambda a: a.reshape((n // p, p) + a.shape[1:]), stacked)
        caches_g = jax.tree.map(
            lambda a: a.reshape((n // p, p) + a.shape[1:]), caches_s)
        alphas_g = alphas_s.reshape((n // p, p) + alphas_s.shape[1:])

        def body(x, xs):
            blk_g, cache_g, al = xs
            new_caches, stats = [], []
            for j in range(p):
                blk = jax.tree.map(lambda a: a[j], blk_g)
                cache = jax.tree.map(lambda a: a[j], cache_g)
                x, cache, st = _block_chunk_fwd(
                    blk, x, cfg, cache, offset, valid, windows[j], al[j],
                    tok_mask, collect_stats=collect_stats)
                new_caches.append(cache)
                if collect_stats:
                    stats.append(st)
            ys = (jax.tree.map(lambda *ls: jnp.stack(ls), *new_caches),
                  (jax.tree.map(lambda *ls: jnp.stack(ls), *stats)
                   if collect_stats else None))
            return x, ys

        x2, (new_caches, stats) = jax.lax.scan(
            body, x, (grouped, caches_g, alphas_g))
        new_caches = jax.tree.map(
            lambda a: a.reshape((n,) + a.shape[2:]), new_caches)
        if collect_stats:
            stats = jax.tree.map(
                lambda a: a.reshape((n,) + a.shape[2:]), stats)
        return x2, new_caches, stats

    new = {}
    all_stats = []
    nf = cfg.first_dense_layers
    if "first_blocks" in params:
        x, new["first"], st = run(params["first_blocks"], caches["first"],
                                  alphas[:nf], nf)
        all_stats.append(st)
    x, new["blocks"], st = run(params["blocks"], caches["blocks"], alphas[nf:],
                               cfg.n_layers - nf)
    all_stats.append(st)
    if collect_stats:
        stats = jax.tree.map(lambda *ls: jnp.concatenate(ls), *all_stats)
        return x, new, stats
    return x, new, None


def _dense_stack_fwd(params, x, cfg: ModelConfig, positions,
                     collect_kv: bool, max_len: int = 0):
    windows = _windows(cfg)
    p = len(windows)
    aux0 = jnp.zeros((), jnp.float32)

    def apply_seq(x, aux, stacked, n):
        grouped = jax.tree.map(
            lambda a: a.reshape((n // p, p) + a.shape[1:]), stacked)

        def body(carry, xs):
            x, aux = carry
            kvs = []
            for j in range(p):
                blk = jax.tree.map(lambda a: a[j], xs)
                x, aux, kv = _block_fwd(blk, x, cfg, positions, windows[j],
                                        aux,
                                        kv_pad_to=max_len if collect_kv
                                        else 0)
                if collect_kv:
                    kvs.append(_seed_cache(kv, max_len, cfg))
            ys = jax.tree.map(lambda *ls: jnp.stack(ls), *kvs) if collect_kv \
                else None
            return (x, aux), ys

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux), caches = jax.lax.scan(body, (x, aux), grouped)
        if collect_kv:
            # (n_groups, p, ...) -> flat (n, ...) per-layer stacking
            caches = jax.tree.map(
                lambda a: a.reshape((n,) + a.shape[2:]), caches)
        return x, aux, caches

    caches = {}
    aux = aux0
    if "first_blocks" in params:
        x, aux, c0 = apply_seq(x, aux, params["first_blocks"],
                               cfg.first_dense_layers)
        caches["first"] = c0
    x, aux, c1 = apply_seq(x, aux, params["blocks"],
                           cfg.n_layers - cfg.first_dense_layers)
    caches["blocks"] = c1
    return x, aux, caches if collect_kv else None


def _shard_cache_tree(cache: dict, seq_shard: bool) -> dict:
    return {kk: (R.shard_kv_cache(vv, seq_shard) if kk in ("k", "v")
                 else R.shard_kv_scale(vv, seq_shard))
            for kk, vv in cache.items()}


def _seed_cache(kv, max_len, cfg: ModelConfig):
    k, v = kv
    b, s = k.shape[0], k.shape[1]
    dt = jnp.dtype(cfg.kv_cache_dtype)
    cache = A.init_kv_cache(b, max_len, C.attn_cfg(cfg), dt)
    cache = A.update_kv_cache(cache, k, v, jnp.int32(0))
    return _shard_cache_tree(cache, cfg.seq_shard_kv)


def _dense_stack_decode(params, x, cfg: ModelConfig, caches, cache_len,
                        alphas=None, collect_stats: bool = False,
                        block_table=None):
    """``alphas``: optional traced override of the static schedule — either
    (n_layers,) per-layer or (n_layers, B) per-layer-per-slot (SLA tiers,
    DESIGN.md §5).  The serve-path controller's adapted values enter here
    without retracing (the static path embeds them as constants).
    ``block_table`` (B, nbps): paged-KV mode — ``caches`` leaves are then
    layer-stacked pool blocks (L, N, block, K, hd) instead of per-slot
    dense buffers (DESIGN.md §10); the table is closed over by the scan
    body (shared by every layer).

    Where the MLP's row groups can be gathered straight out of the
    layer-stacked matrices (``SM.gathers_from_stack``), those matrices are
    closed over whole and ``xs`` carries the layer index instead: a scan
    slice of a whole matrix would copy it each step, where the gather
    reads only the selected rows."""
    from repro.core import sparse_mlp as SM
    windows = _windows(cfg)
    p = len(windows)
    if alphas is None:
        alphas = jnp.asarray(_alphas(cfg))
    else:
        alphas = jnp.asarray(alphas, jnp.float32)

    def run(stacked, caches_s, alphas_s, n):
        mlp_stack, layers_g = {}, None
        if ("mlp" in stacked
                and SM.gathers_from_stack(_mlp_sparse_cfg(cfg),
                                          stacked["mlp"])):
            mlp = dict(stacked["mlp"])
            mlp_stack = {kk: mlp.pop(kk) for kk in SM.STACKED_MATRICES
                         if kk in mlp}
            stacked = dict(stacked, mlp=mlp)
            layers_g = jnp.arange(n, dtype=jnp.int32).reshape(n // p, p)
        grouped = jax.tree.map(
            lambda a: a.reshape((n // p, p) + a.shape[1:]), stacked)
        caches_g = jax.tree.map(
            lambda a: a.reshape((n // p, p) + a.shape[1:]), caches_s)
        alphas_g = alphas_s.reshape((n // p, p) + alphas_s.shape[1:])

        def body(x, xs):
            blk_g, cache_g, al, lyr = xs
            new_caches, stats = [], []
            for j in range(p):
                blk = jax.tree.map(lambda a: a[j], blk_g)
                cache = jax.tree.map(lambda a: a[j], cache_g)
                layer = None
                if mlp_stack:
                    blk["mlp"] = dict(blk["mlp"], **mlp_stack)
                    layer = lyr[j]
                x, cache, st = _block_decode(blk, x, cfg, cache, cache_len,
                                             windows[j], al[j],
                                             collect_stats=collect_stats,
                                             block_table=block_table,
                                             layer=layer)
                new_caches.append(cache)
                if collect_stats:
                    stats.append(st)
            ys = (jax.tree.map(lambda *ls: jnp.stack(ls), *new_caches),
                  (jax.tree.map(lambda *ls: jnp.stack(ls), *stats)
                   if collect_stats else None))
            return x, ys

        x2, (new_caches, stats) = jax.lax.scan(
            body, x, (grouped, caches_g, alphas_g, layers_g))
        new_caches = jax.tree.map(
            lambda a: a.reshape((n,) + a.shape[2:]), new_caches)
        if collect_stats:  # (n/p, p, B) -> (n, B) per layer
            stats = jax.tree.map(
                lambda a: a.reshape((n,) + a.shape[2:]), stats)
        return x2, new_caches, stats

    new = {}
    all_stats = []
    nf = cfg.first_dense_layers
    if "first_blocks" in params:
        x, new["first"], st = run(params["first_blocks"], caches["first"],
                                  alphas[:nf], nf)
        all_stats.append(st)
    x, new["blocks"], st = run(params["blocks"], caches["blocks"], alphas[nf:],
                               cfg.n_layers - nf)
    all_stats.append(st)
    if collect_stats:
        stats = jax.tree.map(lambda *ls: jnp.concatenate(ls), *all_stats)
        return x, new, stats
    return x, new, None


# ------------------------------------------------------------ hybrid fwd --

def _hybrid_fwd(params, x, cfg: ModelConfig, positions, collect_state: bool,
                max_len: int = 0):
    mc = mamba_cfg(cfg)
    n_inv, n_main, n_tail = _hybrid_layout(cfg)
    ae = cfg.attn_every
    aux = jnp.zeros((), jnp.float32)

    # per-BLOCK remat (not per-group): only one mamba layer's chunk-boundary
    # SSD states are live during backward (DESIGN.md memory budget)
    def attn_block(x, aux, lora_g):
        return _block_fwd(params["shared"], x, cfg, positions, 0, aux,
                          lora=lora_g)

    def mamba_block(blk, xa):
        h = C.norm_apply(cfg, blk["ln"], xa)
        if collect_state:
            h, st = M2.mamba2_forward(blk["mixer"], h, mc, return_state=True)
        else:
            h = M2.mamba2_forward(blk["mixer"], h, mc)
            st = None
        return R.shard_activations(xa + h, sp=cfg.sp_activations), st

    if cfg.remat:
        attn_block = jax.checkpoint(attn_block, prevent_cse=False)
        mamba_block = jax.checkpoint(mamba_block, prevent_cse=False,
                                     static_argnums=())

    def group_body(carry, xs):
        x, aux = carry
        mamba_g, lora_g = xs
        xa, aux, kv = attn_block(x, aux, lora_g)
        states = []
        for j in range(ae):
            blk = jax.tree.map(lambda a: a[j], mamba_g)
            xa, st = mamba_block(blk, xa)
            if collect_state:
                states.append(st)
        ys = (jax.tree.map(lambda *ls: jnp.stack(ls), *states)
              if collect_state else None,
              _seed_cache(kv, max_len, cfg) if collect_state else None)
        return (xa, aux), ys

    grouped = jax.tree.map(
        lambda a: a.reshape((n_inv, ae) + a.shape[1:]), params["mamba"])
    lora = params.get("lora")
    if lora is None:
        lora = {"lora_a": jnp.zeros((n_inv, 1, 1), x.dtype),
                "lora_b_q": jnp.zeros((n_inv, 1, cfg.n_heads *
                                       cfg.resolved_head_dim), x.dtype)}
    (x, aux), (m_states, kv_caches) = jax.lax.scan(group_body, (x, aux),
                                                   (grouped, lora))
    tail_states = []
    if n_tail:
        for j in range(n_tail):
            blk = jax.tree.map(lambda a: a[j], params["mamba_tail"])
            h = C.norm_apply(cfg, blk["ln"], x)
            if collect_state:
                h, st = M2.mamba2_forward(blk["mixer"], h, mc,
                                          return_state=True)
                tail_states.append(st)
            else:
                h = M2.mamba2_forward(blk["mixer"], h, mc)
            x = R.shard_activations(x + h, sp=cfg.sp_activations)
    caches = None
    if collect_state:
        caches = {"mamba": m_states, "attn": kv_caches}
        if tail_states:
            caches["tail"] = jax.tree.map(lambda *ls: jnp.stack(ls),
                                          *tail_states)
    return x, aux, caches


def _hybrid_decode(params, x, cfg: ModelConfig, caches, cache_len,
                   alphas=None, collect_stats: bool = False):
    mc = mamba_cfg(cfg)
    n_inv, n_main, n_tail = _hybrid_layout(cfg)
    ae = cfg.attn_every
    if alphas is None:
        alphas = jnp.asarray(_alphas(cfg))
    else:
        alphas = jnp.asarray(alphas, jnp.float32)

    grouped = jax.tree.map(
        lambda a: a.reshape((n_inv, ae) + a.shape[1:]), params["mamba"])
    lora = params.get("lora")
    if lora is None:
        lora = {"lora_a": jnp.zeros((n_inv, 1, 1), x.dtype),
                "lora_b_q": jnp.zeros((n_inv, 1, cfg.n_heads *
                                       cfg.resolved_head_dim), x.dtype)}

    def body(x, xs):
        mamba_g, lora_g, m_state_g, kv_cache, al = xs
        x, kv_cache, mlp_st = _block_decode(params["shared"], x, cfg,
                                            kv_cache, cache_len, 0, al,
                                            lora=lora_g,
                                            collect_stats=collect_stats)
        new_states = []
        for j in range(ae):
            blk = jax.tree.map(lambda a: a[j], mamba_g)
            st = jax.tree.map(lambda a: a[j], m_state_g)
            h = C.norm_apply(cfg, blk["ln"], x)
            h, st = M2.mamba2_decode(blk["mixer"], h, M2.Mamba2State(*st), mc)
            x = x + h
            new_states.append(st)
        return x, (jax.tree.map(lambda *ls: jnp.stack(ls), *new_states),
                   kv_cache, mlp_st)

    al_g = alphas[:n_inv]
    x, (m_states, kv_caches, mlp_stats) = jax.lax.scan(
        body, x, (grouped, lora, caches["mamba"], caches["attn"], al_g))
    new = {"mamba": m_states, "attn": kv_caches}
    if n_tail:
        sts = []
        for j in range(n_tail):
            blk = jax.tree.map(lambda a: a[j], params["mamba_tail"])
            st = jax.tree.map(lambda a: a[j], caches["tail"])
            h = C.norm_apply(cfg, blk["ln"], x)
            h, st = M2.mamba2_decode(blk["mixer"], h, M2.Mamba2State(*st), mc)
            x = x + h
            sts.append(st)
        new["tail"] = jax.tree.map(lambda *ls: jnp.stack(ls), *sts)
    return x, new, mlp_stats if collect_stats else None


# ------------------------------------------------------------- xlstm fwd --

def _xlstm_fwd(params, x, cfg: ModelConfig, collect_state: bool):
    xc = xlstm_cfg(cfg)
    p = xc.slstm_every
    n_groups = cfg.n_layers // p
    m_grouped = jax.tree.map(
        lambda a: a.reshape((n_groups, p - 1) + a.shape[1:]), params["mlstm"])

    # per-BLOCK remat: one mLSTM's sqrt-BPTT boundary states live at a time
    def m_block(blk, x):
        h = C.norm_apply(cfg, blk["ln"], x)
        if collect_state:
            h, st = XL.mlstm_forward(blk["cell"], h, xc, return_state=True)
        else:
            h = XL.mlstm_forward(blk["cell"], h, xc)
            st = None
        return R.shard_activations(x + h, sp=cfg.sp_activations), st

    def s_block(blk, x):
        h = C.norm_apply(cfg, blk["ln"], x)
        if collect_state:
            h, st = XL.slstm_forward(blk["cell"], h, xc, return_state=True)
        else:
            h = XL.slstm_forward(blk["cell"], h, xc)
            st = None
        return R.shard_activations(x + h, sp=cfg.sp_activations), st

    if cfg.remat:
        m_block = jax.checkpoint(m_block, prevent_cse=False)
        s_block = jax.checkpoint(s_block, prevent_cse=False)

    def body(x, xs):
        m_g, s_blk = xs
        m_states, s_state = [], None
        # pattern: [mlstm, slstm, mlstm, ...]: slstm at position 1
        mi = 0
        for tag in ["m0", "s", *[f"m{j}" for j in range(1, p - 1)]]:
            if tag == "s":
                x, s_state = s_block(s_blk, x)
            else:
                blk = jax.tree.map(lambda a: a[mi], m_g)
                x, st = m_block(blk, x)
                m_states.append(st)
                mi += 1
        ys = ((jax.tree.map(lambda *ls: jnp.stack(ls), *m_states),
               s_state) if collect_state else None)
        return x, ys

    x, states = jax.lax.scan(body, x, (m_grouped, params["slstm"]))
    caches = None
    if collect_state:
        caches = {"mlstm": states[0], "slstm": states[1]}
    return x, jnp.zeros((), jnp.float32), caches


def _xlstm_decode(params, x, cfg: ModelConfig, caches):
    xc = xlstm_cfg(cfg)
    p = xc.slstm_every
    n_groups = cfg.n_layers // p
    m_grouped = jax.tree.map(
        lambda a: a.reshape((n_groups, p - 1) + a.shape[1:]), params["mlstm"])

    def body(x, xs):
        m_g, s_blk, m_st_g, s_st = xs
        new_m, new_s = [], None
        order = ["m0", "s", *[f"m{j}" for j in range(1, p - 1)]]
        mi = 0
        for tag in order:
            if tag == "s":
                h = C.norm_apply(cfg, s_blk["ln"], x)
                h, st = XL.slstm_decode(s_blk["cell"], h, XL.SLSTMState(*s_st),
                                        xc)
                new_s = st
            else:
                blk = jax.tree.map(lambda a: a[mi], m_g)
                st = jax.tree.map(lambda a: a[mi], m_st_g)
                h = C.norm_apply(cfg, blk["ln"], x)
                h, st = XL.mlstm_decode(blk["cell"], h, XL.MLSTMState(*st), xc)
                new_m.append(st)
                mi += 1
            x = x + h
        return x, (jax.tree.map(lambda *ls: jnp.stack(ls), *new_m), new_s)

    x, (m_states, s_states) = jax.lax.scan(
        body, x, (m_grouped, params["slstm"], caches["mlstm"],
                  caches["slstm"]))
    return x, {"mlstm": m_states, "slstm": s_states}


# ----------------------------------------------------------- public API --

def _embed_in(params, cfg: ModelConfig, tokens):
    dt = C.compute_dtype(cfg)
    x = E.embed(params["embed"], tokens, cfg.embed_scale, dt)
    return R.shard_activations(x, sp=False)


def _head_table(params):
    return params.get("unembed", params["embed"])["table"]


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            positions: Optional[jax.Array] = None):
    """Teacher-forcing forward to final hidden states. tokens: (B, S)."""
    tokens = R.shard_tokens(tokens)
    x = _embed_in(params, cfg, tokens)
    if positions is None:
        positions = jnp.arange(tokens.shape[1])
    if cfg.family in ("dense", "moe"):
        x, aux, _ = _dense_stack_fwd(params, x, cfg, positions, False)
    elif cfg.family == "hybrid":
        x, aux, _ = _hybrid_fwd(params, x, cfg, positions, False)
    elif cfg.family == "xlstm":
        x, aux, _ = _xlstm_fwd(params, x, cfg, False)
    else:
        raise ValueError(cfg.family)
    x = C.norm_apply(cfg, params["final_norm"], x)
    return x, aux


def lm_loss(params: dict, cfg: ModelConfig, batch: dict):
    """batch: {'tokens': (B,S), 'labels': (B,S)} -> (loss, metrics)."""
    hidden, aux = forward(params, cfg, batch["tokens"])
    loss = C.chunked_xent(hidden, batch["labels"], _head_table(params),
                          cfg.final_softcap, cfg.loss_chunk)
    return loss + aux, {"xent": loss, "aux": aux}


def prefill(params: dict, cfg: ModelConfig, tokens: jax.Array, max_len: int):
    """Prompt pass building decode caches. Returns (last_hidden, caches)."""
    tokens = R.shard_tokens(tokens)
    x = _embed_in(params, cfg, tokens)
    positions = jnp.arange(tokens.shape[1])
    if cfg.family in ("dense", "moe"):
        x, _, caches = _dense_stack_fwd(params, x, cfg, positions, True,
                                        max_len)
    elif cfg.family == "hybrid":
        x, _, caches = _hybrid_fwd(params, x, cfg, positions, True, max_len)
    elif cfg.family == "xlstm":
        x, _, caches = _xlstm_fwd(params, x, cfg, True)
    else:
        raise ValueError(cfg.family)
    x = C.norm_apply(cfg, params["final_norm"], x)
    logits = C.head_logits(x[:, -1], _head_table(params), cfg.final_softcap)
    return logits, caches


# Families the scheduler may stream through prefill_chunk (hybrid/xlstm
# recurrent state has no offset splice; they stay on monolithic prefill).
CHUNK_PREFILL_FAMILIES = ("dense", "moe")

# Families whose caches are pure per-layer KV and can live in the paged
# block pool (DESIGN.md §10); hybrid/xlstm recurrent state has no block
# layout and keeps dense per-slot buffers.
PAGED_KV_FAMILIES = ("dense", "moe")


def init_kv_pool(cfg: ModelConfig, n_blocks: int, block_size: int) -> dict:
    """Zero paged-KV block pool: the ``init_caches`` tree with every KV
    leaf's (batch, max_len) dims replaced by (n_blocks, block_size) —
    leaves (L, N, block, K, hd) (+ (L, N, block, K) int8 scales), shared by
    every slot through per-slot block tables (DESIGN.md §10)."""
    if cfg.family not in PAGED_KV_FAMILIES:
        raise NotImplementedError(
            f"paged KV pool supports {PAGED_KV_FAMILIES}, not "
            f"{cfg.family!r}")
    tpl = init_caches(cfg, 1, block_size)
    return jax.tree.map(
        lambda a: jnp.zeros((a.shape[0], n_blocks) + a.shape[2:], a.dtype),
        tpl)


def prefill_chunk(params: dict, cfg: ModelConfig, tokens: jax.Array,
                  caches: dict, offset: jax.Array, valid: jax.Array, *,
                  alphas=None, collect_stats: bool = False):
    """One fixed-size prefill chunk against decode-layout caches.

    tokens: (B, S) — a chunk of the prompt starting at sequence ``offset``
    (a traced scalar, so one executable serves every chunk of a given
    shape — the fixed chunk shape is what structurally eliminates the
    per-prompt-length trace cache, DESIGN.md §9).  ``valid`` (scalar or
    (B,)): total real prompt length; positions >= valid inside the chunk
    are padding (dead-alpha'd out of the sparse union, K/V zeroed).
    ``caches`` is the decode cache tree from ``init_caches`` — chunks must
    arrive in order from offset 0.

    Returns (logits (B, V), caches[, stats]): logits are next-token logits
    read at position ``valid - 1`` and only meaningful on the chunk that
    contains it; ``stats`` (collect_stats) is the (L, B) MLP telemetry
    pytree matching ``decode_step``'s contract, reduced over the chunk's
    real positions.

    Only dense/moe families chunk (hybrid/xlstm recurrent state doesn't
    splice at an offset); the scheduler falls back to monolithic prefill
    for those.
    """
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"chunked prefill supports dense/moe, not {cfg.family!r}")
    tokens = R.shard_tokens(tokens)
    with jax.named_scope("embed"):
        x = _embed_in(params, cfg, tokens)
    b, s = tokens.shape
    off = jnp.asarray(offset, jnp.int32)
    vld = jnp.asarray(valid, jnp.int32)
    if vld.ndim == 0:
        vld = jnp.full((b,), vld, jnp.int32)
    pos = off + jnp.arange(s, dtype=jnp.int32)
    tok_mask = pos[None, :] < vld[:, None]                    # (B, S)
    x, caches, stats = _dense_stack_chunk(params, x, cfg, caches, off, vld,
                                          tok_mask, alphas, collect_stats)
    with jax.named_scope("head"):
        x = C.norm_apply(cfg, params["final_norm"], x)
        last = jnp.clip(vld - 1 - off, 0, s - 1)              # (B,)
        xl = x[jnp.arange(b), last]
        logits = C.head_logits(xl, _head_table(params), cfg.final_softcap)
    if collect_stats:
        return logits, caches, stats
    return logits, caches


def init_caches(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Zero caches for decode-from-scratch (dry-run / serving restore)."""
    dt = jnp.dtype(cfg.dtype)
    kv_dt = jnp.dtype(cfg.kv_cache_dtype)
    if cfg.family in ("dense", "moe"):
        def kv(n):
            c = A.init_kv_cache(batch, max_len, C.attn_cfg(cfg), kv_dt)
            return _shard_cache_tree(
                {kk: jnp.zeros((n,) + a.shape, a.dtype)
                 for kk, a in c.items()}, cfg.seq_shard_kv)
        caches = {}
        if cfg.first_dense_layers:
            caches["first"] = kv(cfg.first_dense_layers)
        caches["blocks"] = kv(cfg.n_layers - cfg.first_dense_layers)
        return caches
    if cfg.family == "hybrid":
        n_inv, n_main, n_tail = _hybrid_layout(cfg)
        mc = mamba_cfg(cfg)
        st = M2.init_mamba2_state(batch, mc, dt)
        stack = lambda s, n: jax.tree.map(
            lambda a: jnp.zeros((n,) + a.shape, a.dtype), s)
        kv = A.init_kv_cache(batch, max_len, C.attn_cfg(cfg), kv_dt)
        caches = {
            "mamba": jax.tree.map(
                lambda a: jnp.zeros((n_inv, cfg.attn_every) + a.shape,
                                    a.dtype), st),
            "attn": _shard_cache_tree(
                {kk: jnp.zeros((n_inv,) + a.shape, a.dtype)
                 for kk, a in kv.items()}, cfg.seq_shard_kv),
        }
        if n_tail:
            caches["tail"] = stack(st, n_tail)
        return caches
    if cfg.family == "xlstm":
        xc = xlstm_cfg(cfg)
        p = xc.slstm_every
        n_groups = cfg.n_layers // p
        ms = XL.init_mlstm_state(batch, xc, dt)
        ss = XL.init_slstm_state(batch, xc)
        return {
            "mlstm": jax.tree.map(
                lambda a: jnp.zeros((n_groups, p - 1) + a.shape, a.dtype), ms),
            "slstm": jax.tree.map(
                lambda a: jnp.zeros((n_groups,) + a.shape, a.dtype), ss),
        }
    raise ValueError(cfg.family)


def decode_step(params: dict, cfg: ModelConfig, token: jax.Array,
                caches: dict, cache_len: jax.Array, *,
                alphas=None, collect_stats: bool = False,
                block_table=None):
    """One decode step. token: (B, 1) -> (logits (B, V), new caches).

    ``cache_len``: scalar shared length, or (B,) per-slot lengths — the
    slot-refill scheduler's layout where each batch slot holds its own
    request at its own position (DESIGN.md §5).
    ``alphas``: optional predictor-alpha override (the serve controller's
    adapted values; None keeps the static schedule and is bit-identical to
    the pre-controller path).  Shape (n_layers,) per-layer, or
    (n_layers, B) per-layer-per-slot — the SLA-tier alpha vector threads
    through every MLP strategy as a per-token alpha.  With
    ``collect_stats`` the return gains a third element: per-layer MLP
    telemetry arrays keyed by ``repro.core.sparse_mlp.MLP_STAT_KEYS``,
    shaped (L, B) (L = alpha-consuming layers: n_layers for dense/moe,
    invocation groups for hybrid, none for xlstm).  On the pallas strategy
    the telemetry is produced in-kernel per slot (realized density, actual
    gate activity, the false-negative proxy — DESIGN.md §4), so the serve
    controller needs no masked-path audit re-dispatch.

    Tensor-parallel serving (DESIGN.md §8): with ``cfg.sparse.tp_shards``
    set the sparse MLPs run the shard-local formulation — under an active
    mesh with a matching 'model' axis the whole sparse decode step executes
    under shard_map (weights row-sharded, per-shard union selection, one
    psum telemetry epilogue), and the stats gain a per-shard rider under
    ``SHARD_STAT_KEY`` shaped (L, B, tp_shards).  Results are bitwise
    identical to the single-device emulation of the same config.
    """
    with jax.named_scope("embed"):
        x = _embed_in(params, cfg, token)
    stats = None
    if block_table is not None and cfg.family not in PAGED_KV_FAMILIES:
        raise NotImplementedError(
            f"paged KV decode supports {PAGED_KV_FAMILIES}, not "
            f"{cfg.family!r} (recurrent state has no block layout)")
    if cfg.family in ("dense", "moe"):
        x, caches, stats = _dense_stack_decode(params, x, cfg, caches,
                                               cache_len, alphas,
                                               collect_stats, block_table)
    elif cfg.family == "hybrid":
        x, caches, stats = _hybrid_decode(params, x, cfg, caches, cache_len,
                                          alphas, collect_stats)
    elif cfg.family == "xlstm":
        x, caches = _xlstm_decode(params, x, cfg, caches)
    else:
        raise ValueError(cfg.family)
    with jax.named_scope("head"):
        x = C.norm_apply(cfg, params["final_norm"], x)
        logits = C.head_logits(x[:, 0], _head_table(params),
                               cfg.final_softcap)
    if collect_stats:
        return logits, caches, stats
    return logits, caches


def prepare_sparse(params: dict, sparse=None) -> dict:
    """Offline step ① for serving: pack gate-weight sign bits everywhere a
    gated MLP lives (works through stacked leading dims).

    With ``sparse.weight_dtype == "int8"`` (a ``SparseInferConfig``) the
    dense-stack MLP nodes are additionally quantized to symmetric
    per-group int8 leaves + scales (DESIGN.md §13) — sign packs still come
    from the ORIGINAL fp weights.  MoE expert nodes (recognized by their
    sibling ``router`` leaf) stay fp: the MoE dispatch reads the fp
    matrices directly and carries no sparse-MLP selection machinery."""
    quant = sparse is not None and getattr(sparse, "weight_dtype", "") == \
        "int8"
    if quant:
        from repro.core import quantize as CQ

    def rec(node):
        if isinstance(node, dict):
            out = {k: rec(v) for k, v in node.items()}
            if "wg_t" in node and "wd_t" in node:
                if quant and "router" not in node:
                    return CQ.quantize_mlp_node(
                        out, sparse.quant_group_size, sparse.group_size)
                out["sign_wg"] = CP.pack_signs(node["wg_t"])
            return out
        return node
    return rec(params)
