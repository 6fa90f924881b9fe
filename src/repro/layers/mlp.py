"""MLP block: gated (llama-style) or plain FFN, with the SparseInfer hook.

Training / prefill use the dense path (the paper applies sparsity only in
decode, §V-C); decode dispatches to the configured SparseInfer strategy.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import sparse_mlp as SM
from repro.core.sparse_mlp import SparseInferConfig


def init_mlp(key: jax.Array, d: int, k: int, gated: bool = True,
             dtype=jnp.float32) -> dict:
    return SM.init_gated_mlp(key, d, k, dtype=dtype, gated=gated)


def mlp_apply(params: dict, x: jax.Array, cfg: SparseInferConfig,
              *, decode: bool = False, prefill: bool = False,
              alpha: jax.Array | float | None = None,
              layer_idx: int = 0, num_layers: int = 1,
              return_stats: bool = False,
              layer: Optional[jax.Array] = None):
    """x: (..., d). Dense unless (decode and cfg.enabled) or — the
    sequence-axis extension (DESIGN.md §9) — (prefill and cfg.enabled and
    cfg.sparse_prefill), where a chunk's token rows run through the same
    batch-union machinery as a decode batch.

    ``alpha`` overrides the per-layer schedule (used under scan-over-layers
    where layer_idx is traced: the schedule is precomputed into an array; the
    serve-path controller feeds its adapted per-layer alphas the same way).
    It may be a scalar or a per-token vector broadcasting against the token
    dims — the slot-refill scheduler's per-slot SLA alphas (DESIGN.md §5).
    ``return_stats`` additionally yields the strategy's telemetry, exactly
    ``SM.MLP_STAT_KEYS``, one float32 value per token (a fixed pytree that
    stacks under scan).
    ``layer``: the node's ``SM.STACKED_MATRICES`` are layer-stacked and
    ``layer`` (traced) picks this layer (``SM.gathers_from_stack``): the
    gather strategy reads its row groups from the stack, the dense paths
    the layer's own matrices.
    """
    shape = x.shape

    def finish(out):
        if return_stats:
            y, stats = out
            # contract keys, plus the sharded strategies' per-shard riders
            # (token dims + (tp_shards,)) which the DistributedController
            # pops host-side before aggregation (DESIGN.md §8)
            keys = SM.MLP_STAT_KEYS + tuple(
                k for k in SM.SHARD_RIDER_KEYS if k in stats)
            stats = {k: jnp.asarray(stats[k], jnp.float32) for k in keys}
            if cfg.tp_shards:
                # paths that bypass the sharded dispatch (the big-batch
                # dense fallback below) must still emit the riders so their
                # stats stack against sharded layers' under scan
                tok = stats["realized_density"].shape
                for rk in SM.SHARD_RIDER_KEYS:
                    if rk not in stats:
                        stats[rk] = jnp.zeros(
                            tok + (cfg.tp_shards,), jnp.float32)
            return y.reshape(shape).astype(x.dtype), stats
        return out.reshape(shape).astype(x.dtype)

    sparse = cfg.enabled and (decode or (prefill and cfg.sparse_prefill))
    if prefill and (cfg.tp_shards or cfg.dp_shards):
        # the sharded decode formulation's row layout is batch slots, not
        # chunk tokens — sparse prefill under TP/DP stays dense for now
        sparse = False
    if not sparse:
        return finish(SM.dense_mlp(SM.own_layer(params, layer), x, cfg,
                                   return_stats=return_stats))
    xf = x.reshape(-1, shape[-1])
    # union-mask regime bound is PER-DEVICE tokens (DESIGN.md §2): under a
    # mesh the global batch is sharded over the data axes; tokens are
    # grouped per shard so every device selects/gathers only its own rows
    from repro.sharding import rules as R
    mesh = R.current_mesh()
    dp = R.axis_size(mesh, R.data_axes(mesh)) if mesh is not None else 1
    n = xf.shape[0]
    # a prefill chunk is many rows; its union bound is its own knob
    max_rows = cfg.prefill_max_tokens if prefill else cfg.sparse_max_batch
    if n > max_rows * dp:
        out = SM.dense_mlp(SM.own_layer(params, layer), xf, cfg,
                           return_stats=return_stats)
    elif (cfg.strategy == "gather" and decode and n > cfg.sparse_max_batch
          and n % dp == 0 and dp > 1
          and not (cfg.tp_shards or cfg.dp_shards)):
        xg = xf.reshape(dp, n // dp, shape[-1])
        xg = R.shard(xg, R.data_axes(mesh), None, None)
        ag = 1.0 if alpha is None else alpha
        if getattr(ag, "ndim", 0) == 1:          # per-token -> per-group
            ag = ag.reshape(dp, n // dp)
        out = SM.gather_mlp(params, xg, cfg, alpha=ag,
                            return_stats=return_stats, layer=layer)
        if return_stats:
            st = {k: out[1][k].reshape(n) for k in SM.MLP_STAT_KEYS}
            out = (out[0].reshape(n, shape[-1]), st)
        else:
            out = out.reshape(n, shape[-1])
    else:
        out = SM.apply(params, xf, cfg, alpha=alpha, layer_idx=layer_idx,
                       num_layers=num_layers, return_stats=return_stats,
                       layer=layer)
    return finish(out)
