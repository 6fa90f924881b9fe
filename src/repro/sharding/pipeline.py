"""GPipe-style pipeline parallelism over a 'pipe' mesh axis (optional).

The production mesh (DESIGN.md) does not need PP — (pod, data, model) covers
the assigned cells — but 1000+-node deployments of the larger archs would
add a pipe axis to cut the FSDP gather span. This module provides a real,
tested implementation: ``shard_map`` over ``pipe`` with microbatch streaming
via ``jax.lax.ppermute`` (the canonical JAX-native PP pattern).

Schedule: GPipe (fill/drain). With M microbatches over S stages the bubble
fraction is (S-1)/(M+S-1); choose M >= 4·S in practice.

Layout: layer stack split into S contiguous stages; stage s holds the
stacked params of its layers only (P('pipe') on the stage dim), so PP also
partitions parameter memory.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def stage_params(params_stacked, n_stages: int):
    """Split (n_layers, ...) stacked layer params into (S, layers/S, ...)."""
    def split(a):
        n = a.shape[0]
        assert n % n_stages == 0, (n, n_stages)
        return a.reshape((n_stages, n // n_stages) + a.shape[1:])
    return jax.tree.map(split, params_stacked)


def pipeline_apply(block_fn: Callable, stage_weights, x, *,
                   mesh: jax.sharding.Mesh, n_microbatches: int,
                   axis: str = "pipe"):
    """Run x through all stages with GPipe microbatch streaming.

    block_fn(weights_for_stage, x_mb) -> x_mb : applies ONE stage's layers.
    stage_weights: pytree with leading (S, ...) dims (use stage_params).
    x: (B, ...) global batch; B % n_microbatches == 0.

    Inside shard_map each pipe-rank loops over M + S - 1 ticks: on each tick
    it processes the microbatch it holds (or a dummy during fill/drain) and
    ppermutes activations to the next stage.  Returns x after the last
    stage, in original microbatch order.
    """
    s = mesh.shape[axis]
    m = n_microbatches
    b = x.shape[0]
    assert b % m == 0, (b, m)
    mb = b // m

    def stage_loop(weights, xg):
        # weights arrive as (1, layers/S, ...) per rank (sharded stage dim):
        # drop the local singleton. xg: full (B, ...) input (replicated over
        # pipe; only stage 0 reads it).
        weights = jax.tree.map(lambda a: a[0], weights)
        rank = jax.lax.axis_index(axis)
        xmb = xg.reshape((m, mb) + xg.shape[1:])
        n_ticks = m + s - 1
        buf = jnp.zeros((mb,) + xg.shape[1:], xg.dtype)   # in-flight mb
        out = jnp.zeros_like(xmb)                         # drained results

        def tick(carry, t):
            buf, out = carry
            # stage 0 injects microbatch t (if still filling)
            inject = xmb[jnp.clip(t, 0, m - 1)]
            buf = jnp.where(rank == 0,
                            jnp.where(t < m, inject, buf), buf)
            # every stage processes what it holds
            y = block_fn(weights, buf)
            # last stage records finished microbatch (t - (s-1))
            done_idx = t - (s - 1)
            out = jnp.where(
                (rank == s - 1) & (done_idx >= 0),
                out.at[jnp.clip(done_idx, 0, m - 1)].set(y), out)
            # stream forward: stage i -> i+1 (ring; wraparound ignored)
            y_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % s) for i in range(s)])
            return (y_next, out), None

        (buf, out), _ = jax.lax.scan(tick, (buf, out),
                                     jnp.arange(n_ticks))
        # broadcast the last stage's result to all ranks (so out_specs can
        # be replicated-over-pipe)
        out = jax.lax.psum(
            jnp.where(rank == s - 1, out, jnp.zeros_like(out)), axis)
        return out.reshape((b,) + xg.shape[1:])

    other_axes = tuple(a for a in mesh.axis_names if a != axis)
    in_specs = (jax.tree.map(lambda _: P(axis), stage_weights,
                             is_leaf=lambda a: hasattr(a, "shape")),
                P())
    fn = jax.shard_map(stage_loop, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=False)
    return fn(stage_weights, x)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
