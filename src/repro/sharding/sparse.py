"""Sharding rules for the tensor-parallel sparse decode path (DESIGN.md §8).

SparseInfer's predictor is embarrassingly shardable along the FFN hidden
dimension: sign bits are packed along ``d`` (the reduction axis), so a shard
owning rows ``[s*k/ms, (s+1)*k/ms)`` of the neuron-major weights computes its
margin slice, its group margins, its batch-union and its top-(C/ms)
selection with NO communication — only the down-projection partials and the
telemetry counters cross the ``model`` axis (runtime/distributed.py).

This module is the *placement* half of that design: partition specs and
device_put helpers for the sparse-MLP params, margin slices and the serve
path's full param tree, plus the divisibility validation the server runs
before committing to a mesh.  The *execution* half (shard_map bodies,
collective epilogues) lives in ``repro.runtime.distributed``.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.sharding import rules as R

# Neuron-major sparse-MLP leaves, all row-sharded over 'model' (the k axis
# is dim 0 after the layer-stacking dims).  The int8 leaves (DESIGN.md §13)
# follow the same rule: every quant leaf's dim 0 is proportional to k (int8
# tiles have k rows, wd scales k/qg rows), so row-sharding ms ways slices
# each leaf consistently with runtime.distributed's proportional slicer.
SPARSE_MLP_KEYS = ("wg_t", "wu_t", "wd_t", "sign_wg",
                   "wg_q", "wg_s", "wu_q", "wu_s", "wd_q", "wd_s")


def mesh_shard_count(mesh: Optional[jax.sharding.Mesh] = None) -> int:
    """Size of the tensor-parallel axis (1 without a mesh / 'model')."""
    mesh = mesh or R.current_mesh()
    if mesh is None or R.tp_axis(mesh) is None:
        return 1
    return R.axis_size(mesh, "model")


def mesh_data_count(mesh: Optional[jax.sharding.Mesh] = None) -> int:
    """Size of the 'data' axis (1 without a mesh / 'data')."""
    mesh = mesh or R.current_mesh()
    if mesh is None or "data" not in R.mesh_axes(mesh):
        return 1
    return R.axis_size(mesh, "data")


def resolve_grid(sparse, mesh, batch: int) -> tuple[int, int]:
    """Resolve the SEMANTIC (ds, ms) shard grid for serving on ``mesh``.

    The config's explicit ``dp_shards`` / ``tp_shards`` win (so the same
    semantics can be pinned across placements); unset fields default to the
    mesh's axis sizes.  The mesh axes must evenly divide the semantic
    counts (each device loops over its contiguous semantic tiles — that is
    what keeps results placement-invariant, DESIGN.md §8), and the batch
    must split evenly over the data shards."""
    ms_mesh = mesh_shard_count(mesh)
    ds_mesh = mesh_data_count(mesh)
    ms = sparse.tp_shards or ms_mesh
    ds = sparse.dp_shards or ds_mesh
    if ms % ms_mesh:
        raise ValueError(
            f"tp_shards={ms} not divisible by the mesh's 'model' axis "
            f"({ms_mesh} devices) — the mesh axis must evenly divide the "
            "semantic shard count (DESIGN.md §8)")
    if ds % ds_mesh:
        raise ValueError(
            f"dp_shards={ds} not divisible by the mesh's 'data' axis "
            f"({ds_mesh} devices) — the mesh axis must evenly divide the "
            "semantic shard count (DESIGN.md §8)")
    if batch % ds:
        raise ValueError(
            f"batch {batch} not divisible by dp_shards={ds}: every data "
            "shard owns the same number of batch slots (DESIGN.md §8)")
    return ds, ms


def validate_shardable(sparse, k: int, ms: int) -> None:
    """Fail fast before any tracing if the config cannot shard ``ms`` ways.

    Checks the row-group tiling and EVERY capacity-ladder bucket: the server
    jits one decode executable per bucket, and each needs the same static
    per-shard grid on every device."""
    if ms <= 1:
        return
    g = sparse.group_size
    if k % (ms * g):
        raise ValueError(
            f"d_ff={k} not divisible by tp_shards={ms} × group_size={g} "
            "(DESIGN.md §8)")
    if getattr(sparse, "weight_dtype", "") == "int8":
        qg = sparse.quant_group_size
        if (k // ms) % qg:
            raise ValueError(
                f"per-shard rows k/ms={k // ms} not divisible by "
                f"quant_group_size={qg} — every shard must own whole wd "
                "quant row-groups (DESIGN.md §13)")
    import dataclasses
    for capg in sparse.capacity_ladder(k):
        # shard_capacity raises with the offending bucket in the message
        dataclasses.replace(sparse, tp_shards=ms,
                            capacity_override=capg).shard_capacity(k)


# --------------------------------------------------------- param specs ----

def mlp_param_spec(name: str, shape: tuple) -> P:
    """Row-shard a sparse-MLP leaf over 'model'; leading stack dims (scan
    over layer groups) stay unsharded.  Replicated for non-MLP leaves.

    This is the shard_map in_spec the distributed MLP partitions its
    weights with (``runtime/distributed.py:shard_map_apply``); it matches
    the ``rules._PARAM_RULES`` serve-mode placement (``('tp', 'fsdp')`` on
    the same leaves), so the eager ``place_serve_params`` transfer makes
    the shard_map dispatch a no-op resharding."""
    if name not in SPARSE_MLP_KEYS or len(shape) < 2:
        return P()
    pad = len(shape) - 2
    return P(*((None,) * pad), "model", None)


def serve_param_shardings(params, mesh=None):
    """NamedShardings for the whole serve-path param tree (TP over 'model',
    replicated over data axes — ``rules`` mode='serve')."""
    mesh = mesh or R.current_mesh()
    specs = R.param_specs(params, mode="serve", mesh=mesh)
    return R.named_shardings(specs, mesh)


def _path_leaf(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def place_serve_params(params, mesh=None):
    """device_put the param tree onto the mesh with the serve specs — the
    one eager transfer the Server's mesh mode performs at construction."""
    mesh = mesh or R.current_mesh()
    if mesh is None:
        return params
    return jax.tree.map(jax.device_put, params,
                        serve_param_shardings(params, mesh))
