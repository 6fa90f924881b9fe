"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets the fake-device count before any
jax initialization).
"""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    """Every axis Auto: the sharding rules place arrays by annotation."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 (256 chips/pod, v5e) or 2x16x16 (2 pods, 512 chips).

    Axes: 'model' = TP/EP (innermost, ICI-contiguous), 'data' = DP/FSDP,
    'pod' = cross-pod DP (DCN): only gradient reduction crosses it.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape: tuple, axes: tuple) -> jax.sharding.Mesh:
    """Arbitrary mesh for tests / elastic reconfiguration."""
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


# Hardware constants (TPU v5e target) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
DCN_BW = 6.25e9                 # bytes/s per host cross-pod (assumed)
HBM_PER_CHIP = 16 * 2**30       # v5e: 16 GiB
