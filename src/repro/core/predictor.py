"""SparseInfer training-free activation-sparsity predictor (paper §IV-A).

Pure-JAX reference implementation of the sign-bit XOR/popcount predictor.
The Pallas TPU kernels in ``repro.kernels`` implement the same math; this
module is the algorithmic source of truth (and the CPU execution path).

Conventions
-----------
Weights are stored *neuron-major*: for a gated MLP ``h1 = x @ W_gate`` with
``W_gate ∈ R^{d×k}``, we hold ``wg_t = W_gate.T ∈ R^{k×d}`` so that neuron
``j`` of the hidden dimension is the contiguous row ``wg_t[j]``.  Sign bits
are packed along the ``d`` (reduction) axis into int32 words, LSB-first:
bit ``b`` of word ``i`` is ``sign(v[i*32 + b])``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

PACK = 32  # sign bits per packed word (int32)


def packed_width(d: int) -> int:
    """Number of int32 words needed to pack ``d`` sign bits."""
    return (d + PACK - 1) // PACK


@jax.jit
def pack_signs(v: jax.Array) -> jax.Array:
    """Pack sign bits of the last axis into int32 words (LSB-first).

    ``v`` may be f32/bf16/f16 or any signed int dtype. Zeros pack as
    positive (bit 0), matching ``v < 0``.  The last axis is zero-padded to a
    multiple of 32; padded lanes pack as positive bits, which the predictor
    accounts for via ``d_valid``.

    Shape: (..., d) -> (..., ceil(d/32)) int32.  Jitted, so packing a
    stacked full-size gate matrix fuses into one reduction instead of
    materializing its (..., d) uint32 bit planes.
    """
    d = v.shape[-1]
    w = packed_width(d)
    pad = w * PACK - d
    bits = (v < 0).astype(jnp.uint32)
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
    bits = bits.reshape(v.shape[:-1] + (w, PACK))
    weights = (jnp.uint32(1) << jnp.arange(PACK, dtype=jnp.uint32))
    packed = jnp.sum(bits * weights, axis=-1, dtype=jnp.uint32)
    return packed.astype(jnp.int32)


def unpack_signs(packed: jax.Array, d: int) -> jax.Array:
    """Inverse of :func:`pack_signs` -> bool array (..., d). True = negative."""
    packed = packed.astype(jnp.uint32)
    shifts = jnp.arange(PACK, dtype=jnp.uint32)
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(packed.shape[:-1] + (packed.shape[-1] * PACK,))
    return bits[..., :d].astype(jnp.bool_)


def neg_counts(packed_w: jax.Array, packed_x: jax.Array) -> jax.Array:
    """Predicted-negative-product counts per neuron.

    packed_w: (k, w) int32 — packed signs of neuron-major weights.
    packed_x: (..., w) int32 — packed signs of the input vector(s).
    Returns (..., k) int32: for each neuron j, the number of elementwise
    products ``x[i] * w[j, i]`` predicted negative (sign bits differ).
    """
    x = packed_x[..., None, :]  # (..., 1, w)
    xor = jnp.bitwise_xor(x, packed_w)  # (..., k, w)
    return jnp.sum(jax.lax.population_count(xor), axis=-1, dtype=jnp.int32)


def margins(
    packed_w: jax.Array,
    packed_x: jax.Array,
    d_valid: int,
    alpha: jax.Array | float = 1.0,
) -> jax.Array:
    """Prediction margin per neuron: ``N_neg - alpha * N_pos`` (paper eq. 2).

    Positive margin  => predicted sparse (skip).
    Non-positive     => predicted active (keep).
    ``d_valid`` is the true reduction length (padding lanes always count as
    positive products and are excluded from N_pos here).

    ``alpha`` may be a scalar or an array broadcasting against the *batch*
    dims of ``packed_x`` (e.g. per-token alphas (B,) against margins (B, k),
    or per-layer alphas under vmap-over-layers) — a trailing neuron axis is
    appended so a non-scalar alpha never silently broadcasts against ``k``.
    Returns float32 (..., k).
    """
    n_neg = neg_counts(packed_w, packed_x).astype(jnp.float32)
    n_pos = jnp.float32(d_valid) - n_neg
    a = jnp.asarray(alpha, jnp.float32)
    if a.ndim:
        a = a[..., None]
    return n_neg - a * n_pos


def predict_sparse(
    packed_w: jax.Array,
    packed_x: jax.Array,
    d_valid: int,
    alpha: jax.Array | float = 1.0,
) -> jax.Array:
    """Boolean skip mask (..., k): True = predicted sparse (skippable)."""
    return margins(packed_w, packed_x, d_valid, alpha) > 0


@dataclasses.dataclass(frozen=True)
class AlphaSchedule:
    """Per-layer conservativeness schedule (paper §IV-A / §V-B).

    The paper sets alpha slightly above 1.0 for the early (low-precision)
    layers and 1.0 for the rest; empirically 1.01–1.03 over the first half.
    """

    base: float = 1.0
    early: float = 1.03
    early_frac: float = 0.5  # paper: first 20 of 40 layers

    def alpha_for_layer(self, layer_idx: int, num_layers: int) -> float:
        cutoff = int(round(num_layers * self.early_frac))
        return self.early if layer_idx < cutoff else self.base

    def alphas(self, num_layers: int) -> np.ndarray:
        return np.asarray(
            [self.alpha_for_layer(i, num_layers) for i in range(num_layers)],
            dtype=np.float32,
        )

    def init_state(self, num_layers: int) -> np.ndarray:
        """Initial per-layer alpha vector for the online controller
        (repro.runtime.controller) — the schedule is the starting point the
        feedback loop then adapts per layer."""
        return self.alphas(num_layers).copy()


def predictor_op_count(d: int, k: int) -> int:
    """Number of 32-bit XOR(+popcount) ops per token (paper Table I)."""
    return k * packed_width(d)


def predictor_sign_bytes(d: int, k: int) -> int:
    """Bytes of packed sign storage per weight matrix (paper §V-A2)."""
    return k * packed_width(d) * 4


def mlp_macs(d: int, k: int, gated: bool = True) -> int:
    """Dense MAC count of one gated-MLP block per token (paper Table I)."""
    n_mats = 3 if gated else 2
    return n_mats * d * k
