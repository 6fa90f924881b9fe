"""Pallas TPU kernels: XOR + popcount sparsity predictor (paper §IV-B2).

Two entry points:

``predict_counts``
    The paper's Listing-1 kernel: tile the packed sign matrix (k × d/32,
    int32) over the grid, XOR against packed input signs and
    ``population_count`` on the VPU.  Emits raw negative-product counts;
    margins are an XLA epilogue.  Kept for the standalone predictor API and
    the op-count studies.

``predict_group_margins``
    The decode predictor (DESIGN.md §2): XOR/popcount, the alpha margin
    (paper eq. 2) and the row-group min-aggregation in ONE kernel.  The
    (B, k) count matrix lives only in VMEM; the kernel writes
    selection-ready per-token per-group margins (B, k/G).  The input is
    sign-packed by ``core.predictor.pack_signs`` before the call (B·d/32
    words — the TPU compiler cannot split the lane axis into 32-bit words
    in-kernel).  Prefill chunks use the same kernel: tokens are tiled by 8,
    and taken one at a time within a tile.

Reads ``k·d/8`` bytes of packed weight signs — 16× fewer than one bf16
weight matrix — making prediction a ~6% overhead on the dense MLP's traffic
(paper Table I: 2.2e6 predictor ops vs 2.1e8 MLP MACs for 13B).

Tiling: the k axis is cut into tiles whose OUTPUT width is a multiple of
the 128-lane vreg (``G·128`` rows for the group kernel, 128 for counts) or
the whole of k, and the grid is ``cdiv`` — the last tile may overhang k,
and its out-of-range lanes are never written back.  So any ``k`` divisible
by ``G`` tiles, including k/G = 1376 (ProSparse-Llama2-7B), whose only
divisors are not lane-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128          # vreg lane width: output tiles are multiples of it
TOKEN_TILE = 8       # f32 sublanes: tokens per grid step
# Half of a v5e core's 16 MiB scoped VMEM, for what scales with the k-tile:
# the double-buffered (bk, w) int32 packed-weight tile plus ONE token's
# (bk, w) XOR and popcount temporaries (the kernels loop over the token
# tile), i.e. 16·w bytes per k row.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024
BYTES_PER_ROW_WORD = 4 * (2 + 2)


def choose_block_k(k: int, w: int, b: int, group_size: int = 1) -> int:
    """k-tile for the predictor kernels.

    The tile's output width ``bk / group_size`` must be a multiple of 128
    lanes, or the tile must be the whole of ``k``; the tile's VMEM
    (``BYTES_PER_ROW_WORD · w`` bytes per row) stays under
    ``VMEM_BUDGET_BYTES``.  The batch does not enter the budget: tokens are
    processed one at a time within a tile, so one G·128-row tile fits for
    any batch up to w = 512 words (d = 16384).  Raises ``ValueError`` when
    no such tile exists — the ``ops`` layer then runs the jnp oracle in
    interpret mode and refuses the shape on the chip.
    """
    if k <= 0 or w <= 0 or b <= 0:
        raise ValueError(f"predictor tiling needs k,w,b > 0, got "
                         f"k={k} w={w} b={b}")
    if k % group_size:
        raise ValueError(f"k={k} not divisible by group_size={group_size}")
    budget = VMEM_BUDGET_BYTES // (BYTES_PER_ROW_WORD * w)
    if k <= budget:
        return k
    unit = group_size * LANES
    bk = budget - budget % unit
    if bk < unit:
        raise ValueError(
            f"degenerate predictor tile: k={k}, w={w} words leave a k-tile "
            f"budget of {budget} rows, below one lane-aligned tile of "
            f"{unit} rows (group_size={group_size})")
    return bk


def _token_counts(px_ref, pw, t: int) -> jax.Array:
    """Negative-product counts of token ``t`` of the tile against the
    (bk, w) weight tile: (1, bk) int32.  One token at a time keeps the
    XOR intermediate at (bk, w) whatever the token tile."""
    px = px_ref[t:t + 1, :]                                   # (1, w)
    xor = jnp.bitwise_xor(px[:, None, :], pw[None, :, :])     # (1, bk, w)
    return jnp.sum(jax.lax.population_count(xor), axis=-1, dtype=jnp.int32)


def _predict_kernel(pw_ref, px_ref, out_ref):
    pw = pw_ref[...]                      # (bk, w) int32
    for t in range(px_ref.shape[0]):
        out_ref[t:t + 1, :] = _token_counts(px_ref, pw, t)


@functools.partial(jax.jit, static_argnames=("interpret", "block_k"))
def predict_counts(packed_w: jax.Array, packed_x: jax.Array, *,
                   interpret: bool = True,
                   block_k: int | None = None) -> jax.Array:
    """packed_w: (k, w) int32; packed_x: (B, w) int32 -> (B, k) int32 counts."""
    k, w = packed_w.shape
    b = packed_x.shape[0]
    bk = block_k or choose_block_k(k, w, b)
    bt = min(b, TOKEN_TILE)
    return pl.pallas_call(
        _predict_kernel,
        grid=(pl.cdiv(k, bk), pl.cdiv(b, bt)),
        in_specs=[
            pl.BlockSpec((bk, w), lambda i, t: (i, 0)),
            pl.BlockSpec((bt, w), lambda i, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((bt, bk), lambda i, t: (t, i)),
        out_shape=jax.ShapeDtypeStruct((b, k), jnp.int32),
        interpret=interpret,
        name="predict_counts",
    )(packed_w, packed_x)


def _make_group_margins_kernel(d_valid: int, group_size: int):
    """Fused XOR/popcount + alpha margin + group-min kernel.

    The margin arithmetic reproduces ``core.predictor`` bitwise (same op
    sequence in the same dtypes), so the selection downstream is
    bit-identical to the multi-dispatch path it replaces.
    """
    def kernel(px_ref, pw_ref, alpha_ref, gm_ref):
        pw = pw_ref[...]                                 # (bk, w)
        for t in range(px_ref.shape[0]):
            n_neg = _token_counts(px_ref, pw, t).astype(jnp.float32)
            a = alpha_ref[t:t + 1, :]                    # (1, 1)
            # paper eq. (2), as the exact op sequence core.predictor.margins
            # lowers to — so the compiled kernel is BITWISE identical to the
            # jitted multi-dispatch epilogue it replaces (XLA contracts the
            # mul+sub into an FMA in both; only the un-jitted eager path
            # rounds the product separately) and selections match the
            # gather strategy.
            m = n_neg - a * (jnp.float32(d_valid) - n_neg)   # (1, bk)
            bk = m.shape[1]
            gm_ref[t:t + 1, :] = m.reshape(
                1, bk // group_size, group_size).min(-1)
    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("d_valid", "group_size", "interpret", "block_k"))
def predict_group_margins(packed_w: jax.Array,
                          packed_x: jax.Array,
                          alpha: jax.Array,
                          *,
                          d_valid: int,
                          group_size: int = 8,
                          interpret: bool = True,
                          block_k: int | None = None):
    """Fused decode / prefill-chunk predictor.

    packed_w: (k, w) int32 packed gate-weight signs; packed_x: (B, w) int32
    packed input signs; alpha: (B,) per-token conservativeness.  Returns
    ``(gm, cnt)``: per-token per-group margins (B, k/G) float32 (group =
    min over members, ready for batch-union + top-C selection) and
    per-token predicted-active group counts (B,) int32.

    The grid is (k tiles, token tiles) with tokens fast, so each packed
    weight tile is fetched once and stays resident while every token tile
    of a prefill chunk passes over it.
    """
    k, w = packed_w.shape
    b = packed_x.shape[0]
    assert k % group_size == 0, (k, group_size)
    bk = block_k or choose_block_k(k, w, b, group_size)
    bt = min(b, TOKEN_TILE)
    a = jnp.reshape(alpha.astype(jnp.float32), (b, 1))
    gm = pl.pallas_call(
        _make_group_margins_kernel(d_valid, group_size),
        grid=(pl.cdiv(k, bk), pl.cdiv(b, bt)),
        in_specs=[
            pl.BlockSpec((bt, w), lambda i, t: (t, 0)),
            pl.BlockSpec((bk, w), lambda i, t: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((bt, bk // group_size), lambda i, t: (t, i)),
        out_shape=jax.ShapeDtypeStruct((b, k // group_size), jnp.float32),
        interpret=interpret,
        name="predict_group_margins",
    )(packed_x, packed_w, a)
    return gm, jnp.sum(gm <= 0, axis=-1, dtype=jnp.int32)
