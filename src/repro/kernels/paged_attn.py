"""Paged-attention decode kernel: gather/scatter over KV-pool pages.

The serve path's paged KV pool (DESIGN.md §10) stores each layer's cache as
a global block pool ``(N, block, K, hd)`` plus per-slot block tables
``(B, nbps)``.  Two kernels cover the decode step's pool traffic:

``paged_attention``
    One grid step per batch slot.  The slot's block-table row and cache
    length arrive via scalar prefetch (``pltpu.PrefetchScalarGridSpec``) so
    the page loads are table-driven; the slot's pages are gathered into its
    dense ``(S, K, hd)`` view and a single full-width masked softmax runs —
    operation-for-operation the jnp gather path in
    ``layers/attention.py:paged_decode_attend``, which is itself bitwise
    against the dense per-slot decode (the kv_pad-to-width denominator
    argument, DESIGN.md §9/§10).  ``kernels/ref.py:paged_attention_ref`` is
    the dense oracle both are pinned against.

``paged_kv_write``
    The scatter half: one token per slot lands in pool block
    ``table[b, pos//block]`` at row ``pos % block``, in place via
    ``input_output_aliases`` (pure data movement, bitwise trivially).

The pool is VMEM-resident per grid step (fine for interpret mode and the
CPU container; a production variant would stream pages by DMA), so
``check_tiling`` bounds the resident bytes and raises ``ValueError`` for
oversized pools — the ops.py wrapper then falls back to the jnp oracle,
matching the degenerate-tiling convention of the other kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30          # matches layers/attention.py NEG_INF (mask parity)

# Resident-pool ceiling per grid step (K pages + V pages).  Generous for the
# reduced CPU configs; a pool past this must stream pages instead.
POOL_VMEM_BYTES = 64 * 1024 * 1024


def check_tiling(n_blocks: int, block: int, n_kv: int, hd: int,
                 itemsize: int, n_heads: int) -> None:
    """Raise ``ValueError`` when the kernel cannot run this shape (the ops
    wrapper falls back to the jnp oracle, like choose_block_k elsewhere)."""
    if n_blocks < 1 or block < 1:
        raise ValueError(f"degenerate pool: n_blocks={n_blocks} "
                         f"block={block}")
    if n_heads % n_kv:
        raise ValueError(f"n_heads={n_heads} not a multiple of "
                         f"n_kv_heads={n_kv}")
    resident = 2 * n_blocks * block * n_kv * hd * itemsize
    if resident > POOL_VMEM_BYTES:
        raise ValueError(
            f"pool too large for a VMEM-resident gather: {resident} bytes "
            f"> {POOL_VMEM_BYTES} (stream pages instead)")


@functools.partial(jax.jit,
                   static_argnames=("softcap", "window", "interpret"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    table: jax.Array, lengths: jax.Array, *,
                    softcap: float = 0.0, window: int = 0,
                    interpret: bool = True) -> jax.Array:
    """q (B, H, hd) × pool pages (N, block, K, hd) -> context (B, H, hd) f32.

    ``table`` (B, nbps) int32 pool-block ids per logical sequence block;
    ``lengths`` (B,) per-slot cache lengths (the new token's position —
    its K/V must already be scattered, exactly like the dense path writes
    before attending).  int8 pools take the oracle path (ops.py): the
    factored-scale epilogue stays jnp-side.
    """
    b, h, hd = q.shape
    n, bs, kvh, _ = k_pages.shape
    nbps = table.shape[1]
    s_max = nbps * bs
    rep = h // kvh

    def kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref):
        i = pl.program_id(0)
        cl = len_ref[i]
        # table-driven page gather: the slot's dense (S, K, hd) view
        kk = jnp.concatenate(
            [k_ref[pl.ds(table_ref[i, j], 1)] for j in range(nbps)], axis=0)
        vv = jnp.concatenate(
            [v_ref[pl.ds(table_ref[i, j], 1)] for j in range(nbps)], axis=0)
        kk = kk.reshape(s_max, kvh, hd)
        vv = vv.reshape(s_max, kvh, hd)
        qg = q_ref[0].reshape(kvh, rep, hd).astype(kk.dtype)
        s = jnp.einsum("krh,tkh->krt", qg, kk,
                       preferred_element_type=jnp.float32)
        # pre-fused constants: a chained (s*scale)/softcap lets the XLA
        # simplifier combine differently per graph (1-ulp drift vs the ref
        # oracle); one python-folded multiply is rewrite-proof
        if softcap > 0.0:
            s = jnp.tanh(s * ((hd ** -0.5) / softcap)) * softcap
        else:
            s = s * (hd ** -0.5)
        kvp = jnp.arange(s_max, dtype=jnp.int32)
        mask = kvp <= cl          # stale/unwritten lanes (recycled pages,
        if window > 0:            # future blocks) die here: weight exact 0.0
            mask &= (cl - kvp) < window
        s = jnp.where(mask[None, None, :], s, NEG_INF)
        m = s.max(-1)
        p = jnp.exp(s - m[..., None])
        l = p.sum(-1)
        o = jnp.einsum("krt,tkh->krh", p.astype(vv.dtype), vv,
                       preferred_element_type=jnp.float32)
        o = o / jnp.maximum(l, 1e-30)[..., None]
        o_ref[0] = o.reshape(h, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((n, bs, kvh, hd), lambda i, *_: (0, 0, 0, 0)),
            pl.BlockSpec((n, bs, kvh, hd), lambda i, *_: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda i, *_: (i, 0, 0)),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
        interpret=interpret,
        name="paged_attention",
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), q,
      k_pages, v_pages)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_write(pages: jax.Array, vals: jax.Array, blocks: jax.Array,
                   offsets: jax.Array, *, interpret: bool = True) -> jax.Array:
    """Scatter one row per slot into the pool, in place.

    pages (N, block, ...), vals (B, ...), blocks/offsets (B,) — writes
    ``pages[blocks[b], offsets[b]] = vals[b]``.  Slots aimed at a shared
    write-off block collide; the grid is sequential so the last slot wins
    (that block is never gathered for a live slot, DESIGN.md §10).
    """
    b = vals.shape[0]
    n, bs = pages.shape[:2]
    rest = pages.shape[2:]

    def kernel(blk_ref, off_ref, val_ref, page_in_ref, page_ref):
        i = pl.program_id(0)
        del page_in_ref  # aliased with page_ref (in-place update)
        page_ref[pl.ds(blk_ref[i], 1), pl.ds(off_ref[i], 1)] = (
            val_ref[:].reshape((1, 1) + rest).astype(page_ref.dtype))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1,) + rest, lambda i, *_: (i,) + (0,) * len(rest)),
            pl.BlockSpec((n, bs) + rest,
                         lambda i, *_: (0, 0) + (0,) * len(rest)),
        ],
        out_specs=pl.BlockSpec((n, bs) + rest,
                               lambda i, *_: (0, 0) + (0,) * len(rest)),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
        name="paged_kv_write",
    )(blocks.astype(jnp.int32), offsets.astype(jnp.int32),
      vals.astype(pages.dtype), pages)
