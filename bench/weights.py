"""Seeded model weights, made by the benchmark and handed to the program.

One generator serves both sides: ``program_params`` builds the program's
parameter tree (every layer, bf16, in one jitted call on the device) and
``layer_weights`` rebuilds one layer for the reference, which must take no
weights from the program.  Both evaluate the same per-layer function on the
same keys, so the reference sees exactly the served bf16 values.

The gate carries the activation structure of a ReLU-fied model whose
active rows depend on the token (``weights`` block of a configuration
file).  The first ``struct_frac`` of the hidden coordinates ("R") hold a
code that only the embedding writes: split into ``bias_blocks`` blocks at
+dc for every token and ``code_blocks`` blocks at +dc or -dc, one seeded
bit per token and block.  No matrix writes R (wo and wd are zero there),
and none but the gate reads it (wq, wk, wv, the up projection are zero
there; the final norm's gain is zero there, so the head, tied or not, sees
tokens alone).  So every layer sees the current token's code.

A hot row group answers one pattern: ``code_match`` code blocks with a
target bit each.  Its rows lean +-cb on those blocks and -cb*m/B on the
bias blocks, so for a token whose code matches all m bits the two cancel
exactly and the random part decides (about half the rows fire); one
mismatched bit leaves 2*dc*cb per coordinate of a block against it, far
below zero, and the sign-bit predictor sees the blocks' signs disagree.
Cold groups lean on the bias blocks only and never fire.  A token then
activates the hot groups whose pattern it matches (1 in 2**m), slots of a
batch activate different groups, and their union covers most hot groups
but not all.  Plain normal weights instead give ~50% positive gates and a
served sparse MLP ~88% away from the dense one (PERF.md, Findings).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Leaf ids for fold_in: fixed forever, so a seed always gives one model.
_CODE, _EMBED, _UNEMBED, _LAYERS = 1, 2, 3, 10
_WQ, _WK, _WV, _WO, _WG, _HOT, _WU, _WD, _PICK, _BITS = (1, 2, 3, 4, 5, 6,
                                                         7, 8, 9, 11)


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (64 bits are kept)."""
    seed = int(seed) % (1 << 64)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def hot_groups(m: dict, w: dict) -> int:
    """Number of hot row groups per layer."""
    n_groups = m["d_ff"] // m["group_size"]
    return max(1, int(round(n_groups * w["hot_group_frac"])))


def blocks(m: dict, w: dict) -> tuple:
    """(coordinates in R, blocks in R, coordinates per block)."""
    n_blocks = w["bias_blocks"] + w["code_blocks"]
    r = int(round(m["d_model"] * w["struct_frac"]))
    if r % n_blocks:
        raise ValueError(f"{r} structured coordinates do not split into "
                         f"{n_blocks} blocks")
    return r, n_blocks, r // n_blocks


def _normal(key, i, shape, scale):
    return jax.random.normal(jax.random.fold_in(key, i), shape,
                             jnp.float32) * scale


def _z_mask(m: dict, w: dict):
    """1 on the token coordinates (Z), 0 on the code coordinates (R)."""
    r = blocks(m, w)[0]
    return (jnp.arange(m["d_model"]) >= r).astype(jnp.float32)


def _spread(per_block, m: dict, w: dict):
    """(..., n_blocks) -> (..., d): each block's value over its
    coordinates of R, zero on Z."""
    r, _, size = blocks(m, w)
    x = jnp.repeat(per_block, size, axis=-1)
    pad = [(0, 0)] * (x.ndim - 1) + [(0, m["d_model"] - r)]
    return jnp.pad(x, pad)


def _gate_structure(kl, m: dict, w: dict):
    """(k, d) lean of every gate row on R (see module docstring)."""
    g, nb, nc = m["group_size"], w["bias_blocks"], w["code_blocks"]
    n_groups = m["d_ff"] // g
    hot = jax.random.permutation(jax.random.fold_in(kl, _HOT),
                                 n_groups) < hot_groups(m, w)
    # each group's pattern: the code blocks of its m smallest draws, and a
    # target bit for each
    draws = jax.random.uniform(jax.random.fold_in(kl, _PICK), (n_groups, nc))
    rank = jnp.argsort(jnp.argsort(draws, axis=1), axis=1)
    picked = (rank < w["code_match"]) & hot[:, None]
    bits = jnp.where(jax.random.bernoulli(jax.random.fold_in(kl, _BITS), 0.5,
                                          (n_groups, nc)), 1.0, -1.0)
    bias = -w["cb"] * w["code_match"] / nb
    coef = jnp.concatenate([jnp.full((n_groups, nb), bias, jnp.float32),
                            jnp.where(picked, w["cb"] * bits, 0.0)], axis=1)
    return _spread(jnp.repeat(coef, g, axis=0), m, w)


def _layer(key, layer, m: dict, w: dict) -> dict:
    """One layer's matrices in float32 (not yet rounded)."""
    d, k, g = m["d_model"], m["d_ff"], m["group_size"]
    hq, hk = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    kl = jax.random.fold_in(jax.random.fold_in(key, _LAYERS), layer)
    z = _z_mask(m, w)
    reads = lambda w_in: w_in * z[:, None]        # (d, out): zero rows on R
    rows = lambda w_t: w_t * z[None, :]           # (., d): zero columns on R
    # rows a matching token fires: about half of its hot groups' rows
    fired = hot_groups(m, w) * g * 0.5 ** w["code_match"]
    out = {
        "wq": reads(_normal(kl, _WQ, (d, hq), d ** -0.5)),
        "wk": reads(_normal(kl, _WK, (d, hk), d ** -0.5)),
        "wv": reads(_normal(kl, _WV, (d, hk), d ** -0.5)),
        "wo": rows(_normal(kl, _WO, (hq, d), hq ** -0.5)),
        "wg_t": (_normal(kl, _WG, (k, d), 1.0) + _gate_structure(kl, m, w))
        * d ** -0.5,
        "wd_t": rows(_group_diff(_normal(kl, _WD, (k, d), fired ** -0.5),
                                 g)),
    }
    if m["gated_mlp"]:
        out["wu_t"] = rows(_normal(kl, _WU, (k, d), d ** -0.5))
    return out


def _group_diff(u, g: int):
    """(u_j - u_{j+1}) / sqrt(2), cyclic within each group of ``g`` rows:
    each group's rows sum to zero, so a group whose rows fire alike (ReLU
    outputs have a positive mean) adds no fixed vector to the output."""
    k, d = u.shape
    ug = u.reshape(k // g, g, d)
    return ((ug - jnp.roll(ug, -1, 1)) * 2 ** -0.5).reshape(k, d)


def token_codes(key, m: dict, w: dict):
    """(vocab_rows, bias_blocks + code_blocks) of +-1: each token's code."""
    bits = jax.random.bernoulli(jax.random.fold_in(key, _CODE), 0.5,
                                (m["vocab_rows"], w["code_blocks"]))
    return jnp.concatenate([jnp.ones((m["vocab_rows"], w["bias_blocks"]),
                                     jnp.float32),
                            jnp.where(bits, 1.0, -1.0)], axis=1)


def _embed(key, m: dict, w: dict):
    z = _normal(key, _EMBED, (m["vocab_rows"], m["d_model"]), w["embed_std"])
    return jnp.where(_z_mask(m, w)[None, :] > 0, z,
                     _spread(token_codes(key, m, w) * w["dc"], m, w))


def _unembed(key, m: dict):
    d = m["d_model"]
    return _normal(key, _UNEMBED, (m["vocab_rows"], d), d ** -0.5)


def final_gain(m: dict, w: dict):
    """(d,) gain of the final norm: ``final_gain`` on Z, 0 on R."""
    return _z_mask(m, w) * w["final_gain"]


def _bf16(x):
    return x.astype(jnp.bfloat16)


class Generator:
    """Jitted makers for one configuration (``model`` and ``weights``
    blocks of its file).  The seed enters as a key argument, so one
    compiled program serves every seed."""

    def __init__(self, m: dict, w: dict):
        self.m, self.w = m, w
        blocks(m, w)
        self._tree = jax.jit(self._tree_fn)
        self._layer = jax.jit(lambda key, l: jax.tree.map(
            lambda x: _bf16(x).astype(jnp.float32), _layer(key, l, m, w)))
        self._embed = jax.jit(lambda key: _bf16(_embed(key, m, w)))
        self._unembed = jax.jit(lambda key: _bf16(_unembed(key, m)))

    def _tree_fn(self, key):
        """The program's dense-LM parameter tree (``models/lm.py:init_lm``
        layout): weights bf16, norm gains 1 ((1 + scale) form) but the
        final one (``final_gain``)."""
        m, w = self.m, self.w
        d, hd, L = m["d_model"], m["head_dim"], m["n_layers"]
        layers = jax.lax.map(
            lambda l: jax.tree.map(_bf16, _layer(key, l, m, w)),
            jnp.arange(L, dtype=jnp.int32))
        zeros = lambda *shape: jnp.zeros(shape, jnp.float32)
        attn = {kk: layers[kk] for kk in ("wq", "wk", "wv", "wo")}
        if m["qk_norm"]:
            attn["q_norm"] = {"scale": zeros(L, hd)}
            attn["k_norm"] = {"scale": zeros(L, hd)}
        tree = {
            "embed": {"table": _bf16(_embed(key, m, w))},
            "final_norm": {"scale": final_gain(m, w) - 1.0},
            "blocks": {
                "ln1": {"scale": zeros(L, d)},
                "attn": attn,
                "ln2": {"scale": zeros(L, d)},
                "mlp": {kk: layers[kk] for kk in ("wg_t", "wu_t", "wd_t")
                        if kk in layers},
            },
        }
        if not m["tie_embeddings"]:
            tree["unembed"] = {"table": _bf16(_unembed(key, m))}
        return tree

    def program_params(self, seed: int, expected=None) -> dict:
        """Make the served weights on the device in one jitted call.

        ``expected``: the program's own parameter shapes (``jax.eval_shape``
        of its init); a tree or shape that differs raises, so a change of
        the program's layout cannot pass unnoticed."""
        key = base_key(seed)
        if expected is not None:
            mine = jax.eval_shape(self._tree_fn, key)
            a = jax.tree.map(lambda x: x.shape, mine)
            b = jax.tree.map(lambda x: x.shape, expected)
            if a != b:
                raise ValueError(f"program parameter layout differs from "
                                 f"the benchmark's: {b} != {a}")
        return self._tree(key)

    def layer(self, seed: int, layer: int) -> dict:
        """One layer's served (bf16) values as float32, for the reference."""
        return self._layer(base_key(seed), jnp.int32(layer))

    def embed(self, seed: int) -> jax.Array:
        return self._embed(base_key(seed))

    def head(self, seed: int) -> jax.Array:
        """The output projection, bf16 (tied: the embedding table)."""
        if self.m["tie_embeddings"]:
            return self.embed(seed)
        return self._unembed(base_key(seed))

    def final_gain(self) -> jax.Array:
        return final_gain(self.m, self.w)

    def codes(self, seed: int) -> jax.Array:
        """Each token's code (+-1 per block of R)."""
        return token_codes(base_key(seed), self.m, self.w)
