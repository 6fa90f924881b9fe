"""The gather decode step reads row groups straight from the layer-stacked
MLP matrices (``core.sparse_mlp.gathers_from_stack``): the layer scan
closes over ``wg_t``/``wu_t``/``wd_t`` whole and gathers rows
``layer * k/g + group`` of their (L*k/g, g, d) view.

Pinned here on the CPU: ``decode_step`` gives bitwise the logits, caches
and telemetry of a reference that loops the layers in Python and hands
each block its own matrices ``w[l]`` (so ``gather_mlp`` runs on one
layer's weights, as before the stacked read).  The paths that keep a
layer's own matrices, the big-batch dense fallback and int8 weights, give
the reference's values too.  What the chip compiles is pinned in
tests/test_chip_compile.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, SparseInferConfig
from repro.core import sparse_mlp as SM
from repro.models import common as C
from repro.models import lm

B, MAX_LEN, BS = 4, 32, 8
LENGTHS = (3, 9, 17, 26)

BASE = ModelConfig(
    name="tiny-stacked", family="dense", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=256, vocab=128, max_seq=64, dtype="float32",
    param_dtype="float32", kv_cache_dtype="float32", attn_chunk=32,
    loss_chunk=64, remat=False, activation="relu",
    sparse=SparseInferConfig(enabled=True, strategy="gather",
                             activation="relu", group_size=8,
                             capacity_frac=0.25, alpha_base=1.0,
                             alpha_early=1.03))


def _cfg(gated=True, first=False, **sparse):
    return BASE.replace(gated_mlp=gated, first_dense_layers=int(first),
                        sparse=dataclasses.replace(BASE.sparse, **sparse))


def _inputs(cfg, paged):
    key = jax.random.PRNGKey(7)
    params = lm.prepare_sparse(lm.init_lm(key, cfg), cfg.sparse)
    if paged:
        nbps = MAX_LEN // BS
        caches = lm.init_kv_pool(cfg, B * nbps + 1, BS)
        # fragmented: slot b owns every B-th block, block 0 left out
        table = jnp.asarray(1 + np.arange(B * nbps, dtype=np.int32)
                            .reshape(nbps, B).T)
    else:
        caches = lm.init_caches(cfg, B, MAX_LEN)
        table = None
    leaves, tdef = jax.tree.flatten(caches)
    keys = jax.random.split(jax.random.PRNGKey(8), len(leaves))
    caches = jax.tree.unflatten(tdef, [
        jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])
    tok = jnp.asarray(np.arange(B, dtype=np.int32)[:, None] * 5 + 1)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    alphas = jnp.asarray(np.linspace(0.9, 1.1, cfg.n_layers * B,
                                     dtype=np.float32)
                         .reshape(cfg.n_layers, B))
    return params, tok, caches, lengths, alphas, table


def _per_layer_ref(params, cfg, tok, caches, lengths, alphas, table):
    """Layers in a Python loop, each block given its own ``w[l]``."""
    x = lm._embed_in(params, cfg, tok)
    windows = lm._windows(cfg)
    nf = cfg.first_dense_layers
    new, stats = {}, []
    for name, ck, lo, n in (("first_blocks", "first", 0, nf),
                            ("blocks", "blocks", nf, cfg.n_layers - nf)):
        if name not in params:
            continue
        outs = []
        for i in range(n):
            blk = jax.tree.map(lambda a: a[i], params[name])
            cache = jax.tree.map(lambda a: a[i], caches[ck])
            x, cache, st = lm._block_decode(
                blk, x, cfg, cache, lengths, windows[i % len(windows)],
                alphas[lo + i], collect_stats=True, block_table=table)
            outs.append(cache)
            stats.append(st)
        new[ck] = jax.tree.map(lambda *ls: jnp.stack(ls), *outs)
    x = C.norm_apply(cfg, params["final_norm"], x)
    logits = C.head_logits(x[:, 0], lm._head_table(params), cfg.final_softcap)
    return logits, new, jax.tree.map(lambda *ls: jnp.stack(ls), *stats)


CASES = {
    f"{'gated' if gated else 'plain'}"
    f"{'-first' if first else ''}{'-paged' if paged else ''}":
        (dict(gated=gated, first=first), paged, True)
    for gated in (True, False) for first in (False, True)
    for paged in (False, True)
}
# paths that keep reading a layer's own matrices
CASES["dense-fallback"] = (dict(sparse_max_batch=B - 1), False, True)
CASES["int8"] = (dict(weight_dtype="int8", quant_group_size=32), False,
                 False)


@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_step_matches_per_layer_reference(case):
    kw, paged, stacked = CASES[case]
    cfg = _cfg(**kw)
    params, tok, caches, lengths, alphas, table = _inputs(cfg, paged)
    assert SM.gathers_from_stack(cfg.sparse,
                                 params["blocks"]["mlp"]) == stacked

    def step(p, t, c, n, a, tab):
        return lm.decode_step(p, cfg, t, c, n, alphas=a, collect_stats=True,
                              block_table=tab)

    def ref(p, t, c, n, a, tab):
        return _per_layer_ref(p, cfg, t, c, n, a, tab)

    args = (params, tok, caches, lengths, alphas, table)
    got = jax.jit(step)(*args)
    want = jax.jit(ref)(*args)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for path, g, w in zip(
            [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(want)[0]],
            jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{case} {path}")
    # the step's gathers read the (L*k/g, g, d) view of the whole stack
    n = cfg.n_layers - cfg.first_dense_layers
    view = f"f32[1,{n * cfg.d_ff // 8},8,{cfg.d_model}]"
    gathers = stacked and "sparse_max_batch" not in kw
    assert (view in str(jax.make_jaxpr(step)(*args))) == gathers
