"""The seeded weights: the reference's one-layer rebuild equals the served
layer bit for bit, and the gate has the stated token-dependent structure."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import weights  # noqa: E402

M = {"n_layers": 3, "d_model": 512, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 64, "d_ff": 1536, "vocab": 500, "vocab_rows": 512,
     "gated_mlp": True, "qk_norm": True, "tie_embeddings": False,
     "group_size": 8}
W = {"struct_frac": 0.5, "bias_blocks": 2, "code_blocks": 6,
     "code_match": 3, "hot_group_frac": 0.19, "dc": 1.0, "cb": 2.0,
     "embed_std": 1.0, "final_gain": 0.5}
SEED = 2 ** 33 + 5


@pytest.fixture(scope="module")
def gen():
    return weights.Generator(M, W)


def test_reference_layer_is_the_served_layer(gen):
    tree = gen.program_params(SEED)
    for layer in range(M["n_layers"]):
        lw = gen.layer(SEED, layer)
        for k in ("wq", "wk", "wv", "wo"):
            src = tree["blocks"]["attn"][k][layer]
            assert src.dtype == jnp.bfloat16
            assert np.array_equal(np.asarray(src, np.float32),
                                  np.asarray(lw[k]))
        for k in ("wg_t", "wu_t", "wd_t"):
            assert np.array_equal(
                np.asarray(tree["blocks"]["mlp"][k][layer], np.float32),
                np.asarray(lw[k]))
    assert np.array_equal(np.asarray(tree["embed"]["table"], np.float32),
                          np.asarray(gen.embed(SEED), np.float32))
    assert np.array_equal(np.asarray(tree["unembed"]["table"], np.float32),
                          np.asarray(gen.head(SEED), np.float32))
    scale = np.asarray(tree["final_norm"]["scale"])
    assert np.array_equal(scale + 1.0, np.asarray(gen.final_gain()))


def test_seed_changes_the_weights(gen):
    a = gen.layer(SEED, 0)["wq"]
    b = gen.layer(SEED + 1, 0)["wq"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_a_token_fires_the_hot_groups_its_code_matches(gen):
    g = M["group_size"]
    r, n_blocks, size = weights.blocks(M, W)
    lw = gen.layer(SEED, 1)
    wg = np.asarray(lw["wg_t"]) * M["d_model"] ** 0.5
    # each group's lean on each block of R, out of the random part
    lean = np.rint(wg[:, :r].reshape(-1, g, n_blocks, size).mean((1, 3)))
    table = np.asarray(gen.embed(SEED), np.float32)
    codes = np.asarray(gen.codes(SEED))[:200]
    assert np.array_equal(np.sign(table[:200, :r:size]), codes)
    nb = W["bias_blocks"]
    hot = (lean[:, nb:] != 0).any(1)
    assert hot.sum() == weights.hot_groups(M, W)
    assert (lean[:, :nb] == -W["cb"] * W["code_match"] / nb).all()
    assert ((lean[hot, nb:] != 0).sum(1) == W["code_match"]).all()
    match = hot[None] & ((lean[None, :, nb:] == 0)
                         | (np.sign(lean[None, :, nb:])
                            == codes[:, None, nb:])).all(-1)
    x = table[:200] / np.sqrt((table[:200] ** 2).mean(-1, keepdims=True))
    fired = (x @ wg.T > 0).reshape(200, -1, g).any(-1)  # (tokens, groups)
    # a token fires the hot groups whose pattern its code matches (at this
    # small width a few others too), nearly all of them, and about 1 in
    # 2**m of the hot groups
    assert (fired & ~match).sum() <= 0.01 * fired.sum(), (fired & ~match).sum()
    assert fired.sum() >= 0.95 * match.sum()
    share = match.sum(1).mean() / hot.sum()
    assert 0.5 * 0.5 ** W["code_match"] < share < 2 * 0.5 ** W["code_match"]
    # slots differ: 16 tokens' union is several times one token's set
    union = fired[:16].any(0).sum()
    assert union > 4 * fired[:16].sum(1).mean()


def test_nothing_but_the_embedding_writes_the_code(gen):
    r = weights.blocks(M, W)[0]
    lw = gen.layer(SEED, 0)
    for k in ("wo", "wd_t"):
        assert not np.asarray(lw[k])[:, :r].any()
    for k in ("wq", "wk", "wv"):
        assert not np.asarray(lw[k])[:r].any()
    assert not np.asarray(lw["wu_t"])[:, :r].any()
    gain = np.asarray(gen.final_gain())
    assert not gain[:r].any() and (gain[r:] == W["final_gain"]).all()


def test_layout_mismatch_raises(gen):
    shapes = jax.eval_shape(lambda: gen.program_params(SEED))
    bad = jax.tree.map(lambda s: s, shapes)
    bad["blocks"]["mlp"]["wg_t"] = jax.ShapeDtypeStruct((3, 8, 8),
                                                        jnp.bfloat16)
    with pytest.raises(ValueError, match="layout"):
        gen.program_params(SEED, bad)
