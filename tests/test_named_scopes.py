"""Named scopes in the model step and names on the Pallas kernels.

The lowered HLO's ``op_name`` metadata carries JAX's name stack, so a
profiler trace ties each device op to its part of the step: ``embed``;
per layer ``attention`` (with ``kv_write``) and ``mlp`` (with ``predict``,
``select`` and ``gather``); ``head``.  Each ``pallas_call`` carries a
stable ``name=``.  Scopes are metadata only: the served tokens are pinned
elsewhere (tests/test_metrics.py)."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, SparseInferConfig
from repro.kernels import ops as kops
from repro.models import lm
from repro.runtime.server import Server, ServeConfig

jax.config.update("jax_platform_name", "cpu")

CFG = ModelConfig(
    name="tiny-scopes", family="dense", n_layers=2, d_model=32, n_heads=2,
    n_kv_heads=2, d_ff=512, vocab=128, max_seq=64, dtype="float32",
    param_dtype="float32", attn_chunk=8, loss_chunk=64, remat=False,
    activation="relu",
    sparse=SparseInferConfig(enabled=True, strategy="gather",
                             activation="relu", group_size=1,
                             alpha_base=0.3, alpha_early=0.3))
B, MAX_LEN, CHUNK = 2, 32, 8
STEP_SCOPES = {"embed", "attention", "kv_write", "mlp", "head"}
SPARSE_SCOPES = {"predict", "select", "gather"}


def _params(cfg):
    return lm.init_lm(jax.random.PRNGKey(0), cfg)


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).as_text(dialect="hlo", debug_info=True)


def _op_names(text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', text)


def _scopes(text: str) -> set:
    """Every scope of the ops' ``op_name`` metadata: the name stack's
    components but the last, which names the primitive itself (a
    ``gather`` or ``select_n`` op is no scope)."""
    return {part for name in _op_names(text)
            for part in name.split("/")[:-1]}


def _decode_hlo(cfg, **kw) -> str:
    caches = lm.init_caches(cfg, B, MAX_LEN)
    tok = jnp.zeros((B, 1), jnp.int32)
    lengths = jnp.zeros((B,), jnp.int32)
    return _hlo(lambda p, t, c, n: lm.decode_step(p, cfg, t, c, n, **kw),
                _params(cfg), tok, caches, lengths)


def _chunk_hlo(cfg) -> str:
    caches = lm.init_caches(cfg, 1, MAX_LEN)
    toks = jnp.zeros((1, CHUNK), jnp.int32)
    return _hlo(lambda p, t, c: lm.prefill_chunk(p, cfg, t, c, 0, 5),
                _params(cfg), toks, caches)


def test_decode_step_scopes():
    scopes = _scopes(_decode_hlo(CFG))
    assert STEP_SCOPES | SPARSE_SCOPES <= scopes, (
        STEP_SCOPES | SPARSE_SCOPES) - scopes


@pytest.mark.parametrize("sparse_prefill", [False, True])
def test_prefill_chunk_scopes(sparse_prefill):
    cfg = CFG.replace(sparse=dataclasses.replace(
        CFG.sparse, sparse_prefill=sparse_prefill))
    scopes = _scopes(_chunk_hlo(cfg))
    assert STEP_SCOPES <= scopes, STEP_SCOPES - scopes
    # a dense chunk runs no predictor
    assert SPARSE_SCOPES & scopes == (SPARSE_SCOPES if sparse_prefill
                                      else set())


def test_scopes_nest_inside_their_layer_part():
    names = _op_names(_decode_hlo(CFG))
    for inner, outer in (("kv_write", "attention"), ("predict", "mlp"),
                         ("select", "mlp"), ("gather", "mlp")):
        paths = [n.split("/")[:-1] for n in names
                 if inner in n.split("/")[:-1]]
        assert paths, inner
        assert all(outer in p[:p.index(inner)] for p in paths), inner


def test_served_step_unembeds_in_the_head():
    srv = Server(lm, CFG, ServeConfig(batch=B, max_len=MAX_LEN),
                 _params(CFG))
    args = (srv.params, jnp.zeros((B, 1), jnp.int32),
            lm.init_caches(CFG, B, MAX_LEN), jnp.zeros((B,), jnp.int32))
    head = {n.split("/")[-1] for n in _op_names(srv.decode_fn.lower(
        *args).as_text(dialect="hlo", debug_info=True))
        if "head" in n.split("/")[:-1]}
    # final norm and unembedding (JAX lowers argmax's variadic reduce
    # with no name stack, so no scope can claim it)
    assert {"rsqrt", "dot_general"} <= head, head


def _pallas_decode_hlo() -> str:
    cfg = CFG.replace(sparse=dataclasses.replace(CFG.sparse,
                                                 strategy="pallas"))
    return _decode_hlo(cfg)


def _paged_decode_hlo() -> str:
    cfg = CFG.replace(paged_attn_kernel=True)
    bs = 8
    nbps = MAX_LEN // bs
    pool = lm.init_kv_pool(cfg, B * nbps + 1, bs)
    table = jnp.asarray(np.arange(B * nbps, dtype=np.int32).reshape(B, nbps))
    tok = jnp.zeros((B, 1), jnp.int32)
    lengths = jnp.zeros((B,), jnp.int32)
    return _hlo(lambda p, t, c, n, tab: lm.decode_step(p, cfg, t, c, n,
                                                       block_table=tab),
                _params(cfg), tok, pool, lengths, table)


def _predict_counts_hlo() -> str:
    w = jnp.zeros((64, 1), jnp.int32)
    x = jnp.zeros((B, 1), jnp.int32)
    return _hlo(lambda a, b: kops.predict_counts(a, b, interpret=True), w, x)


@pytest.mark.parametrize("kernel,lower", [
    ("predict_group_margins", _pallas_decode_hlo),
    ("fused_sparse_mlp", _pallas_decode_hlo),
    ("paged_attention", _paged_decode_hlo),
    ("paged_kv_write", _paged_decode_hlo),
    ("predict_counts", _predict_counts_hlo),
])
def test_pallas_kernels_carry_their_names(kernel, lower):
    assert kernel in _scopes(lower())
