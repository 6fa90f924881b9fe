"""Compile the main-path Pallas kernels for a described TPU v5e.

The TPU compiler is installed with jax; it compiles for a chip that is
described (``topologies.get_topology_desc``) and not attached, so these
tests catch what interpret mode cannot — block shapes the chip's tiling
refuses, unsupported in-kernel ops, VMEM overruns — at the widths of
ProSparse-Llama2-7B (d=4096, k=d_ff=11008, G=8, batch 4, bucket 0.2), and
the predictor also at ProSparse-Llama2-13B's (d=5120, k=13824, batch 8).
The gather strategy's whole decode step is compiled at Qwen3-8B's MLP
widths, to pin where its row gathers read from.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library at once, and every test worker
imports every test file.  This is the only file that describes the chip.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.core.sparse_mlp import SparseInferConfig
from repro.kernels import ops
from repro.launch import compile_cache as CC
from repro.models import lm

D, K, G, B, CHUNK, QG = 4096, 11008, 8, 4, 128, 128
D13, K13, B13 = 5120, 13824, 8
CAP = SparseInferConfig(group_size=G, capacity_frac=0.2).capacity(K)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _predict(sh, rows, d=D, k=K):
    def f(pw, x, a):
        return ops.predict_group_margins(pw, x, d, a, group_size=G,
                                         interpret=False)
    return jax.jit(f).lower(sh((k, d // 32), jnp.int32),
                            sh((rows, d), jnp.bfloat16),
                            sh((rows,), jnp.float32))


def _mlp(sh, rows, stats):
    fn = ops.fused_sparse_mlp_chunk if rows > B else ops.fused_sparse_mlp

    def f(x, wg, wu, wd, sel, cnt, gm):
        return fn(x, wg, wu, wd, sel, cnt, gm if stats else None,
                  group_size=G, collect_stats=stats, interpret=False)
    w = sh((K, D), jnp.bfloat16)
    return jax.jit(f).lower(sh((rows, D), jnp.bfloat16), w, w, w,
                            sh((CAP,), jnp.int32), sh((), jnp.int32),
                            sh((rows, K // G), jnp.float32))


def _mlp_q(sh, rows):
    fn = ops.fused_sparse_mlp_chunk_q if rows > B else ops.fused_sparse_mlp_q

    def f(x, wgq, wgs, wuq, wus, wdq, wds, sel, cnt, gm):
        return fn(x, wgq, wgs, wuq, wus, wdq, wds, sel, cnt, gm,
                  group_size=G, collect_stats=True, interpret=False)
    wq, s_row = sh((K, D), jnp.int8), sh((K, D // QG), jnp.float32)
    return jax.jit(f).lower(sh((rows, D), jnp.bfloat16), wq, s_row, wq,
                            s_row, wq, sh((K // QG, D), jnp.float32),
                            sh((CAP,), jnp.int32), sh((), jnp.int32),
                            sh((rows, K // G), jnp.float32))


CASES = {
    "predict_group_margins": lambda sh: _predict(sh, B),
    "predict_group_margins_chunk": lambda sh: _predict(sh, CHUNK),
    "predict_group_margins_13b": lambda sh: _predict(sh, B13, D13, K13),
    "predict_group_margins_13b_chunk":
        lambda sh: _predict(sh, CHUNK, D13, K13),
    "fused_sparse_mlp": lambda sh: _mlp(sh, B, False),
    "fused_sparse_mlp_stats": lambda sh: _mlp(sh, B, True),
    "fused_sparse_mlp_chunk_stats": lambda sh: _mlp(sh, CHUNK, True),
    "fused_sparse_mlp_q": lambda sh: _mlp_q(sh, B),
    "fused_sparse_mlp_chunk_q": lambda sh: _mlp_q(sh, CHUNK),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_compiles_for_v5e(name, one_chip, no_compile_cache):
    """Compiled, not interpreted: the program holds a Mosaic kernel."""
    def sh(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = CASES[name](sh).compile()
    assert "tpu_custom_call" in compiled.as_text()


# Qwen3-8B's MLP (d 4096, k 12288, G 8, capacity 320 groups) under a
# two-layer gather decode step of 16 slots
QD, QK, QL, QB = 4096, 12288, 2, 16
_INSTR = re.compile(r"(%[\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\((.*)")


def _instructions(text: str) -> dict:
    """name -> (result shape, opcode, rest of line) of every array-valued
    instruction of a compiled HLO module (fused computations included)."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.search(line)
        if m:
            out[m.group(1)] = m.group(2), m.group(3), m.group(4)
    return out


def _decode_step_hlo(sh, gated: bool) -> str:
    cfg = ModelConfig(
        name="qwen3-8b-mlp", family="dense", n_layers=QL, d_model=QD,
        n_heads=32, n_kv_heads=8, head_dim=128, d_ff=QK, vocab=1024,
        max_seq=256, dtype="bfloat16", param_dtype="bfloat16",
        kv_cache_dtype="bfloat16", remat=False, activation="relu",
        gated_mlp=gated,
        sparse=SparseInferConfig(enabled=True, strategy="gather",
                                 activation="relu", group_size=G,
                                 capacity_frac=0.2))
    assert cfg.sparse.capacity(QK) == 320

    def shapes(tree):
        return jax.tree.map(lambda a: sh(a.shape, a.dtype), tree)
    params = shapes(jax.eval_shape(lambda: lm.prepare_sparse(
        lm.init_lm(jax.random.PRNGKey(0), cfg), cfg.sparse)))
    caches = shapes(jax.eval_shape(lambda: lm.init_caches(cfg, QB, 256)))

    def step(p, tok, c, n, a):
        return lm.decode_step(p, cfg, tok, c, n, alphas=a)
    return jax.jit(step).lower(
        params, sh((QB, 1), jnp.int32), caches, sh((QB,), jnp.int32),
        sh((QL, QB), jnp.float32)).compile().as_text()


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "plain"])
def test_gather_decode_reads_rows_from_the_stack(gated, one_chip,
                                                 no_compile_cache):
    """No op copies a layer's whole grouped MLP matrix out of the stack;
    every row gather reads the (L*k/G, G, d) view of the stacked
    parameter, which nothing but the parameter itself produces."""
    def sh(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    instrs = _instructions(_decode_step_hlo(sh, gated))
    layer_view = re.compile(rf"\[(\d+,)*{QK // G},{G},{QD}\]")
    copies = [n for n, (shape, _, _) in instrs.items()
              if layer_view.search(shape)]
    assert not copies, copies
    stack_view = f"bf16[1,{QL * QK // G},{G},{QD}]"
    row_gathers = [rest for _, op, rest in instrs.values()
                   if op == "gather" and f"slice_sizes={{1,1,{G},{QD}}}"
                   in rest]
    assert len(row_gathers) == (3 if gated else 2)
    for rest in row_gathers:
        assert instrs[rest.split(",")[0]][0] == stack_view, rest
    stack = {stack_view, f"bf16[{QL},{QK},{QD}]"}
    makers = {op for shape, op, _ in instrs.values() if shape in stack}
    assert makers <= {"parameter", "get-tuple-element", "bitcast"}, makers


class TestCompileCacheLocation:
    def test_env_var_respected(self, monkeypatch, tmp_path):
        prev = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
        try:
            assert CC.use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)

    def test_fixed_checkout_path_when_unset(self, monkeypatch):
        prev = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv(CC.ENV_VAR, raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        try:
            path = CC.use_compile_cache()
            assert path == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
