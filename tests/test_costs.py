"""Tests for the trip-count-aware cost models (launch/costs.py) — including
the verification that XLA's cost_analysis once-counts while bodies."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.costs import jaxpr_cost


def _scan10(x):
    def body(c, _):
        return c @ c, None
    y, _ = jax.lax.scan(body, x, None, length=10)
    return y


def _unroll10(x):
    for _ in range(10):
        x = x @ x
    return x


class TestXLAOnceCounting:
    def test_xla_cost_analysis_once_counts_loops(self):
        """The motivating bug: XLA reports a 10-iteration scan as one."""
        xs = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        f_scan = jax.jit(_scan10).lower(xs).compile().cost_analysis()
        f_unroll = jax.jit(_unroll10).lower(xs).compile().cost_analysis()
        ratio = f_unroll["flops"] / max(f_scan["flops"], 1)
        assert ratio > 8, ratio  # ~10x undercount


class TestJaxprCost:
    def test_scan_multiplies_trip_count(self):
        xs = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        c_scan = jaxpr_cost(_scan10, xs)
        c_unroll = jaxpr_cost(_unroll10, xs)
        assert c_scan["dot_flops"] == c_unroll["dot_flops"] == 10 * 2 * 64**3

    def test_nested_scans_multiply(self):
        def f(x):
            def outer(c, _):
                def inner(ci, _):
                    return ci @ ci, None
                c, _ = jax.lax.scan(inner, c, None, length=3)
                return c, None
            y, _ = jax.lax.scan(outer, x, None, length=5)
            return y
        xs = jax.ShapeDtypeStruct((16, 16), jnp.float32)
        assert jaxpr_cost(f, xs)["dot_flops"] == 15 * 2 * 16**3

    def test_grad_includes_remat_recompute(self):
        def f(w, x):
            def blk(x, w_):
                return jax.nn.relu(x @ w_), None
            blk = jax.checkpoint(blk)
            y, _ = jax.lax.scan(blk, x, jnp.broadcast_to(w, (4,) + w.shape))
            return (y ** 2).sum()
        w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
        x = jax.ShapeDtypeStruct((8, 32), jnp.float32)
        fwd = jaxpr_cost(f, w, x)["dot_flops"]
        bwd = jaxpr_cost(lambda w, x: jax.grad(f)(w, x), w, x)["dot_flops"]
        # fwd + recompute + 2 bwd dots per layer = 4x fwd
        assert bwd == 4 * fwd

    def test_gather_bytes_counted(self):
        def f(w, idx):
            return w[idx].sum()
        w = jax.ShapeDtypeStruct((128, 64), jnp.float32)
        idx = jnp.arange(16)
        c = jaxpr_cost(f, w, idx)
        assert c["gather_bytes"] == 16 * 64 * 4

    def test_dot_flops_batched(self):
        def f(a, b):
            return jnp.einsum("gbd,gdn->gbn", a, b)
        a = jax.ShapeDtypeStruct((4, 8, 16), jnp.float32)
        b = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)
        assert jaxpr_cost(f, a, b)["dot_flops"] == 2 * 4 * 8 * 16 * 32
