"""Int8 weight quantization tests (DESIGN.md §13, the PR 10 acceptance
suite).

What this file pins:

* **Round-trip semantics** of ``core.quantize`` — symmetric per-group
  absmax, half-to-even rounding, ±127 clipping, stacked leading dims, the
  all-zero-group scale guard, and the tiling validator the ops wrappers
  fall back through.
* **Predictor invariance** (property-based): quantize-then-dequantize
  never flips the sign of a weight whose quantized value is nonzero, so
  the sign-packs — and therefore the predicted selection sets — are
  IDENTICAL fp32-vs-int8 across random alphas, group sizes and weight
  scales.  The one edge case is pinned explicitly: a small-magnitude
  weight in a group with a much larger absmax can round to q = 0, which
  dequantizes to +0.0 and packs as a POSITIVE sign bit (``v < 0`` is
  False for +0.0 and -0.0 alike) even when the original was negative.
  ``quantize_mlp_node`` sidesteps the flip by deriving ``sign_wg`` from
  the ORIGINAL fp weights before dropping them — selection sets are then
  identical by construction, not by numerical luck.
* **Bitwise kernel parity**: the int8 pallas fused MLP vs the quantized
  jnp oracle (which replays the kernel's exact op order) across
  strategies, capacity buckets, alphas, gated/ungated and fatrelu —
  outputs AND in-kernel telemetry, to the last bit.
* **The HBM traffic model's dtype itemization**: per capacity bucket, the
  int8 fused weight+scale bytes are <= 0.5x the fp32 weight bytes (the
  bench acceptance bar) and the int8 tile term is exactly 4x smaller.
* **End-to-end int8 serving** (single device): greedy tokens and
  controller telemetry bitwise-equal to a server whose fused kernel is
  swapped for the quantized oracle, and a warmed capacity-bucket ladder
  serves with zero post-warmup retraces.

The 2x4-mesh int8 serve parity lives in tests/test_distributed.py (it
needs the 8-device host platform fixtures).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # tier-1 runs with no extra deps
    from _hypothesis_shim import given, settings, strategies as st

from repro.configs.base import ControllerConfig, MetricsConfig, ModelConfig
from repro.core import predictor as P
from repro.core import quantize as Q
from repro.core import selection as S
from repro.core import sparse_mlp as SM
from repro.core.sparse_mlp import (SparseInferConfig, init_gated_mlp,
                                   prepare_sparse_params)
from repro.kernels import ops, ref
from repro.kernels.sparse_mlp_fused import kernel_hbm_bytes
from repro.models import lm
from repro.runtime.server import Request, Server, ServeConfig

jax.config.update("jax_platform_name", "cpu")

KEY = jax.random.PRNGKey(0)


def _eq(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=msg)


# ---------------------------------------------------------------------------
# core.quantize round-trip semantics
# ---------------------------------------------------------------------------

class TestQuantizeCore:
    def test_row_roundtrip_error_bound(self):
        """|deq - w| <= scale/2 per (row, d-group) — half a quant step."""
        w = jax.random.normal(KEY, (16, 64))
        q, s = Q.quantize_rows(w, 16)
        assert q.dtype == jnp.int8 and s.shape == (16, 4)
        deq = Q.dequant_rows(q, s)
        err = np.abs(np.asarray(deq) - np.asarray(w))
        bound = np.repeat(np.asarray(s), 16, axis=1) * 0.5 + 1e-7
        assert (err <= bound).all()

    def test_col_roundtrip_error_bound(self):
        w = jax.random.normal(KEY, (64, 16))
        q, s = Q.quantize_cols(w, 16)
        assert q.dtype == jnp.int8 and s.shape == (4, 16)
        deq = Q.dequant_cols(q, s)
        err = np.abs(np.asarray(deq) - np.asarray(w))
        bound = np.repeat(np.asarray(s), 16, axis=0) * 0.5 + 1e-7
        assert (err <= bound).all()

    def test_symmetric_grid_no_minus_128(self):
        w = jnp.asarray([[-1.0, 1.0, -0.5, 0.5]])
        q, _ = Q.quantize_rows(w, 4)
        assert int(np.asarray(q).min()) >= -127

    def test_all_zero_group_scale_one(self):
        w = jnp.zeros((2, 8))
        q, s = Q.quantize_rows(w, 4)
        _eq(s, np.ones((2, 2), np.float32))
        _eq(q, np.zeros((2, 8), np.int8))

    def test_stacked_leading_dims(self):
        """Scan-over-layer-groups leaves (p, k, d) quantize per-slice."""
        w = jax.random.normal(KEY, (3, 8, 32))
        q, s = Q.quantize_rows(w, 8)
        assert q.shape == (3, 8, 32) and s.shape == (3, 8, 4)
        q0, s0 = Q.quantize_rows(w[1], 8)
        _eq(q[1], q0)
        _eq(s[1], s0)

    @pytest.mark.parametrize("d,k,g,qg", [(60, 64, 8, 16),   # d % qg
                                          (64, 60, 8, 16),   # k % qg
                                          (64, 64, 8, 12),   # qg % g
                                          (64, 64, 8, 0)])   # qg < 1
    def test_check_quant_dims_guards(self, d, k, g, qg):
        with pytest.raises(ValueError):
            Q.check_quant_dims(d, k, g, qg)

    def test_quantize_mlp_node_swaps_leaves(self):
        node = init_gated_mlp(KEY, 64, 128, dtype=jnp.float32)
        node["extra"] = jnp.ones(3)
        out = Q.quantize_mlp_node(node, 32, group_size=8)
        assert set(Q.QUANT_KEYS) <= set(out)
        assert not {"wg_t", "wu_t", "wd_t"} & set(out)
        _eq(out["extra"], node["extra"])
        _eq(out["sign_wg"], P.pack_signs(node["wg_t"]))
        assert Q.is_quantized(out) and not Q.is_quantized(node)
        assert Q.quant_group_size_of(out) == 32
        assert Q.mlp_hidden_rows(out) == 128 == Q.mlp_hidden_rows(node)

    def test_dense_view_roundtrip_and_passthrough(self):
        node = init_gated_mlp(KEY, 64, 128, dtype=jnp.float32)
        qnode = Q.quantize_mlp_node(node, 32)
        dv = Q.dense_view(qnode)
        assert {"wg_t", "wu_t", "wd_t"} <= set(dv)
        assert not set(Q.QUANT_KEYS) & set(dv)
        _eq(dv["wg_t"], Q.dequant_rows(qnode["wg_q"], qnode["wg_s"]))
        assert Q.dense_view(node) is node          # fp passthrough


# ---------------------------------------------------------------------------
# predictor/selection invariance (the property the whole design leans on)
# ---------------------------------------------------------------------------

class TestSignPackInvariance:
    """``sign_wg`` comes from the ORIGINAL weights, so selection is
    invariant by construction; these tests show the numerics also cooperate
    whenever no quantized value rounds to zero — and pin the one case where
    they would not."""

    @given(st.integers(1, 6), st.sampled_from([64, 128]),
           st.sampled_from([16, 32, 64]), st.floats(0.5, 2.0),
           st.floats(0.01, 10.0), st.sampled_from([1, 4, 8]))
    @settings(max_examples=30, deadline=None)
    def test_selection_sets_identical(self, seed, d, qg, alpha, scale, g):
        """Weights with per-entry magnitude in [0.5, 1]·scale cannot round
        to zero (|w|/s >= 0.5·127/absmax >= 63.5 within any group), so the
        dequantized sign-pack equals the original — and the predicted
        selection set is identical fp32-vs-int8 for every alpha."""
        k = 128
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        sign = jnp.where(jax.random.bernoulli(ks[0], 0.5, (k, d)), 1., -1.)
        mag = jax.random.uniform(ks[1], (k, d), minval=0.5, maxval=1.0)
        wg = sign * mag * scale
        node = {"wg_t": wg, "wu_t": wg * 0.5, "wd_t": wg * 0.25}
        qnode = Q.quantize_mlp_node(node, qg, group_size=g)
        deq = Q.dense_view(qnode)["wg_t"]
        assert (np.asarray(deq) != 0.0).all()      # no zero-crossings
        _eq(P.pack_signs(deq), P.pack_signs(wg), "dequantized sign-pack")
        _eq(qnode["sign_wg"], P.pack_signs(wg), "stored sign-pack")
        # identical packs -> identical margins -> identical selection
        x = jax.random.normal(ks[2], (2, d))
        px = P.pack_signs(x)
        m_fp = P.margins(P.pack_signs(wg), px, d, alpha)
        m_q = P.margins(qnode["sign_wg"], px, d, alpha)
        _eq(m_fp, m_q)
        gm = S.group_margins(S.union_margin(m_fp), g)
        sel_fp = S.capacity_select(gm, max(1, (k // g) // 2))
        gm_q = S.group_margins(S.union_margin(m_q), g)
        sel_q = S.capacity_select(gm_q, max(1, (k // g) // 2))
        _eq(sel_fp.indices, sel_q.indices)
        _eq(sel_fp.count, sel_q.count)

    def test_zero_crossing_pin(self):
        """THE documented edge case (DESIGN.md §13): a tiny negative weight
        sharing a quant group with a large one rounds to q = 0, which
        dequantizes to +0.0 — and +0.0 packs as a POSITIVE sign bit, unlike
        the original.  A sign-pack taken from the dequantized weights would
        therefore flip this neuron's predictor bit; ``quantize_mlp_node``
        packs the ORIGINALS instead, so the stored pack keeps the negative
        bit and selection cannot drift."""
        # group absmax 1.0 -> scale 1/127; |-1e-6| / s ~ 1.27e-4 rounds to 0
        wg = jnp.asarray([[-1e-6, 1.0, 0.25, -0.5]])
        q, s = Q.quantize_rows(wg, 4)
        assert int(np.asarray(q)[0, 0]) == 0
        deq = Q.dequant_rows(q, s)
        assert float(np.asarray(deq)[0, 0]) == 0.0
        # +0.0 and -0.0 both pack positive ('v < 0' is False for both)...
        _eq(P.pack_signs(deq), P.pack_signs(deq.at[0, 0].set(-0.0)))
        # ...so the dequantized pack LOSES the original's negative bit
        assert not np.array_equal(np.asarray(P.pack_signs(deq)),
                                  np.asarray(P.pack_signs(wg)))
        # the node-level API is immune: sign_wg is packed from ORIGINALS
        # (k=4 rows so the (k, d)=(4, 4) node admits qg=4 on both axes)
        wg4 = jnp.concatenate([wg, jax.random.normal(KEY, (3, 4))])
        node = {"wg_t": wg4, "wd_t": jnp.ones((4, 4)) * 0.1}
        qnode = Q.quantize_mlp_node(node, 4, group_size=1)
        _eq(qnode["sign_wg"], P.pack_signs(wg4))
        deq4 = Q.dense_view(qnode)["wg_t"]
        assert float(np.asarray(deq4)[0, 0]) == 0.0   # the crossing persists


# ---------------------------------------------------------------------------
# int8 pallas kernel vs the quantized oracle — bitwise
# ---------------------------------------------------------------------------

def _qsetup(k, d, b, g, qg, gated=True, alpha=1.0, cap_frac=0.5, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (b, d))
    node = init_gated_mlp(ks[1], d, k, dtype=jnp.float32, gated=gated)
    qnode = Q.quantize_mlp_node(node, qg, group_size=g)
    gm_tok, _ = ops.predict_group_margins(qnode["sign_wg"], x, d, alpha,
                                          group_size=g, interpret=True)
    gm = S.union_margin(gm_tok)
    sel = S.capacity_select(gm, max(1, int((k // g) * cap_frac)))
    return x, qnode, sel, gm_tok


def _qargs(qnode):
    return (qnode["wg_q"], qnode["wg_s"], qnode.get("wu_q"),
            qnode.get("wu_s"), qnode["wd_q"], qnode["wd_s"])


@pytest.mark.quant
class TestQuantKernelVsOracle:
    """Pallas (interpret) int8 fused MLP vs ref.fused_sparse_mlp_q_ref:
    BITWISE on outputs and telemetry — the oracle replays the kernel's op
    order, so any drift is a real kernel bug, not float noise."""

    @pytest.mark.parametrize("k,d,b,g,qg", [(256, 128, 1, 8, 32),
                                            (512, 256, 4, 8, 64),
                                            (256, 128, 2, 1, 128),
                                            (128, 64, 3, 4, 16)])
    @pytest.mark.parametrize("alpha", [1.0, 1.02])
    @pytest.mark.parametrize("cap_frac", [0.25, 0.5, 1.0])
    def test_gated_bitwise(self, k, d, b, g, qg, alpha, cap_frac):
        x, qn, sel, gm_tok = _qsetup(k, d, b, g, qg, alpha=alpha,
                                     cap_frac=cap_frac)
        y, tel = ops.fused_sparse_mlp_q(
            x, *_qargs(qn), sel.indices, sel.count, gm_tok, group_size=g,
            collect_stats=True, interpret=True)
        y_ref, tel_ref = ref.fused_sparse_mlp_q_ref(
            x, *_qargs(qn), sel.indices, sel.count, gm_tok, group_size=g,
            collect_stats=True)
        _eq(y, y_ref, f"y @ cap_frac={cap_frac} alpha={alpha}")
        _eq(tel, tel_ref, f"tel @ cap_frac={cap_frac} alpha={alpha}")

    def test_ungated_bitwise(self):
        x, qn, sel, _ = _qsetup(256, 128, 2, 8, 32, gated=False)
        out = ops.fused_sparse_mlp_q(x, *_qargs(qn), sel.indices, sel.count,
                                     group_size=8, interpret=True)
        want = ref.fused_sparse_mlp_q_ref(x, *_qargs(qn), sel.indices,
                                          sel.count, group_size=8)
        _eq(out, want)

    def test_fatrelu_bitwise(self):
        x, qn, sel, gm_tok = _qsetup(256, 128, 2, 8, 32)
        kw = dict(group_size=8, activation="fatrelu", fatrelu_threshold=0.1,
                  collect_stats=True)
        y, tel = ops.fused_sparse_mlp_q(x, *_qargs(qn), sel.indices,
                                        sel.count, gm_tok, interpret=True,
                                        **kw)
        y_ref, tel_ref = ref.fused_sparse_mlp_q_ref(
            x, *_qargs(qn), sel.indices, sel.count, gm_tok, **kw)
        _eq(y, y_ref)
        _eq(tel, tel_ref)

    def test_chunk_bitwise(self):
        """Row-tiled prefill twin: per-row math identical to the decode
        kernel, so the decode oracle is the chunk oracle too."""
        x, qn, sel, gm_tok = _qsetup(256, 128, 16, 8, 32)
        y, tel = ops.fused_sparse_mlp_chunk_q(
            x, *_qargs(qn), sel.indices, sel.count, gm_tok, group_size=8,
            collect_stats=True, interpret=True)
        y_ref, tel_ref = ref.fused_sparse_mlp_chunk_q_ref(
            x, *_qargs(qn), sel.indices, sel.count, gm_tok, group_size=8,
            collect_stats=True)
        _eq(y, y_ref)
        _eq(tel, tel_ref)

    def test_zero_count_returns_zero(self):
        x, qn, sel, _ = _qsetup(256, 128, 1, 8, 32)
        out = ops.fused_sparse_mlp_q(x, *_qargs(qn), sel.indices,
                                     jnp.int32(0), group_size=8,
                                     interpret=True)
        _eq(out, np.zeros_like(np.asarray(out)))

    def test_grouping_is_load_bearing(self):
        """Shuffling one scale group's value must change the output — the
        kernel really applies per-group scales, not a global rescale."""
        x, qn, sel, _ = _qsetup(256, 128, 2, 8, 32)
        y = ops.fused_sparse_mlp_q(x, *_qargs(qn), sel.indices, sel.count,
                                   group_size=8, interpret=True)
        bent = dict(qn)
        bent["wg_s"] = qn["wg_s"].at[:, 0].mul(2.0)
        y_bent = ops.fused_sparse_mlp_q(x, *_qargs(bent), sel.indices,
                                        sel.count, group_size=8,
                                        interpret=True)
        assert not np.array_equal(np.asarray(y), np.asarray(y_bent))


# ---------------------------------------------------------------------------
# HBM traffic model: weight-dtype itemization (the bench acceptance bar)
# ---------------------------------------------------------------------------

class TestHbmBytesWeightDtype:
    B, D, K, G, QG = 4, 1024, 4096, 8, 128

    def _pair(self, cap_groups):
        fp = kernel_hbm_bytes(self.B, self.D, self.K, cap_groups, self.G,
                              weight_bytes=4)
        q = kernel_hbm_bytes(self.B, self.D, self.K, cap_groups, self.G,
                             weight_bytes=4, weight_dtype="int8",
                             quant_group_size=self.QG)
        return fp, q

    @pytest.mark.parametrize("cap_groups", [64, 128, 256, 512])
    def test_int8_fp32_ratio_per_bucket(self, cap_groups):
        """Per capacity bucket: int8 fused weight+scale traffic <= 0.5x the
        fp32 weight traffic (the ISSUE 10 acceptance bar), and the tile
        term alone is exactly 4x smaller."""
        fp, q = self._pair(cap_groups)
        assert fp["fused_scale_bytes"] == 0
        assert q["fused_weight_bytes"] * 4 == fp["fused_weight_bytes"]
        ratio = ((q["fused_weight_bytes"] + q["fused_scale_bytes"])
                 / fp["fused_weight_bytes"])
        assert ratio <= 0.5, ratio
        assert q["total_sparse_bytes"] < fp["total_sparse_bytes"]

    def test_dtype_labels(self):
        fp, q = self._pair(128)
        assert fp["weight_dtype"] == "fp32"
        assert q["weight_dtype"] == "int8"
        bf16 = kernel_hbm_bytes(self.B, self.D, self.K, 128, self.G)
        assert bf16["weight_dtype"] == "fp16"

    def test_scale_bytes_itemized(self):
        """Scale traffic follows the §13 layout: (rows, d/qg) f32 tiles for
        gate+up per selected group plus the resident (k/qg, d) f32
        down-proj scale matrix, read once per call."""
        _, q = self._pair(128)
        sel_rows = 128 * self.G
        want = (2 * sel_rows * (self.D // self.QG) * 4    # wg + wu scales
                + (self.K // self.QG) * self.D * 4)       # wd scale matrix
        assert q["fused_scale_bytes"] == want

    def test_act_bytes_decoupled(self):
        """int8 weights with f32 activations: act traffic keys off
        act_bytes, not the weight dtype."""
        a2 = kernel_hbm_bytes(self.B, self.D, self.K, 128, self.G,
                              weight_dtype="int8", act_bytes=2)
        a4 = kernel_hbm_bytes(self.B, self.D, self.K, 128, self.G,
                              weight_dtype="int8", act_bytes=4)
        assert a2["fused_weight_bytes"] == a4["fused_weight_bytes"]
        assert a2["predictor_bytes"] < a4["predictor_bytes"]


# ---------------------------------------------------------------------------
# strategy routing on quantized nodes
# ---------------------------------------------------------------------------

class TestQuantStrategyRouting:
    D, K = 64, 256

    def _cfg(self, strategy, **kw):
        base = dict(enabled=True, activation="relu", group_size=8,
                    capacity_frac=0.5, weight_dtype="int8",
                    quant_group_size=32)
        base.update(kw)
        return SparseInferConfig(strategy=strategy, **base)

    def _nodes(self):
        node = init_gated_mlp(KEY, self.D, self.K, dtype=jnp.float32)
        fp = prepare_sparse_params(node)
        qn = prepare_sparse_params(node, self._cfg("pallas"))
        return fp, qn

    def test_prepare_sparse_params_quantizes(self):
        fp, qn = self._nodes()
        assert Q.is_quantized(qn) and not Q.is_quantized(fp)
        _eq(qn["sign_wg"], fp["sign_wg"])

    @pytest.mark.parametrize("strategy", ["masked", "gather", "pallas"])
    def test_strategies_run_and_match_dense_view(self, strategy):
        """masked/gather consume the dequantized dense view bitwise; pallas
        routes to the int8 kernel and must match ITS oracle bitwise (the
        int8 model is a different function than fp32 — strategies are only
        compared within the same weight numerics)."""
        fp, qn = self._nodes()
        x = jax.random.normal(jax.random.PRNGKey(1), (2, self.D))
        cfg = self._cfg(strategy)
        y, stats = SM.apply(qn, x, cfg, alpha=1.0, return_stats=True)
        dv = dict(Q.dense_view(qn))
        dv["sign_wg"] = qn["sign_wg"]
        y_dv, stats_dv = SM.apply(dv, x, dataclasses.replace(
            cfg, weight_dtype=""), alpha=1.0, return_stats=True)
        if strategy == "pallas":
            # same selection, int8 numerics ~ dequantized numerics
            np.testing.assert_allclose(np.asarray(y), np.asarray(y_dv),
                                       rtol=2e-5, atol=2e-5)
        else:
            _eq(y, y_dv, strategy)
        _eq(stats["predicted_density"], stats_dv["predicted_density"])

    def test_selection_invariance_fp_vs_int8_stats(self):
        """The serving telemetry the controller consumes — predicted /
        realized density, union demand, overflow — is bitwise-identical
        fp32-vs-int8 (selection is sign-pack-driven and the pack is shared;
        DESIGN.md §13)."""
        fp, qn = self._nodes()
        x = jax.random.normal(jax.random.PRNGKey(2), (3, self.D))
        for alpha in (0.8, 1.0, 1.3):
            _, st_fp = SM.apply(fp, x, self._cfg("pallas", weight_dtype=""),
                                alpha=alpha, return_stats=True)
            _, st_q = SM.apply(qn, x, self._cfg("pallas"), alpha=alpha,
                               return_stats=True)
            for key in ("predicted_density", "realized_density",
                        "union_demand_frac", "overflow_frac"):
                _eq(st_fp[key], st_q[key], f"{key} @ alpha={alpha}")


# ---------------------------------------------------------------------------
# end-to-end int8 serving (single device; the mesh twin lives in
# tests/test_distributed.py)
# ---------------------------------------------------------------------------

CFG_Q = ModelConfig(
    name="tiny-int8", family="dense", n_layers=2, d_model=64, n_heads=2,
    n_kv_heads=2, d_ff=256, vocab=128, max_seq=64, dtype="float32",
    param_dtype="float32", attn_chunk=8, loss_chunk=64, remat=False,
    activation="relu",
    sparse=SparseInferConfig(enabled=True, strategy="pallas",
                             activation="relu", group_size=8,
                             capacity_frac=0.5, weight_dtype="int8",
                             quant_group_size=32))


def _reqs(n=3, max_new=5):
    rng = np.random.default_rng(0)
    return [Request(uid=i, prompt=rng.integers(0, 128, size=6),
                    max_new=max_new) for i in range(n)]


@pytest.mark.quant
class TestInt8Serve:
    def test_serve_matches_quant_oracle_bitwise(self, monkeypatch):
        """int8 e2e serve == the same serve with the pallas int8 kernel
        swapped for the quantized oracle: greedy tokens and every
        controller telemetry leaf, bitwise.  (pallas_mlp resolves the ops
        attr at trace time, so monkeypatching reroutes the oracle server's
        fresh per-instance traces.)"""
        params = lm.init_lm(jax.random.PRNGKey(0), CFG_Q)
        ccfg = ControllerConfig(enabled=True, target_density=0.25,
                                audit_period=4)
        scfg = ServeConfig(batch=2, max_len=64, controller=ccfg)
        srv_k = Server(lm, CFG_Q, scfg, params)
        done_k = srv_k.serve(_reqs())

        def oracle(*a, **kw):
            kw.pop("interpret", None)
            kw.pop("groups_per_step", None)
            return ref.fused_sparse_mlp_q_ref(*a, **kw)

        monkeypatch.setattr("repro.kernels.ops.fused_sparse_mlp_q", oracle)
        monkeypatch.setattr("repro.kernels.ops.fused_sparse_mlp_chunk_q",
                            oracle)
        srv_o = Server(lm, CFG_Q, scfg, params)
        done_o = srv_o.serve(_reqs())
        for a, b in zip(done_k, done_o):
            _eq(a.out, b.out, f"tokens uid={a.uid}")
        for name in ("alphas", "density_ema", "fn_ema", "union_ema",
                     "predicted_ema"):
            _eq(getattr(srv_k.controller.state, name),
                getattr(srv_o.controller.state, name), name)

    def test_warmed_bucket_ladder_retrace_silent(self):
        """int8 through the capacity-bucket ladder: every bucket traced
        exactly once at warmup, zero post-warmup retraces across bucket
        switches (the PR 3 invariant, preserved by the quantized path)."""
        cfg = CFG_Q.replace(sparse=dataclasses.replace(
            CFG_Q.sparse, capacity_buckets=(0.25, 0.5, 1.0)))
        srv = Server(lm, cfg,
                     ServeConfig(batch=2, max_len=64, warm_buckets=True,
                                 controller=ControllerConfig(enabled=True),
                                 metrics=MetricsConfig(enabled=True)),
                     lm.init_lm(jax.random.PRNGKey(0), cfg))
        try:
            srv.serve(_reqs())                  # drain 1: warm + arm
            assert srv.metrics.watchdog.armed
            srv.serve(_reqs(n=6))               # drain 2: sweep the ladder
            assert srv.metrics.watchdog.retraces_post_warmup == 0
            assert srv.metrics.counter_value("retrace_post_warmup") == 0
            assert all(c == 1 for c in srv._trace_counts.values()), \
                dict(srv._trace_counts)
        finally:
            srv.metrics.close()

    def test_int8_decode_tracks_fp_greedy_mostly(self):
        """Accuracy proxy: int8 decode agrees with the fp32 sparse decode
        on most greedy tokens (quantization noise, not selection drift —
        selection is identical by the invariance tests above)."""
        cfg_fp = CFG_Q.replace(sparse=dataclasses.replace(
            CFG_Q.sparse, weight_dtype=""))
        params = lm.init_lm(jax.random.PRNGKey(0), CFG_Q)
        prompts = np.random.default_rng(1).integers(0, 128, size=(2, 8))
        gen_fp = Server(lm, cfg_fp, ServeConfig(batch=2, max_len=32),
                        params).generate(prompts, 8)
        gen_q = Server(lm, CFG_Q, ServeConfig(batch=2, max_len=32),
                       params).generate(prompts, 8)
        agree = (gen_fp == gen_q).mean()
        assert agree > 0.5, agree
