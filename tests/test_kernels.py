"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import predictor as P
from repro.core import selection as S
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape)
    if dtype == jnp.int8:
        return (x * 32).astype(jnp.int8)
    return x.astype(dtype)


def _np_pack(v) -> np.ndarray:
    """Loop-free numpy reference for core.predictor.pack_signs: LSB-first
    sign bits of the last axis, zero-padded to whole 32-bit words."""
    neg = np.asarray(v) < 0
    pad = -neg.shape[-1] % 32
    neg = np.pad(neg, [(0, 0)] * (neg.ndim - 1) + [(0, pad)])
    bits = neg.reshape(neg.shape[:-1] + (-1, 32)).astype(np.uint64)
    words = (bits << np.arange(32, dtype=np.uint64)).sum(-1)
    return words.astype(np.uint32).view(np.int32)


class TestSignPack:
    """The XLA sign packer feeds the predictor kernel its packed input."""

    @pytest.mark.parametrize("rows,d", [(8, 32), (16, 128), (64, 2048),
                                        (13824 // 32, 5120 // 4), (5, 96)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
    def test_matches_ref(self, rows, d, dtype):
        v = rand(KEY, (rows, d), dtype)
        np.testing.assert_array_equal(np.asarray(P.pack_signs(v)),
                                      _np_pack(v))

    def test_odd_width_falls_back(self):
        """d not a multiple of 32: padded lanes pack as positive bits."""
        v = jax.random.normal(KEY, (4, 37))
        out = P.pack_signs(v)
        assert out.shape == (4, 2)
        np.testing.assert_array_equal(np.asarray(out), _np_pack(v))

    def test_leading_batch_dims(self):
        v = jax.random.normal(KEY, (2, 3, 64))
        out = P.pack_signs(v)
        assert out.shape == (2, 3, 2)
        np.testing.assert_array_equal(np.asarray(out), _np_pack(v))


class TestPredictCounts:
    @pytest.mark.parametrize("k,d,b", [(64, 128, 1), (512, 256, 4),
                                       (1728, 640, 2), (128, 4096, 16)])
    def test_matches_ref(self, k, d, b):
        kw, kx = jax.random.split(KEY)
        w = jax.random.normal(kw, (k, d))
        x = jax.random.normal(kx, (b, d))
        pw, px = P.pack_signs(w), P.pack_signs(x)
        out = ops.predict_counts(pw, px, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ref.predict_counts_ref(pw, px)))

    def test_margins_equal_core(self):
        kw, kx = jax.random.split(KEY)
        w = jax.random.normal(kw, (256, 128))
        x = jax.random.normal(kx, (2, 128))
        pw, px = P.pack_signs(w), P.pack_signs(x)
        m_kernel = ops.predict_margins(pw, px, 128, 1.02, interpret=True)
        m_core = P.margins(pw, px, 128, 1.02)
        np.testing.assert_allclose(np.asarray(m_kernel), np.asarray(m_core))


class TestTilingGuards:
    """Degenerate tilings raise explicit errors instead of silently running
    worst-case tiles; the ops layer routes those shapes to the jnp oracle."""

    def test_choose_block_k_typical_shapes(self):
        from repro.kernels.predict import choose_block_k
        assert choose_block_k(1024, 8, 4) == 1024          # fits the budget
        assert choose_block_k(13824, 160, 1) == 3200       # lane-aligned cut
        assert choose_block_k(512, 16, 2, group_size=8) == 512
        bk = choose_block_k(4096, 128, 16)                 # budget-bound
        assert 8 <= bk <= 4096 and 4096 % bk == 0

    def test_choose_block_k_group_aligned(self):
        from repro.kernels.predict import choose_block_k
        bk = choose_block_k(4096, 128, 16, group_size=8)
        assert bk % 8 == 0 and 4096 % bk == 0

    @pytest.mark.parametrize("k,w,b", [(0, 4, 1), (64, 0, 1), (64, 4, 0)])
    def test_choose_block_k_rejects_empty(self, k, w, b):
        from repro.kernels.predict import choose_block_k
        with pytest.raises(ValueError):
            choose_block_k(k, w, b)

    def test_choose_block_k_rejects_huge_batch(self):
        """The batch no longer shrinks the tile (tokens run one at a time
        within it), but a row too wide for one 128-lane tile must error,
        not silently degrade to narrower tiles."""
        from repro.kernels.predict import choose_block_k
        assert choose_block_k(4096, 4096, 64) == choose_block_k(4096, 4096, 1)
        with pytest.raises(ValueError, match="degenerate"):
            choose_block_k(4096, 8192, 64)

    def test_choose_block_k_rejects_indivisible_group(self):
        from repro.kernels.predict import choose_block_k
        with pytest.raises(ValueError, match="divisible"):
            choose_block_k(100, 4, 1, group_size=8)

    def test_choose_block_k_lane_aligned_overhang(self):
        """ProSparse-Llama2-7B: k/G = 1376 has no 128-lane divisor, so the
        tile is lane-aligned and the last one overhangs k."""
        from repro.kernels.predict import choose_block_k
        bk = choose_block_k(11008, 128, 4, group_size=8)
        assert (bk // 8) % 128 == 0 and 11008 % bk != 0

    @pytest.mark.parametrize("k,b", [(1376, 4), (1376, 20)])
    def test_overhanging_tiles_match_ref(self, k, b):
        """A k tile past the end of k (cdiv grid) and a partial token tile
        (b not a multiple of 8) leave every real row bitwise unchanged."""
        from repro.kernels import predict as KP
        d, g = 256, 8
        w = jax.random.normal(KEY, (k, d))
        x = jax.random.normal(jax.random.PRNGKey(7), (b, d))
        pw, a = P.pack_signs(w), jnp.full((b,), 1.02)
        gm, cnt = KP.predict_group_margins(pw, P.pack_signs(x), a, d_valid=d,
                                           group_size=g, interpret=True,
                                           block_k=1024)
        gm_ref, cnt_ref = jax.jit(
            ref.predict_group_margins_ref, static_argnums=(2, 4))(
                pw, x, d, a, g)
        np.testing.assert_array_equal(np.asarray(gm), np.asarray(gm_ref))
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_ref))

    def test_compiled_refuses_untileable_shape(self, monkeypatch):
        """Compiled (not interpreting), a shape no kernel tile fits raises
        with the shape instead of running the jnp oracle."""
        from repro.kernels import predict as KP
        # a 2 MiB budget leaves 512 rows at w = 256 words: below one
        # lane-aligned 1024-row tile of 8-row groups
        monkeypatch.setattr(KP, "VMEM_BUDGET_BYTES", 2 * 1024 * 1024)
        k, d, b = 2048, 8192, 8
        pw = jnp.zeros((k, d // 32), jnp.int32)
        x = jnp.zeros((b, d), jnp.float32)
        with pytest.raises(ValueError, match="no TPU tiling.*k=2048"):
            ops.predict_group_margins(pw, x, d, 1.0, group_size=8,
                                      interpret=False)

    def test_ops_fall_back_on_degenerate_shapes(self, monkeypatch):
        """In interpret mode the dispatch layer absorbs the guard errors:
        results still match the oracle for shapes the kernels refuse to
        tile."""
        from repro.kernels import ref
        from repro.kernels import predict as KP
        # a 2 MiB budget at w = 256 words leaves 512 rows, below one
        # lane-aligned 1024-row tile of 8-row groups -> guard -> oracle
        monkeypatch.setattr(KP, "VMEM_BUDGET_BYTES", 2 * 1024 * 1024)
        k, d, b = 2048, 8192, 8
        from repro.kernels.predict import choose_block_k
        with pytest.raises(ValueError, match="degenerate"):
            choose_block_k(k, d // 32, b, group_size=8)
        w = jax.random.normal(KEY, (k, d)) * 0.1
        x = jax.random.normal(jax.random.PRNGKey(1), (b, d))
        pw = P.pack_signs(w)
        gm, cnt = ops.predict_group_margins(pw, x, d, 1.0, group_size=8,
                                            interpret=True)
        gm_ref, cnt_ref = ref.predict_group_margins_ref(
            pw, x, d, jnp.full((b,), 1.0), 8)
        np.testing.assert_allclose(np.asarray(gm), np.asarray(gm_ref),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_ref))


class TestPredictGroupMargins:
    """Single-dispatch predictor kernel vs the multi-dispatch composition."""

    @pytest.mark.parametrize("k,d,b,g", [(256, 128, 1, 8), (512, 256, 4, 8),
                                         (1024, 96, 2, 4), (128, 64, 4, 1)])
    @pytest.mark.parametrize("alpha", [1.0, 1.02])
    def test_matches_jitted_composition(self, k, d, b, g, alpha):
        """Bitwise vs the JITTED pack->margins->group-min pipeline (both
        sides compile the same op sequence; the eager path differs by FMA
        contraction only)."""
        from repro.kernels import ref
        kw, kx = jax.random.split(jax.random.PRNGKey(k + d))
        w = jax.random.normal(kw, (k, d))
        x = jax.random.normal(kx, (b, d))
        pw = P.pack_signs(w)
        gm, cnt = ops.predict_group_margins(pw, x, d, alpha, group_size=g,
                                            interpret=True)
        gm_ref, cnt_ref = jax.jit(
            ref.predict_group_margins_ref, static_argnums=(2, 4))(
                pw, x, d, jnp.full((b,), alpha), g)
        np.testing.assert_array_equal(np.asarray(gm), np.asarray(gm_ref))
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_ref))

    def test_unpacked_tail_padding(self):
        """d not a multiple of 32: the wrapper pads with zeros (positive
        sign bits), matching core.predictor.pack_signs semantics."""
        from repro.kernels import ref
        d = 96 + 8
        w = jax.random.normal(KEY, (64, d))
        x = jax.random.normal(jax.random.PRNGKey(3), (2, d))
        pw = P.pack_signs(w)
        gm, cnt = ops.predict_group_margins(pw, x, d, 1.0, group_size=1,
                                            interpret=True)
        gm_ref, cnt_ref = ref.predict_group_margins_ref(
            pw, x, d, jnp.full((2,), 1.0), 1)
        np.testing.assert_allclose(np.asarray(gm), np.asarray(gm_ref),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_ref))

    def test_per_token_alpha_column(self):
        """Each batch row's margins follow ITS alpha (per-slot SLA alphas)."""
        w = jax.random.normal(KEY, (64, 128))
        x = jnp.tile(jax.random.normal(jax.random.PRNGKey(5), (1, 128)),
                     (2, 1))
        pw = P.pack_signs(w)
        gm, _ = ops.predict_group_margins(
            pw, x, 128, jnp.asarray([1.0, 2.0]), group_size=1,
            interpret=True)
        m0 = P.margins(pw, P.pack_signs(x[:1]), 128, 1.0)
        m1 = P.margins(pw, P.pack_signs(x[1:]), 128, 2.0)
        np.testing.assert_allclose(np.asarray(gm[0]), np.asarray(m0[0]),
                                   rtol=1e-6, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gm[1]), np.asarray(m1[0]),
                                   rtol=1e-6, atol=1e-5)


class TestFusedSparseMLP:
    def _setup(self, k, d, b, g, dtype, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        x = rand(ks[0], (b, d), dtype)
        wg = rand(ks[1], (k, d), dtype) * jnp.asarray(0.06, dtype)
        wu = rand(ks[2], (k, d), dtype) * jnp.asarray(0.06, dtype)
        wd = rand(ks[3], (k, d), dtype) * jnp.asarray(0.06, dtype)
        m = P.margins(P.pack_signs(wg), P.pack_signs(x), d, 1.0)
        gm = S.group_margins(S.union_margin(m), g)
        sel = S.capacity_select(gm, max(1, (k // g) // 2))
        return x, wg, wu, wd, sel

    @pytest.mark.parametrize("k,d,b,g", [(256, 128, 1, 8), (512, 256, 4, 8),
                                         (1024, 512, 2, 16), (256, 128, 2, 1)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_gated_matches_ref(self, k, d, b, g, dtype):
        x, wg, wu, wd, sel = self._setup(k, d, b, g, dtype)
        out = ops.fused_sparse_mlp(x, wg, wu, wd, sel.indices, sel.count,
                                   group_size=g, interpret=True)
        want = ref.fused_sparse_mlp_ref(x, wg, wu, wd, sel.indices, sel.count,
                                        group_size=g)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=tol, atol=tol)

    def test_ungated(self):
        x, wg, _, wd, sel = self._setup(256, 128, 2, 8, jnp.float32)
        out = ops.fused_sparse_mlp(x, wg, None, wd, sel.indices, sel.count,
                                   group_size=8, interpret=True)
        want = ref.fused_sparse_mlp_ref(x, wg, None, wd, sel.indices,
                                        sel.count, group_size=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_fatrelu(self):
        x, wg, wu, wd, sel = self._setup(256, 128, 1, 8, jnp.float32)
        out = ops.fused_sparse_mlp(x, wg, wu, wd, sel.indices, sel.count,
                                   group_size=8, activation="fatrelu",
                                   fatrelu_threshold=0.1, interpret=True)
        want = ref.fused_sparse_mlp_ref(x, wg, wu, wd, sel.indices, sel.count,
                                        group_size=8, activation="fatrelu",
                                        fatrelu_threshold=0.1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_zero_count_returns_zero(self):
        x, wg, wu, wd, sel = self._setup(256, 128, 1, 8, jnp.float32)
        out = ops.fused_sparse_mlp(x, wg, wu, wd, sel.indices,
                                   jnp.int32(0), group_size=8, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), 0.0)

    @pytest.mark.parametrize("k,d,b,g", [(256, 128, 2, 8), (512, 256, 4, 8),
                                         (256, 128, 3, 1)])
    def test_in_kernel_telemetry_matches_ref(self, k, d, b, g):
        """The (B, 3) counters accumulated alongside the accumulator must
        equal the jnp oracle: actual gate activity, in-union false-negative
        proxy, per-token realized rows (TELEMETRY_COLS)."""
        x, wg, wu, wd, sel = self._setup(k, d, b, g, jnp.float32)
        gm_tok, _ = ops.predict_group_margins(
            P.pack_signs(wg), x, d, 1.0, group_size=g, interpret=True)
        y, tel = ops.fused_sparse_mlp(x, wg, wu, wd, sel.indices, sel.count,
                                      gm_tok, group_size=g,
                                      collect_stats=True, interpret=True)
        y_plain = ops.fused_sparse_mlp(x, wg, wu, wd, sel.indices, sel.count,
                                       group_size=g, interpret=True)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_plain))
        want = ref.fused_mlp_telemetry_ref(x, wg, sel.indices, sel.count,
                                           gm_tok, group_size=g)
        assert tel.shape == (b, 3) and tel.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(tel), np.asarray(want))

    def test_telemetry_zero_count(self):
        x, wg, wu, wd, sel = self._setup(256, 128, 2, 8, jnp.float32)
        gm_tok, _ = ops.predict_group_margins(
            P.pack_signs(wg), x, 128, 1.0, group_size=8, interpret=True)
        _, tel = ops.fused_sparse_mlp(x, wg, wu, wd, sel.indices,
                                      jnp.int32(0), gm_tok, group_size=8,
                                      collect_stats=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(tel), 0)

    def test_byte_model_reduction(self):
        """Analytic HBM model: sparse path must beat dense by >4x at 90%."""
        from repro.kernels.sparse_mlp_fused import kernel_hbm_bytes
        k = 13824
        stats = kernel_hbm_bytes(1, 5120, k, cap_groups=int(k / 8 * 0.125),
                                 group_size=8)
        assert stats["reduction"] > 4.0

    def test_byte_model_itemized(self):
        """The traffic model accounts for every pipeline term: predictor
        input read + margins, selection re-read, telemetry outputs — and
        scales with the capacity bucket."""
        from repro.kernels.sparse_mlp_fused import kernel_hbm_bytes
        lo = kernel_hbm_bytes(4, 1024, 4096, cap_groups=64, group_size=8)
        hi = kernel_hbm_bytes(4, 1024, 4096, cap_groups=256, group_size=8)
        assert lo["dispatches"] == 2
        assert lo["total_sparse_bytes"] < hi["total_sparse_bytes"]
        assert lo["total_sparse_bytes"] == (
            lo["fused_bytes"] + lo["predictor_bytes"]
            + lo["selection_bytes"] + lo["telemetry_bytes"])
        # predictor must charge the raw-input read (the old model did not)
        assert lo["predictor_bytes"] > 4096 * (1024 // 32) * 4
        no_tel = kernel_hbm_bytes(4, 1024, 4096, cap_groups=64, group_size=8,
                                  collect_stats=False)
        assert no_tel["telemetry_bytes"] == 0
        assert no_tel["total_sparse_bytes"] < lo["total_sparse_bytes"]
