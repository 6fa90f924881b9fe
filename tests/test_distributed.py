"""Tensor-parallel sparse decode tests (DESIGN.md §8).

The invariant everything here pins: ``SparseInferConfig.tp_shards`` defines
the decode SEMANTICS (shard-local union + top-C/ms selection, summed
partials / telemetry counts); the mesh is an execution detail.  Running the
same config under shard_map on the 4-device host platform (conftest forces
``--xla_force_host_platform_device_count=4``) must be BITWISE identical to
the single-device emulation — tokens, every ``MLP_STAT_KEYS`` leaf, and the
per-shard rider — across strategies and capacity buckets.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ControllerConfig, ModelConfig
from repro.core import predictor as P
from repro.core import sparse_mlp as SM
from repro.core.sparse_mlp import (MLP_STAT_KEYS, SHARD_STAT_KEY,
                                   SparseInferConfig, init_gated_mlp,
                                   prepare_sparse_params)
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.runtime import distributed as DD
from repro.runtime.controller import (AlphaController, DistributedController,
                                      remap_shard_ema, restore_controller,
                                      save_controller)
from repro.runtime.server import Request, Server, ServeConfig

jax.config.update("jax_platform_name", "cpu")

MS = 4
needs_mesh = pytest.mark.skipif(
    jax.device_count() < MS,
    reason=f"needs {MS} host-platform devices (conftest XLA_FLAGS)")

D, K = 64, 256
STRATEGIES = ("masked", "gather", "pallas")


def _mesh():
    return make_mesh((1, MS), ("data", "model"))


def _params(key=0, dtype=jnp.float32):
    return prepare_sparse_params(
        init_gated_mlp(jax.random.PRNGKey(key), D, K, dtype=dtype))


def _cfg(strategy, **kw):
    base = dict(enabled=True, activation="relu", group_size=8,
                capacity_frac=0.5, tp_shards=MS)
    base.update(kw)
    return SparseInferConfig(strategy=strategy, **base)


def _assert_tree_equal(a, b, msg=""):
    assert set(a) == set(b), (set(a), set(b))
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=f"{msg}:{k}")


class TestShardCapacity:
    def test_per_shard_capacity_divides(self):
        sp = _cfg("gather", capacity_frac=0.5)     # cap 16 groups of 32
        assert sp.shard_capacity(K) == sp.capacity(K) // MS

    def test_indivisible_capacity_rejected(self):
        sp = _cfg("gather", group_size=1, capacity_override=130)
        with pytest.raises(ValueError, match="tp_shards"):
            sp.shard_capacity(K)

    def test_indivisible_k_rejected(self):
        from repro.sharding import sparse as SS
        with pytest.raises(ValueError, match="divisible"):
            SS.validate_shardable(_cfg("gather"), K + 8, MS)

    def test_every_ladder_bucket_validated(self):
        from repro.sharding import sparse as SS
        sp = _cfg("gather", group_size=1, capacity_buckets=(0.1, 0.5))
        SS.validate_shardable(sp, 512, MS)         # 128/256: both divide

    def test_ops_choose_blocks_shard_local(self):
        from repro.kernels import ops as kops
        plan = kops.choose_blocks(K, P.packed_width(D), 3, group_size=8,
                                  n_shards=MS)
        bk = plan.block_k
        assert bk <= K // MS and (K // MS) % bk == 0
        assert plan.mlp_groups == 1          # no bucket given
        with pytest.raises(ValueError, match="divisible"):
            kops.choose_blocks(K, P.packed_width(D), 3, group_size=8,
                               n_shards=3)

    def test_choose_blocks_per_bucket_mlp_tile(self):
        """Wide local buckets get a taller fused-MLP weight tile; narrow
        ones keep the single-group tile (satellite: per-bucket block-shape
        tuning beyond the shared G×d)."""
        from repro.kernels import ops as kops
        wide = kops.choose_blocks(1024, P.packed_width(D), 2, group_size=8,
                                  n_shards=2, capacity_groups=64)
        narrow = kops.choose_blocks(1024, P.packed_width(D), 2, group_size=8,
                                    n_shards=2, capacity_groups=2)
        assert wide.mlp_groups > narrow.mlp_groups == 1
        assert 64 % wide.mlp_groups == 0


@needs_mesh
class TestShardedMlpParity:
    """shard_map execution == single-device emulation, bitwise, for every
    strategy, both alpha layouts, and multiple capacity buckets."""

    @pytest.mark.parametrize("strategy", STRATEGIES)
    @pytest.mark.parametrize("frac", [0.25, 0.5, 1.0])
    def test_bitwise_vs_emulated(self, strategy, frac):
        params = _params()
        x = jax.random.normal(jax.random.PRNGKey(1), (3, D))
        cfg = _cfg(strategy, capacity_frac=frac)
        y_ref, st_ref = SM.apply(params, x, cfg, alpha=1.0,
                                 return_stats=True)
        with _mesh():
            y_sh, st_sh = jax.jit(
                lambda p, xx: SM.apply(p, xx, cfg, alpha=1.0,
                                       return_stats=True))(params, x)
        np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_sh))
        _assert_tree_equal(st_ref, st_sh, strategy)

    @pytest.mark.parametrize("strategy", STRATEGIES)
    def test_bitwise_per_token_alphas(self, strategy):
        params = _params(2)
        x = jax.random.normal(jax.random.PRNGKey(3), (3, D))
        cfg = _cfg(strategy)
        alphas = jnp.asarray([0.6, 1.0, 1.4], jnp.float32)
        y_ref, st_ref = SM.apply(params, x, cfg, alpha=alphas,
                                 return_stats=True)
        with _mesh():
            y_sh, st_sh = jax.jit(
                lambda p, xx, a: SM.apply(p, xx, cfg, alpha=a,
                                          return_stats=True))(
                params, x, alphas)
        np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_sh))
        _assert_tree_equal(st_ref, st_sh, strategy)

    def test_no_stats_path_bitwise(self):
        params = _params()
        x = jax.random.normal(jax.random.PRNGKey(4), (2, D))
        cfg = _cfg("gather")
        y_ref = SM.apply(params, x, cfg, alpha=1.0)
        with _mesh():
            y_sh = jax.jit(lambda p, xx: SM.apply(p, xx, cfg,
                                                  alpha=1.0))(params, x)
        np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_sh))

    def test_mesh_size_mismatch_rejected(self):
        params = _params()
        x = jax.random.normal(jax.random.PRNGKey(5), (2, D))
        cfg = _cfg("gather", tp_shards=2)          # mesh model axis is 4
        with _mesh(), pytest.raises(ValueError, match="model"):
            SM.apply(params, x, cfg, alpha=1.0)


class TestShardedSemantics:
    """Single-device emulation properties (no mesh needed)."""

    @pytest.mark.parametrize("strategy", ["gather", "pallas"])
    def test_matches_unsharded_when_capacity_slack(self, strategy):
        """With per-row selection and no binding clamp the shard-local
        union selection keeps exactly the predicted set — same rows as the
        global selection, so sharding only reorders the down-proj sum."""
        params = _params(6)
        params["wg_t"] = params["wg_t"] - 0.1     # sparse regime
        params = prepare_sparse_params(
            {k: v for k, v in params.items() if k != "sign_wg"})
        x = jax.random.normal(jax.random.PRNGKey(7), (3, D))
        cfg = _cfg(strategy, group_size=1, capacity_frac=1.0)
        cfg0 = dataclasses.replace(cfg, tp_shards=0)
        y, st = SM.apply(params, x, cfg, alpha=1.0, return_stats=True)
        y0, st0 = SM.apply(params, x, cfg0, alpha=1.0, return_stats=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                                   atol=1e-4, rtol=1e-4)
        for k in ("predicted_density", "realized_density",
                  "union_demand_frac"):
            np.testing.assert_allclose(np.asarray(st[k]), np.asarray(st0[k]),
                                       atol=1e-6, err_msg=k)

    def test_sharded_masked_stats_match_unsharded(self):
        """Masked telemetry is count-exact: sharding must not change any
        stat (the counts are partitioned, then summed exactly)."""
        params = _params(8)
        x = jax.random.normal(jax.random.PRNGKey(9), (3, D))
        y, st = SM.apply(params, x, _cfg("masked"), alpha=1.0,
                         return_stats=True)
        y0, st0 = SM.apply(params, x, _cfg("masked", tp_shards=0),
                           alpha=1.0, return_stats=True)
        for k in MLP_STAT_KEYS:
            np.testing.assert_allclose(np.asarray(st[k]), np.asarray(st0[k]),
                                       atol=1e-6, err_msg=k)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                                   atol=1e-4, rtol=1e-4)

    def test_shard_rider_shape_and_consistency(self):
        """The per-shard realized densities must sum (×k_l/k) to the global
        realized density."""
        params = _params(10)
        x = jax.random.normal(jax.random.PRNGKey(11), (5, D))
        _, st = SM.apply(params, x, _cfg("gather"), alpha=1.0,
                         return_stats=True)
        rider = np.asarray(st[SHARD_STAT_KEY])
        assert rider.shape == (5, MS)
        np.testing.assert_allclose(rider.sum(-1) / MS,
                                   np.asarray(st["realized_density"]),
                                   atol=1e-6)

    def test_dead_slot_contributes_nothing(self):
        from repro.runtime.server import DEAD_SLOT_ALPHA
        params = _params(12)
        x = jax.random.normal(jax.random.PRNGKey(13), (2, D))
        cfg = _cfg("gather")
        alphas = jnp.asarray([1.0, DEAD_SLOT_ALPHA], jnp.float32)
        _, st = SM.apply(params, x, cfg, alpha=alphas, return_stats=True)
        assert np.asarray(st["predicted_density"])[1] == 0.0
        assert np.asarray(st["realized_density"])[1] == 0.0
        np.testing.assert_array_equal(np.asarray(st[SHARD_STAT_KEY])[1], 0.0)

    def test_dense_fallback_emits_rider(self):
        """The big-batch dense fallback bypasses the sharded dispatch but
        must still emit the per-shard rider, or its stats would not stack
        against MoE layers' zero-stats under scan (deepseek layout)."""
        from repro.layers.mlp import mlp_apply
        params = _params(16)
        cfg = _cfg("gather")
        x = jax.random.normal(jax.random.PRNGKey(17),
                              (cfg.sparse_max_batch + 4, D))
        _, st = mlp_apply(params, x, cfg, decode=True, alpha=1.0,
                          return_stats=True)
        assert st[SHARD_STAT_KEY].shape == (cfg.sparse_max_batch + 4, MS)
        np.testing.assert_array_equal(np.asarray(st[SHARD_STAT_KEY]), 0.0)

    def test_grouped_input_rejected(self):
        params = _params(14)
        x = jax.random.normal(jax.random.PRNGKey(15), (2, 3, D))
        with pytest.raises(ValueError, match="tp_shards"):
            DD.sharded_apply(params, x, _cfg("gather"), 1.0,
                             strategy="gather")


CFG_LM = ModelConfig(
    name="tiny-tp", family="dense", n_layers=2, d_model=64, n_heads=2,
    n_kv_heads=2, d_ff=K, vocab=128, max_seq=64, dtype="float32",
    param_dtype="float32", attn_chunk=8, loss_chunk=64, remat=False,
    activation="relu",
    sparse=SparseInferConfig(enabled=True, strategy="gather",
                             activation="relu", group_size=8,
                             capacity_frac=0.5))


@needs_mesh
class TestShardedDecodeStep:
    """The whole decode step — attention + sharded KV + sparse MLP — on the
    mesh vs the single-device emulation of the same tp_shards config."""

    @pytest.mark.parametrize("strategy", STRATEGIES)
    def test_decode_step_tokens_and_stats_bitwise(self, strategy):
        """Greedy tokens and ALL telemetry leaves are bitwise-equal; raw
        logits agree to float noise (the sequence-sharded KV cache
        partitions the attention reduction, so GSPMD's combine order may
        differ from the single-device sum — the sign-bit predictor and the
        argmax are insensitive to it, which is what serving consumes)."""
        from repro.models.common import greedy_sample
        cfg = CFG_LM.replace(sparse=dataclasses.replace(
            CFG_LM.sparse, strategy=strategy, tp_shards=MS))
        params = lm.prepare_sparse(lm.init_lm(jax.random.PRNGKey(0), cfg))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                  cfg.vocab)

        def step(params, cfg):
            _, caches = lm.prefill(params, cfg, toks, max_len=32)
            return lm.decode_step(params, cfg, toks[:, -1:], caches,
                                  jnp.int32(8), collect_stats=True)

        logits_ref, _, st_ref = step(params, cfg)
        with _mesh():
            params_sh = jax.tree.map(jnp.asarray, params)
            logits_sh, caches_sh, st_sh = jax.jit(
                lambda p: step(p, cfg))(params_sh)
        np.testing.assert_array_equal(
            np.asarray(greedy_sample(logits_ref)),
            np.asarray(greedy_sample(logits_sh)))
        np.testing.assert_allclose(np.asarray(logits_ref),
                                   np.asarray(logits_sh),
                                   rtol=2e-3, atol=2e-4)
        assert np.asarray(st_sh[SHARD_STAT_KEY]).shape == (cfg.n_layers, 2,
                                                           MS)
        _assert_tree_equal(st_ref, st_sh, strategy)

    def test_kv_cache_sharded_over_model(self):
        """init_caches under the mesh places the decode KV caches with the
        shard_kv_cache layout (sequence over 'model')."""
        cfg = CFG_LM.replace(sparse=dataclasses.replace(
            CFG_LM.sparse, tp_shards=MS))
        with _mesh():
            caches = lm.init_caches(cfg, batch=2, max_len=32)
            spec = caches["blocks"]["k"].sharding.spec
        assert "model" in tuple(spec), spec


def _serve_cfg(strategy, buckets=()):
    return CFG_LM.replace(sparse=dataclasses.replace(
        CFG_LM.sparse, strategy=strategy, group_size=1,
        capacity_buckets=buckets))


def _reqs(n=3, max_new=5, vocab=128):
    rng = np.random.default_rng(0)
    return [Request(uid=i, prompt=rng.integers(0, vocab, size=6),
                    max_new=max_new) for i in range(n)]


@needs_mesh
class TestMeshServer:
    """Server(mesh=...) end to end: bitwise tokens + controller telemetry
    vs the single-device tp_shards path, one executable per bucket."""

    @pytest.mark.parametrize("strategy", STRATEGIES)
    def test_serve_tokens_and_controller_bitwise(self, strategy):
        cfg = _serve_cfg(strategy)
        params = lm.init_lm(jax.random.PRNGKey(0), cfg)
        ccfg = ControllerConfig(enabled=True, target_density=0.25,
                                audit_period=4)
        scfg = ServeConfig(batch=2, max_len=64, controller=ccfg)
        cfg_e = cfg.replace(sparse=dataclasses.replace(cfg.sparse,
                                                       tp_shards=MS))
        srv_e = Server(lm, cfg_e, scfg, params)
        done_e = srv_e.serve(_reqs())
        srv_m = Server(lm, cfg, scfg, params, mesh=_mesh())
        done_m = srv_m.serve(_reqs())
        for a, b in zip(done_e, done_m):
            np.testing.assert_array_equal(a.out, b.out)
        for name in ("alphas", "density_ema", "fn_ema", "union_ema",
                     "predicted_ema"):
            np.testing.assert_array_equal(
                getattr(srv_e.controller.state, name),
                getattr(srv_m.controller.state, name), err_msg=name)
        np.testing.assert_array_equal(srv_e.controller.shard_density_ema,
                                      srv_m.controller.shard_density_ema)

    def test_bucket_ladder_no_retrace_on_mesh(self):
        """One jitted executable per capacity bucket under the mesh: every
        bucket traced exactly once (the warmup), none after — switching
        buckets between decode steps never retraces (PR 3 invariant,
        preserved by the shard_map subsystem).  ``per_shard_buckets=False``
        pins the uniform-tuple ladder: exactly len(ladder) executables,
        keyed by per-shard local-capacity tuples."""
        cfg = _serve_cfg("pallas", buckets=(0.25, 0.5, 1.0))
        cfg = cfg.replace(sparse=dataclasses.replace(
            cfg.sparse, alpha_base=0.3, alpha_early=0.3))
        ccfg = ControllerConfig(enabled=True, gain=0.0, fn_gain=0.0,
                                per_shard_buckets=False)
        srv = Server(lm, cfg,
                     ServeConfig(batch=2, max_len=64, controller=ccfg,
                                 warm_buckets=True),
                     lm.init_lm(jax.random.PRNGKey(0), cfg), mesh=_mesh())
        # global {128, 256} MXU-aligned + deduped -> local C/ms tuples
        assert set(srv._bucket_fns) == {(32,) * MS, (64,) * MS}
        done = srv.serve(_reqs())
        assert all(len(r.out) == 5 for r in done)
        # alpha 0.3 predicts almost nothing -> smallest bucket on all shards
        assert srv._active_cap == (32,) * MS, dict(srv._trace_counts)
        assert all(c == 1 for c in srv._trace_counts.values()), \
            dict(srv._trace_counts)

    def test_mesh_requires_sparse_strategy(self):
        cfg = CFG_LM.replace(sparse=dataclasses.replace(
            CFG_LM.sparse, enabled=False))
        with pytest.raises(ValueError, match="mesh serving"):
            Server(lm, cfg, ServeConfig(batch=2, max_len=64),
                   lm.init_lm(jax.random.PRNGKey(0), cfg), mesh=_mesh())

    def test_skew_report(self):
        cfg = _serve_cfg("gather")
        ccfg = ControllerConfig(enabled=True, target_density=0.25)
        srv = Server(lm, cfg, ServeConfig(batch=2, max_len=64,
                                          controller=ccfg),
                     lm.init_lm(jax.random.PRNGKey(0), cfg), mesh=_mesh())
        srv.serve(_reqs())
        rep = srv.controller.report()
        assert rep["n_shards"] == MS
        skew = rep["shard_skew"]
        assert len(skew["per_layer_skew"]) == cfg.n_layers
        assert skew["max_skew"] >= 0.0
        assert len(skew["mean_shard_density"]) == MS


DS = 4
needs_mesh8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 host-platform devices (conftest XLA_FLAGS)")

# semantic grid pinned in CONFIG (dp_shards=4, tp_shards=4): every
# placement below executes the exact same (data, model) semantics
CFG_2D = CFG_LM.replace(sparse=dataclasses.replace(
    CFG_LM.sparse, group_size=1, tp_shards=MS, dp_shards=DS))

PLACEMENTS = [((1, MS), ("data", "model")),
              ((DS, 1), ("data", "model")),
              ((2, MS), ("data", "model"))]


@needs_mesh8
class TestMesh2DServer:
    """Acceptance pin: greedy tokens and ALL controller telemetry are
    bitwise-identical across 1-device emulation, 1×4, 4×1 and 2×4
    placements of the same (dp_shards=4, tp_shards=4) semantics, for all
    three strategies."""

    @pytest.mark.parametrize("strategy", STRATEGIES)
    def test_serve_bitwise_across_placements(self, strategy):
        cfg = CFG_2D.replace(sparse=dataclasses.replace(
            CFG_2D.sparse, strategy=strategy))
        params = lm.init_lm(jax.random.PRNGKey(0), cfg)
        ccfg = ControllerConfig(enabled=True, target_density=0.25,
                                audit_period=3)
        scfg = ServeConfig(batch=DS, max_len=64, controller=ccfg)

        def reqs():
            rng = np.random.default_rng(0)
            return [Request(uid=i, prompt=rng.integers(0, 128, size=6),
                            max_new=3) for i in range(5)]

        srv_e = Server(lm, cfg, scfg, params)
        done_e = srv_e.serve(reqs())
        for shape, axes in PLACEMENTS:
            srv_m = Server(lm, cfg, scfg, params,
                           mesh=make_mesh(shape, axes))
            done_m = srv_m.serve(reqs())
            for a, b in zip(done_e, done_m):
                np.testing.assert_array_equal(
                    a.out, b.out, err_msg=f"{strategy} tokens @ {shape}")
            for name in ("alphas", "density_ema", "fn_ema", "union_ema",
                         "predicted_ema"):
                np.testing.assert_array_equal(
                    getattr(srv_e.controller.state, name),
                    getattr(srv_m.controller.state, name),
                    err_msg=f"{strategy} {name} @ {shape}")
            np.testing.assert_array_equal(
                srv_e.controller.shard_density_ema,
                srv_m.controller.shard_density_ema,
                err_msg=f"{strategy} shard_density_ema @ {shape}")
            np.testing.assert_array_equal(
                srv_e.controller.shard_union_ema,
                srv_m.controller.shard_union_ema,
                err_msg=f"{strategy} shard_union_ema @ {shape}")

    def test_2d_placed_prefill_matches_unplaced(self):
        """Regression pin for the 2D param-placement workaround: jax
        0.4.37's SPMD partitioner miscomputes prefill when q/k projections
        are column-sharded sub-head over 'model' while a 'data' axis is
        present; ``serve_param_shardings`` therefore replicates the
        attention/embed leaves on 2D meshes (sharding/sparse.py).  Placed
        and unplaced prefill must agree to float noise — a ~1.0-magnitude
        logit error means the workaround regressed."""
        from repro.sharding import sparse as SSP
        cfg = CFG_2D.replace(sparse=dataclasses.replace(
            CFG_2D.sparse, strategy="gather"))
        params = lm.prepare_sparse(lm.init_lm(jax.random.PRNGKey(0), cfg))
        prompts = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0,
                                     cfg.vocab)
        fn = jax.jit(lambda p, t: lm.prefill(p, cfg, t, max_len=64)[0])
        ref = np.asarray(fn(params, prompts))
        with make_mesh((2, MS), ("data", "model")) as mesh:
            placed = SSP.place_serve_params(params, mesh)
            got = np.asarray(fn(placed, prompts))
        np.testing.assert_allclose(ref, got, atol=1e-4, rtol=1e-4)
        # ...and the sparse-MLP leaves did keep their row sharding
        spec = placed["blocks"]["mlp"]["wg_t"].sharding.spec
        assert "model" in tuple(spec), spec

    def test_mesh_must_divide_semantics(self):
        """A mesh axis that does not divide the semantic shard count is
        rejected (3 does not divide dp_shards=4)."""
        cfg = CFG_2D.replace(sparse=dataclasses.replace(
            CFG_2D.sparse, dp_shards=3))
        with pytest.raises(ValueError, match="data"):
            Server(lm, cfg, ServeConfig(batch=6, max_len=64),
                   lm.init_lm(jax.random.PRNGKey(0), cfg),
                   mesh=make_mesh((2, MS), ("data", "model")))

    def test_batch_must_divide_data_shards(self):
        with pytest.raises(ValueError, match="batch"):
            Server(lm, CFG_2D, ServeConfig(batch=3, max_len=64),
                   lm.init_lm(jax.random.PRNGKey(0), CFG_2D),
                   mesh=make_mesh((2, MS), ("data", "model")))


class TestPerShardBuckets:
    """Tentpole: per-shard adaptive capacity buckets — one pre-jitted
    executable per bucket TUPLE, controller-driven per-shard rung
    selection, zero retraces on switches."""

    def _srv(self, per_shard=True, cap=16, mesh=None, warm=False):
        cfg = _serve_cfg("gather", buckets=(0.25, 1.0))
        cfg = cfg.replace(sparse=dataclasses.replace(
            cfg.sparse, tp_shards=2, dp_shards=2))
        ccfg = ControllerConfig(enabled=True, target_density=0.25,
                                per_shard_buckets=per_shard,
                                bucket_tuple_cap=cap)
        return Server(lm, cfg,
                      ServeConfig(batch=2, max_len=64, controller=ccfg,
                                  warm_buckets=warm),
                      lm.init_lm(jax.random.PRNGKey(0), cfg), mesh=mesh)

    def test_tuple_ladder_is_full_product(self):
        srv = self._srv()
        # global ladder {128, 256} -> local {64, 128} over 2 shards
        assert set(srv._bucket_fns) == {(64, 64), (64, 128),
                                        (128, 64), (128, 128)}
        assert srv._per_shard_buckets

    def test_tuple_cap_falls_back_to_uniform(self):
        with pytest.warns(UserWarning, match="bucket_tuple_cap"):
            srv = self._srv(cap=3)
        assert set(srv._bucket_fns) == {(64, 64), (128, 128)}
        assert not srv._per_shard_buckets

    def test_per_shard_switch_zero_retrace(self):
        """Driving the controller's per-shard union EMAs to a skewed
        profile switches to a HETEROGENEOUS bucket tuple; every executable
        traces at most once, and switching back adds zero traces."""
        srv = self._srv()
        srv.serve(_reqs(n=2, max_new=4))
        ctl = srv.controller
        # force a skewed per-shard union-demand profile: shard 0 narrow,
        # shard 1 wide (k_local = 128 neurons; ladder local rungs 64/128)
        ctl.shard_union_ema = np.array([[0.1, 0.9]] * 2, np.float32)
        assert srv._select_bucket() == (64, 128)
        before = dict(srv._trace_counts)
        srv.serve(_reqs(n=1, max_new=3))
        ctl.shard_union_ema = np.array([[0.9, 0.1]] * 2, np.float32)
        assert srv._select_bucket() == (128, 64)
        srv.serve(_reqs(n=1, max_new=3))
        # back to the first tuple: already traced, must not trace again
        ctl.shard_union_ema = np.array([[0.1, 0.9]] * 2, np.float32)
        assert srv._select_bucket() == (64, 128)
        srv.serve(_reqs(n=1, max_new=3))
        assert all(c == 1 for c in srv._trace_counts.values()), \
            dict(srv._trace_counts)
        assert (64, 128) in srv._trace_counts
        assert (128, 64) in srv._trace_counts
        assert before  # the initial serve traced at least one tuple

    @needs_mesh
    def test_heterogeneous_tuple_bitwise_on_mesh(self):
        """A heterogeneous shard_bucket_caps tuple is bitwise-identical
        between the shard_map execution and the emulation — the clamp is
        part of the semantics, not the placement."""
        params = _params(21)
        x = jax.random.normal(jax.random.PRNGKey(22), (4, D))
        for strategy in ("gather", "pallas"):
            cfg = _cfg(strategy, capacity_frac=1.0)
            cfg = dataclasses.replace(cfg, dp_shards=2,
                                      shard_bucket_caps=(2, 8, 4, 8),
                                      capacity_override=32)
            y_ref, st_ref = SM.apply(params, x, cfg, alpha=1.0,
                                     return_stats=True)
            with _mesh():
                y_sh, st_sh = jax.jit(
                    lambda p, xx: SM.apply(p, xx, cfg, alpha=1.0,
                                           return_stats=True))(params, x)
            np.testing.assert_array_equal(np.asarray(y_ref),
                                          np.asarray(y_sh))
            _assert_tree_equal(st_ref, st_sh, f"hetero:{strategy}")

    def test_degenerate_grid_warning_fires_once_per_bucket_shard(
            self, monkeypatch):
        """Satellite: the degenerate-grid warning is deduplicated per
        (bucket, shard) — repeated bucket switches across decode steps
        must not re-warn."""
        from repro.kernels import ops as kops

        def boom(*a, **kw):
            raise ValueError("forced degenerate tile")

        srv = self._srv()
        monkeypatch.setattr(kops, "choose_blocks", boom)
        srv.cfg = srv.cfg.replace(sparse=dataclasses.replace(
            srv.cfg.sparse, strategy="pallas"))
        srv._grid_warned.clear()
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            for _ in range(5):                # five "decode steps"
                srv._check_shard_grids((64, 128))
            srv._check_shard_grids((128, 128))  # one NEW (bucket, shard)
        msgs = [str(w.message) for w in rec
                if "degenerate" in str(w.message)]
        # (64, s0), (128, s1) from the first tuple; (128, s0) new; the
        # repeated (128, s1) is deduped
        assert len(msgs) == 3, msgs

    def test_degenerate_grid_raises_on_tpu(self, monkeypatch):
        """On a TPU backend the compiled kernels never run the jnp oracle,
        so an untileable shard grid is an error, not a warning."""
        from repro.kernels import ops as kops
        from repro.runtime import server as server_mod

        def boom(*a, **kw):
            raise ValueError("forced degenerate tile")

        srv = self._srv()
        monkeypatch.setattr(kops, "choose_blocks", boom)
        monkeypatch.setattr(server_mod.jax, "default_backend",
                            lambda: "tpu")
        srv.cfg = srv.cfg.replace(sparse=dataclasses.replace(
            srv.cfg.sparse, strategy="pallas"))
        srv._grid_warned.clear()
        with pytest.raises(ValueError, match="no TPU kernel tiling"):
            srv._check_shard_grids((64, 128))


class TestControllerPersistence:
    """Satellite: controller state survives server restarts (ROADMAP item).
    Works identically with and without a mesh — state is host numpy."""

    def test_server_restart_resumes_state(self, tmp_path):
        cfg = CFG_LM.replace(sparse=dataclasses.replace(
            CFG_LM.sparse, strategy="masked", group_size=1))
        params = lm.init_lm(jax.random.PRNGKey(0), cfg)
        ccfg = ControllerConfig(enabled=True, target_density=0.2,
                                audit_period=3)
        scfg = ServeConfig(batch=2, max_len=64, controller=ccfg,
                           controller_ckpt=str(tmp_path))
        srv1 = Server(lm, cfg, scfg, params)
        srv1.serve(_reqs())
        steps1 = srv1.controller.state.steps
        assert steps1 > 0
        srv2 = Server(lm, cfg, scfg, params)     # "restart"
        assert srv2.controller.state.steps == steps1
        np.testing.assert_array_equal(srv2.controller.alphas(),
                                      srv1.controller.alphas())
        np.testing.assert_array_equal(srv2.controller.state.density_ema,
                                      srv1.controller.state.density_ema)
        np.testing.assert_array_equal(srv2.controller.state.fn_ema,
                                      srv1.controller.state.fn_ema)
        # ...and serving continues from the restored state
        srv2.serve(_reqs())
        assert srv2.controller.state.steps > steps1

    def test_distributed_controller_roundtrip(self, tmp_path):
        from repro.checkpoint.manager import CheckpointManager
        from repro.core.predictor import AlphaSchedule
        cc = ControllerConfig(enabled=True, ema=1.0)
        ctl = DistributedController(
            AlphaController(cc, AlphaSchedule(), 3), MS)
        stats = {k: np.full((3, 2), 0.4, np.float32) for k in MLP_STAT_KEYS}
        stats[SHARD_STAT_KEY] = np.tile(
            np.linspace(0.1, 0.4, MS, dtype=np.float32), (3, 2, 1))
        rest = ctl.consume_shard_stats(stats)
        assert SHARD_STAT_KEY not in rest
        ctl.observe({k: v.mean(-1) for k, v in rest.items()})
        mgr = CheckpointManager(str(tmp_path))
        save_controller(ctl, mgr)
        ctl2 = DistributedController(
            AlphaController(cc, AlphaSchedule(), 3), MS)
        assert restore_controller(ctl2, mgr)
        np.testing.assert_array_equal(ctl2.shard_density_ema,
                                      ctl.shard_density_ema)
        np.testing.assert_array_equal(ctl2.alphas(), ctl.alphas())
        assert ctl2.state.steps == ctl.state.steps
        # skew of the linspace profile is positive and ordered
        assert ctl2.shard_skew()["max_skew"] > 0

    def test_restore_empty_dir_is_fresh_start(self, tmp_path):
        from repro.checkpoint.manager import CheckpointManager
        from repro.core.predictor import AlphaSchedule
        ctl = AlphaController(ControllerConfig(enabled=True),
                              AlphaSchedule(), 2)
        assert not restore_controller(ctl, CheckpointManager(str(tmp_path)))

    def test_topology_regrid_remaps_shard_emas(self, tmp_path):
        """Elastic restart (DESIGN.md §11): a checkpoint from a different
        model-shard count is ABSORBED — per-(layer, shard) EMAs are
        remapped by tile-overlap-weighted average (mean-preserving), not
        rejected — with a warning recording the regrid."""
        from repro.checkpoint.manager import CheckpointManager
        from repro.core.predictor import AlphaSchedule
        cc = ControllerConfig(enabled=True)
        ctl = DistributedController(AlphaController(cc, AlphaSchedule(), 2),
                                    MS)
        ctl.shard_density_ema = np.tile(
            np.linspace(0.1, 0.4, MS, dtype=np.float32), (2, 1))
        ctl.shard_union_ema = np.tile(
            np.linspace(0.5, 0.8, MS, dtype=np.float32), (2, 1))
        ctl._shard_steps = 7
        mgr = CheckpointManager(str(tmp_path))
        save_controller(ctl, mgr)
        ctl2 = DistributedController(AlphaController(cc, AlphaSchedule(), 2),
                                     2)
        with pytest.warns(UserWarning, match="elastic restart"):
            assert restore_controller(ctl2, mgr)
        assert ctl2.stats_regrids == 1
        assert ctl2._shard_steps == 7
        assert ctl2.shard_density_ema.shape == (2, 2)
        # MS -> 2 halves the tiles: each new shard averages adjacent pairs
        np.testing.assert_allclose(
            ctl2.shard_density_ema,
            ctl.shard_density_ema.reshape(2, 2, MS // 2).mean(-1),
            rtol=1e-6)
        # mean-preserving: skew metrics and capacity hints resume honestly
        np.testing.assert_allclose(ctl2.shard_density_ema.mean(-1),
                                   ctl.shard_density_ema.mean(-1), rtol=1e-6)
        np.testing.assert_allclose(ctl2.shard_union_ema.mean(-1),
                                   ctl.shard_union_ema.mean(-1), rtol=1e-6)
        # the inner (grid-independent) state transferred untouched
        np.testing.assert_array_equal(ctl2.alphas(), ctl.alphas())

    def test_2d_topology_regrid_remaps_and_converges(self, tmp_path):
        """Elastic restart across (data, model) grids: every regrid of a
        2xMS checkpoint restores (warning + remap), a matching grid
        restores silently, and controllers resumed on DIFFERENT grids
        adapt to the same alpha targets when fed the same telemetry —
        the inner update law is grid-independent (ISSUE acceptance)."""
        from repro.checkpoint.manager import CheckpointManager
        from repro.core.predictor import AlphaSchedule
        cc = ControllerConfig(enabled=True, target_density=0.3)
        ctl = DistributedController(AlphaController(cc, AlphaSchedule(), 2),
                                    MS, n_data_shards=2)
        ctl.shard_density_ema = np.tile(
            np.linspace(0.1, 0.4, MS, dtype=np.float32), (2, 1))
        mgr = CheckpointManager(str(tmp_path))
        save_controller(ctl, mgr)
        resumed = []
        for ms, ds in ((MS, 1), (MS, 4), (2, 2), (1, 4)):
            new = DistributedController(
                AlphaController(cc, AlphaSchedule(), 2), ms,
                n_data_shards=ds)
            with pytest.warns(UserWarning, match="elastic restart"):
                assert restore_controller(new, mgr)
            assert new.stats_regrids == 1
            assert new.shard_density_ema.shape == (2, ms)
            np.testing.assert_allclose(
                new.shard_density_ema.mean(-1),
                ctl.shard_density_ema.mean(-1), rtol=1e-6)
            resumed.append(new)
        # the SAME grid restores silently, without a regrid
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            ok = DistributedController(
                AlphaController(cc, AlphaSchedule(), 2), MS,
                n_data_shards=2)
            assert restore_controller(ok, mgr)
        assert ok.stats_regrids == 0
        # convergence: identical telemetry -> identical adapted alphas
        stats = {k: np.full((2,), 0.45, np.float32)
                 for k in MLP_STAT_KEYS}
        for _ in range(16):
            for c in resumed:
                c.observe(stats)
        for c in resumed[1:]:
            np.testing.assert_array_equal(c.alphas(), resumed[0].alphas())

    def test_remap_shard_ema_identity_and_uneven(self):
        ema = np.arange(8, dtype=np.float32).reshape(2, 4)
        same = remap_shard_ema(ema, 4)
        np.testing.assert_array_equal(same, ema)
        assert same is not ema          # defensive copy
        up = remap_shard_ema(ema, 8)    # refine: each tile splits in two
        np.testing.assert_allclose(up, np.repeat(ema, 2, axis=1))
        down = remap_shard_ema(ema, 1)  # collapse: global mean
        np.testing.assert_allclose(down, ema.mean(-1, keepdims=True))
        # uneven 4 -> 3: rows of the overlap matrix still sum to 1
        odd = remap_shard_ema(ema, 3)
        np.testing.assert_allclose(odd.mean(-1), ema.mean(-1), rtol=1e-6)

    @needs_mesh8
    def test_2d_mesh_server_restart_resumes_per_shard_state(self, tmp_path):
        """Satellite: the per-shard bucket state (density AND union-demand
        EMAs) round-trips through CheckpointManager across a 2D-mesh
        server restart, and the restored EMAs steer the first
        _select_bucket."""
        cfg = CFG_2D.replace(sparse=dataclasses.replace(
            CFG_2D.sparse, strategy="gather",
            capacity_buckets=(0.25, 1.0)))
        params = lm.init_lm(jax.random.PRNGKey(0), cfg)
        ccfg = ControllerConfig(enabled=True, target_density=0.25)
        scfg = ServeConfig(batch=DS, max_len=64, controller=ccfg,
                           controller_ckpt=str(tmp_path))
        mesh = make_mesh((2, MS), ("data", "model"))
        srv1 = Server(lm, cfg, scfg, params, mesh=mesh)
        srv1.serve(_reqs(n=4, max_new=3))
        assert srv1.controller._shard_steps > 0
        srv2 = Server(lm, cfg, scfg, params,
                      mesh=make_mesh((2, MS), ("data", "model")))
        np.testing.assert_array_equal(srv2.controller.shard_density_ema,
                                      srv1.controller.shard_density_ema)
        np.testing.assert_array_equal(srv2.controller.shard_union_ema,
                                      srv1.controller.shard_union_ema)
        assert srv2.controller.n_data_shards == DS
        assert srv2._active_cap == srv1._active_cap
        srv2.serve(_reqs(n=2, max_new=3))
        assert srv2.controller.state.steps > srv1.controller.state.steps


# ---------------------------------------------------------------------------
# int8 quantized serving on the 2D mesh (DESIGN.md §13)
# ---------------------------------------------------------------------------

# qg=32 divides d_model=64, d_ff=256 AND the per-shard rows k/ms=64, so
# every model shard owns whole wd quant row-groups (validate_shardable)
CFG_2D_Q = CFG_2D.replace(sparse=dataclasses.replace(
    CFG_2D.sparse, strategy="pallas", weight_dtype="int8",
    quant_group_size=32))


@pytest.mark.quant
@needs_mesh8
class TestMesh2DServerInt8:
    """The PR 10 mesh acceptance pin: int8 end-to-end serving on real
    (data x model) placements is bitwise-identical to the single-device
    int8 emulation — greedy tokens, every controller telemetry leaf, the
    per-shard riders — and a warmed bucket ladder stays retrace-silent."""

    @pytest.mark.parametrize("strategy", ("gather", "pallas"))
    def test_int8_serve_bitwise_across_placements(self, strategy):
        cfg = CFG_2D_Q.replace(sparse=dataclasses.replace(
            CFG_2D_Q.sparse, strategy=strategy))
        params = lm.init_lm(jax.random.PRNGKey(0), cfg)
        ccfg = ControllerConfig(enabled=True, target_density=0.25,
                                audit_period=3)
        scfg = ServeConfig(batch=DS, max_len=64, controller=ccfg)

        def reqs():
            rng = np.random.default_rng(0)
            return [Request(uid=i, prompt=rng.integers(0, 128, size=6),
                            max_new=3) for i in range(5)]

        srv_e = Server(lm, cfg, scfg, params)
        done_e = srv_e.serve(reqs())
        for shape, axes in PLACEMENTS:
            srv_m = Server(lm, cfg, scfg, params,
                           mesh=make_mesh(shape, axes))
            done_m = srv_m.serve(reqs())
            for a, b in zip(done_e, done_m):
                np.testing.assert_array_equal(
                    a.out, b.out, err_msg=f"int8 {strategy} tokens @ {shape}")
            for name in ("alphas", "density_ema", "fn_ema", "union_ema",
                         "predicted_ema"):
                np.testing.assert_array_equal(
                    getattr(srv_e.controller.state, name),
                    getattr(srv_m.controller.state, name),
                    err_msg=f"int8 {strategy} {name} @ {shape}")
            np.testing.assert_array_equal(
                srv_e.controller.shard_density_ema,
                srv_m.controller.shard_density_ema,
                err_msg=f"int8 {strategy} shard_density_ema @ {shape}")
            np.testing.assert_array_equal(
                srv_e.controller.shard_union_ema,
                srv_m.controller.shard_union_ema,
                err_msg=f"int8 {strategy} shard_union_ema @ {shape}")

    def test_int8_bucket_ladder_no_retrace_on_mesh(self):
        """One executable per capacity bucket for the int8 path too: every
        bucket traced exactly once at warmup, zero post-warmup retraces
        across bucket switches on the 2x4 mesh."""
        from repro.configs.base import MetricsConfig
        cfg = CFG_2D_Q.replace(sparse=dataclasses.replace(
            CFG_2D_Q.sparse, capacity_buckets=(0.25, 0.5, 1.0),
            alpha_base=0.3, alpha_early=0.3))
        ccfg = ControllerConfig(enabled=True, gain=0.0, fn_gain=0.0,
                                per_shard_buckets=False)
        srv = Server(lm, cfg,
                     ServeConfig(batch=DS, max_len=64, controller=ccfg,
                                 warm_buckets=True,
                                 metrics=MetricsConfig(enabled=True)),
                     lm.init_lm(jax.random.PRNGKey(0), cfg),
                     mesh=make_mesh((2, MS), ("data", "model")))
        try:
            done = srv.serve(_reqs(n=4, max_new=3))
            assert all(len(r.out) == 3 for r in done)
            srv.serve(_reqs(n=8, max_new=3))
            assert srv.metrics.watchdog.retraces_post_warmup == 0
            assert srv.metrics.counter_value("retrace_post_warmup") == 0
            assert all(c == 1 for c in srv._trace_counts.values()), \
                dict(srv._trace_counts)
        finally:
            srv.metrics.close()

    def test_int8_rejects_indivisible_quant_groups(self):
        """validate_shardable fails fast when a shard would split a wd
        quant row-group: k/ms=64 is not divisible by qg=128."""
        from repro.sharding import sparse as SSP
        bad = dataclasses.replace(CFG_2D_Q.sparse, quant_group_size=128)
        with pytest.raises(ValueError, match="quant_group_size"):
            SSP.validate_shardable(bad, K, MS)
