"""Paged KV pool serving (DESIGN.md §10).

Parity + regression suite for the paged-KV serve path:

- kernel pins: the pallas page-gather attention and page-scatter write
  (kernels/paged_attn.py) are BITWISE against the dense oracles in
  kernels/ref.py — softcap/window variants, bf16 pools, write collisions,
  int8 / oversized-pool fallback routing;
- pool-manager unit tests (runtime/kv_pool.py): refcount protocol,
  commit/dedup, copy-on-write forking, the parked-LRU -> session ->
  RuntimeError eviction cascade, session LRU caps, invariants;
- the property sweep: paged decode over randomized fragmented pools and
  block tables — including COW-style shared-prefix tables — is bitwise
  the dense per-slot decode, on the jnp gather path AND the pallas
  kernel route;
- server-level parity: paged vs dense serve is token- and
  controller-telemetry-bitwise across sparse strategies, monolithic and
  chunked prefill, single-device and the 2x4 (data x model) mesh;
- prefix-cache reuse: a second request sharing a committed prefix admits
  with most prefill chunks skipped and still emits bitwise the tokens of
  a from-scratch serve (the adopted blocks are prefill-origin, so
  re-prefill IS the oracle); session continuation, sticky SLA tiers,
  COW divergence past the reuse boundary;
- the serve-path bugfix satellites: throughput_report zero/NaN guards,
  latency-stamp reset on re-admission of the same Request objects, the
  jax-version gate on the 2D q/k sharding workaround, and the
  structural-vs-timing bench diff gate (benchmarks/bench_diff.py).
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, strategies as st

from repro.configs.base import ControllerConfig, ModelConfig, PagedKVConfig
from repro.configs.registry import default_sparse
from repro.kernels import ops, ref
from repro.kernels import paged_attn as PA
from repro.launch.mesh import make_mesh
from repro.layers import attention as A
from repro.models import lm
from repro.runtime.kv_pool import KVPool, PoolExhausted
from repro.runtime.server import (Request, Server, ServeConfig,
                                  throughput_report)

jax.config.update("jax_platform_name", "cpu")

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 host-platform devices (conftest XLA_FLAGS)")

CFG = ModelConfig(name="tiny-paged", family="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab=128, max_seq=256,
                  dtype="float32", param_dtype="float32",
                  kv_cache_dtype="float32", attn_chunk=256, loss_chunk=64,
                  remat=False)

_PARAMS: dict = {}


def params_for(cfg):
    if cfg.name not in _PARAMS:
        _PARAMS[cfg.name] = lm.init_lm(jax.random.PRNGKey(0), cfg)
    return _PARAMS[cfg.name]


def sparse_cfg(strategy):
    return CFG.replace(
        name=f"tiny-paged-{strategy}", activation="relu",
        sparse=dataclasses.replace(default_sparse(activation="relu"),
                                   strategy=strategy, group_size=8,
                                   capacity_frac=0.5))


def make_requests(rng, plens, max_new=6, slas=None, sessions=None):
    return [Request(uid=i, prompt=rng.integers(0, CFG.vocab, size=p),
                    max_new=max_new,
                    sla=(slas[i] if slas else "balanced"),
                    session_id=(sessions[i] if sessions else None))
            for i, p in enumerate(plens)]


def outs(done):
    return {r.uid: np.asarray(r.out) for r in done}


def assert_same_tokens(a, b, msg=""):
    assert set(a) == set(b)
    for uid in a:
        np.testing.assert_array_equal(a[uid], b[uid],
                                      err_msg=f"uid={uid} {msg}")


# ------------------------------------------------------------ kernels ------

class TestPagedKernels:
    """kernels/paged_attn.py vs the kernels/ref.py dense oracles."""

    def _pool(self, rng, n, bs, kvh, hd, dtype=np.float32):
        k = rng.standard_normal((n, bs, kvh, hd)).astype(dtype)
        v = rng.standard_normal((n, bs, kvh, hd)).astype(dtype)
        return jnp.asarray(k), jnp.asarray(v)

    @pytest.mark.parametrize("softcap,window", [(0.0, 0), (5.0, 0),
                                                (0.0, 11), (5.0, 11)])
    def test_attention_bitwise_vs_ref(self, softcap, window):
        rng = np.random.default_rng(0)
        b, h, kvh, hd, n, bs, nbps = 3, 4, 2, 8, 12, 4, 3
        kp, vp = self._pool(rng, n, bs, kvh, hd)
        q = jnp.asarray(rng.standard_normal((b, h, hd)).astype(np.float32))
        table = jnp.asarray(
            rng.permutation(n - 1)[: b * nbps].reshape(b, nbps) + 1,
            jnp.int32)
        lengths = jnp.asarray([2, 7, 10], jnp.int32)
        got = PA.paged_attention(q, kp, vp, table, lengths,
                                 softcap=softcap, window=window,
                                 interpret=True)
        want = ref.paged_attention_ref(q, kp, vp, table, lengths,
                                       softcap=softcap, window=window)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_attention_bf16_pool_bitwise(self):
        rng = np.random.default_rng(1)
        b, h, kvh, hd, n, bs, nbps = 2, 2, 1, 4, 7, 4, 2
        kp, vp = self._pool(rng, n, bs, kvh, hd)
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
        q = jnp.asarray(rng.standard_normal((b, h, hd)).astype(np.float32))
        table = jnp.asarray([[2, 3], [4, 6]], jnp.int32)
        lengths = jnp.asarray([3, 6], jnp.int32)
        got = ops.paged_attention(q, kp, vp, table, lengths)
        want = ref.paged_attention_ref(q, kp, vp, table, lengths)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_kv_write_bitwise_and_collisions(self):
        rng = np.random.default_rng(2)
        pages = jnp.asarray(rng.standard_normal((6, 4, 2, 4)).astype(
            np.float32))
        vals = jnp.asarray(rng.standard_normal((4, 2, 4)).astype(np.float32))
        # slots 1 and 3 collide on (block 5, row 2): sequential grid means
        # the last slot wins — exactly the jnp .at[].set scatter semantics
        blocks = jnp.asarray([2, 5, 3, 5], jnp.int32)
        offsets = jnp.asarray([0, 2, 3, 2], jnp.int32)
        got = PA.paged_kv_write(pages, vals, blocks, offsets, interpret=True)
        want = ref.paged_kv_write_ref(pages, vals, blocks, offsets)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_int8_pool_routes_to_oracle(self):
        rng = np.random.default_rng(3)
        n, bs, kvh, hd, b = 5, 4, 1, 4, 2
        kp = jnp.asarray(rng.integers(-127, 127, (n, bs, kvh, hd)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 127, (n, bs, kvh, hd)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, (n, bs, kvh)).astype(
            np.float32))
        vs = jnp.asarray(rng.uniform(0.01, 0.1, (n, bs, kvh)).astype(
            np.float32))
        q = jnp.asarray(rng.standard_normal((b, 2, hd)).astype(np.float32))
        table = jnp.asarray([[2, 3], [4, 2]], jnp.int32)
        lengths = jnp.asarray([5, 1], jnp.int32)
        got = ops.paged_attention(q, kp, vp, table, lengths, ks, vs)
        want = ref.paged_attention_ref(q, kp, vp, table, lengths, ks, vs)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_oversized_pool_falls_back(self):
        # a pool past the VMEM ceiling must raise in check_tiling (the ops
        # wrapper then silently takes the oracle path)
        with pytest.raises(ValueError):
            PA.check_tiling(1 << 20, 128, 8, 128, 4, 8)
        with pytest.raises(ValueError):
            PA.check_tiling(4, 4, 3, 8, 4, 8)   # heads not a kv multiple


# ----------------------------------------------------------- pool mgr ------

class TestKVPool:
    def test_alloc_park_revive_free(self):
        p = KVPool(6, 8)
        a, b = p.alloc(), p.alloc()
        assert a == KVPool._RESERVED and b == a + 1
        h = p.block_hashes(b"", np.arange(8))[0]
        [a2] = p.commit_chain([h], [a])
        assert a2 == a
        p.decref(a)                      # committed: parks, stays matchable
        assert p.match_prefix(b"", np.arange(8)) == [a]
        p.incref(a)                      # revived from the park
        p.decref(a)
        p.decref(b)                      # uncommitted: frees immediately
        assert p.snapshot()["free_blocks"] == 3
        p.check_invariants()

    def test_commit_dedup_moves_reference(self):
        p = KVPool(8, 4)
        toks = np.arange(8)
        h = p.block_hashes(b"", toks)
        c1 = p.commit_chain(h, [p.alloc(), p.alloc()])
        c2 = p.commit_chain(h, [p.alloc(), p.alloc()])
        assert c1 == c2                  # dedup resolved to the canonical ids
        assert p.stats["dedup_blocks"] == 2
        assert p.refcount[c1[0]] == 2
        for b in c1 + c2:
            p.decref(b)
        p.check_invariants()

    def test_salt_separates_chains(self):
        p = KVPool(8, 4)
        toks = np.arange(4)
        c = p.commit_chain(p.block_hashes(b"salty", toks), [p.alloc()])
        assert p.match_prefix(b"salty", toks) == c
        assert p.match_prefix(b"", toks) == []

    def test_cow_fork(self):
        p = KVPool(8, 4)
        a = p.alloc()
        wid, src = p.ensure_writable(a)   # exclusive + uncommitted: in place
        assert (wid, src) == (a, None)
        p.incref(a)                       # now shared
        wid, src = p.ensure_writable(a)
        assert wid != a and src == a and p.stats["cow_forks"] == 1
        assert p.refcount[a] == 1 and p.refcount[wid] == 1
        [a] = p.commit_chain(p.block_hashes(b"", np.arange(4)), [a])
        wid2, src2 = p.ensure_writable(a)  # committed: never in place
        assert wid2 != a and src2 == a
        p.check_invariants()

    def test_eviction_cascade(self):
        p = KVPool(2 + 3, 4, max_sessions=4)
        a = p.alloc()
        [a] = p.commit_chain(p.block_hashes(b"", np.arange(4)), [a])
        p.decref(a)                       # parked (evictable, matchable)
        b = p.alloc()
        p.store_session("s", [b], np.arange(4), "balanced")
        c = p.alloc()                     # free list now empty
        d = p.alloc()                     # reclaims the parked block first
        assert d == a and p.stats["evicted_blocks"] == 1
        assert p.match_prefix(b"", np.arange(4)) == []   # uncommitted now
        e = p.alloc()                     # then evicts the LRU session
        assert e == b and p.stats["evicted_sessions"] == 1
        assert p.lookup_session("s") is None
        with pytest.raises(RuntimeError):
            p.alloc()                     # all live references: hard stop
        for x in (c, d, e):
            p.decref(x)
        p.check_invariants()

    def test_session_lru_cap_and_replace(self):
        p = KVPool(12, 4, max_sessions=2)
        blocks = {}
        for i, sid in enumerate(("s0", "s1", "s2")):
            b = p.alloc()
            blocks[sid] = b
            p.store_session(sid, [b], np.arange(4) + i, "quality")
        assert p.lookup_session("s0") is None      # LRU-capped out
        assert p.refcount[blocks["s0"]] == 0
        sess = p.lookup_session("s2")
        assert sess["tier"] == "quality"
        b2 = p.alloc()
        p.store_session("s2", [b2], np.arange(4), "latency")  # replace
        assert p.refcount[blocks["s2"]] == 0
        p.drop_session("s1")
        p.drop_session("s2")
        p.check_invariants()


class TestKVPoolIdHardening:
    """Satellite: every refcount entry point validates its block id —
    reserved (NULL/TRASH), negative, and out-of-range ids raise instead of
    silently corrupting pool state."""

    @pytest.mark.parametrize("bad", [KVPool.NULL, KVPool.TRASH, -1, -7])
    def test_reserved_and_negative_ids_rejected(self, bad):
        p = KVPool(8, 4)
        a = p.alloc()                    # a live block: pool is in use
        for fn in (p.incref, p.decref, p.release):
            with pytest.raises(ValueError):
                fn(bad)
        with pytest.raises(ValueError):
            p.ensure_writable(bad)
        p.decref(a)
        p.check_invariants()

    @pytest.mark.parametrize("bad", [8, 9, 10**9])
    def test_out_of_range_ids_rejected(self, bad):
        p = KVPool(8, 4)
        for fn in (p.incref, p.decref, p.release):
            with pytest.raises(ValueError):
                fn(bad)
        with pytest.raises(ValueError):
            p.ensure_writable(bad)
        p.check_invariants()

    def test_numpy_integer_ids_accepted(self):
        # block tables are int32 numpy rows: ids arrive as np scalars
        p = KVPool(8, 4)
        a = p.alloc()
        p.incref(np.int64(a))
        p.decref(np.int32(a))
        p.decref(a)
        p.check_invariants()

    def test_double_free_still_raises(self):
        p = KVPool(8, 4)
        a = p.alloc()
        p.decref(a)                      # uncommitted: frees
        with pytest.raises(RuntimeError):
            p.decref(a)                  # refcount 0: double free


class TestKVPoolProperties:
    """Satellite: allocator-safety properties over randomized fragmented
    pools — real hypothesis when installed, the seeded stdlib shim in
    tests/_hypothesis_shim.py otherwise."""

    @given(st.integers(0, 2**32 - 1), st.integers(5, 24),
           st.sampled_from([4, 8]))
    @settings(max_examples=10, deadline=None)
    def test_referenced_blocks_never_reclaimed(self, seed, n_blocks, bs):
        """Whatever interleaving of alloc / incref / decref / commit /
        ensure_writable / store_session runs, ``alloc`` never hands out a
        block the caller still holds references to — even when it has to
        evict parked blocks or whole sessions to satisfy the request."""
        rng = np.random.default_rng(seed)
        p = KVPool(n_blocks, bs, max_sessions=3)
        held: dict = {}                  # bid -> refs WE hold
        committed: set = set()           # a block commits at most once

        def take(b, n=1):
            held[b] = held.get(b, 0) + n
            if held[b] == 0:
                del held[b]

        for i in range(64):
            op = int(rng.integers(0, 7))
            bids = list(held)
            fresh = [b for b in bids if b not in committed]
            if op <= 1:                              # alloc (weighted 2x)
                try:
                    b = p.alloc()
                except PoolExhausted:
                    continue
                assert KVPool._RESERVED <= b < n_blocks
                assert held.get(b, 0) == 0, \
                    f"alloc returned live block {b} (held {held})"
                committed.discard(b)     # reclaimed: contents invalidated
                take(b)
            elif op == 2 and bids:                   # incref
                b = int(rng.choice(bids))
                p.incref(b)
                take(b)
            elif op == 3 and bids:                   # decref
                b = int(rng.choice(bids))
                p.decref(b)
                take(b, -1)
            elif op == 4 and fresh:                  # commit (random salt)
                b = int(rng.choice(fresh))
                salt = bytes([int(rng.integers(0, 4))])
                toks = rng.integers(0, 16, size=bs)
                [c] = p.commit_chain(p.block_hashes(salt, toks), [b])
                committed.add(c)
                if c != b:               # dedup moved our ref
                    take(b, -1)
                    take(c)
            elif op == 5 and bids:                   # session adopts refs
                b = int(rng.choice(bids))
                p.store_session(f"s{int(rng.integers(0, 4))}", [b],
                                rng.integers(0, 16, size=bs), "balanced")
                take(b, -1)
            elif op == 6 and bids:                   # copy-on-write fork
                b = int(rng.choice(bids))
                try:
                    wid, _src = p.ensure_writable(b)
                except PoolExhausted:
                    continue
                if wid != b:             # forked: our ref moved to the copy
                    take(b, -1)
                    take(wid)
        for b, n in held.items():
            assert p.refcount[b] >= n
        p.check_invariants()

    @given(st.integers(0, 2**32 - 1), st.integers(5, 20))
    @settings(max_examples=10, deadline=None)
    def test_pressure_monotone_under_consumption(self, seed, n_blocks):
        """``pressure()`` stays in [0, 1], never decreases across allocs
        (parked-block eviction included), never increases across releases,
        and reads exactly 1.0 when ``alloc`` raises ``PoolExhausted`` —
        the admission gate's contract (DESIGN.md §11)."""
        rng = np.random.default_rng(seed)
        p = KVPool(n_blocks, 4)
        assert p.pressure() == 0.0
        held = []
        # fragment: park some committed chains, hold live refs to others
        for i in range(int(rng.integers(0, n_blocks))):
            try:
                b = p.alloc()
            except PoolExhausted:
                break
            if rng.random() < 0.5:
                [c] = p.commit_chain(
                    p.block_hashes(bytes([i]), np.arange(4)), [b])
                p.decref(c)              # parked: still headroom
            else:
                held.append(b)
        last = p.pressure()
        assert 0.0 <= last <= 1.0
        while True:                      # consume to exhaustion
            try:
                held.append(p.alloc())
            except PoolExhausted:
                assert p.pressure() == 1.0
                break
            cur = p.pressure()
            assert cur >= last - 1e-12
            last = cur
        for b in held:                   # release: monotone back down
            p.decref(b)
            cur = p.pressure()
            assert cur <= last + 1e-12
            last = cur
        p.check_invariants()


# --------------------------------------------- layer-level property sweep --

class TestPagedAttendProperty:
    """paged_decode_attend over randomized fragmented pools + block tables
    (incl. COW-shared prefixes) is BITWISE decode_attend on the dense
    per-slot view, on the jnp gather path and the pallas kernel route."""

    @given(st.integers(0, 10_000), st.sampled_from([4, 8]),
           st.sampled_from([1, 2]), st.sampled_from([1, 2]),
           st.sampled_from([0.0, 4.0]), st.sampled_from([0, 13]))
    @settings(max_examples=5, deadline=None)
    def test_fragmented_table_bitwise(self, seed, bs, kvh, rep, softcap,
                                      window):
        rng = np.random.default_rng(seed)
        b_sz, nbps, hd = 2, 3, 4
        h = kvh * rep
        d = h * hd
        cfg = A.AttentionConfig(d_model=d, n_heads=h, n_kv_heads=kvh,
                                head_dim=hd, softcap=softcap, window=window)
        params = A.init_attention(jax.random.PRNGKey(seed % 97), cfg)
        # fragmented pool with spare blocks; slots SHARE their first
        # `share` logical blocks (a reused committed prefix) and own
        # distinct blocks past it, so the write block is always exclusive
        share = int(rng.integers(0, nbps - 1))
        n_blocks = 2 + share + b_sz * (nbps - share) + 3
        pool = {kk: jnp.asarray(rng.standard_normal(
                    (n_blocks, bs, kvh, hd)).astype(np.float32))
                for kk in ("k", "v")}
        ids = list(rng.permutation(n_blocks - 2) + 2)
        shared = [ids.pop() for _ in range(share)]
        table = np.zeros((b_sz, nbps), np.int32)
        for i in range(b_sz):
            table[i, :share] = shared
            table[i, share:] = [ids.pop() for _ in range(nbps - share)]
        lengths = np.asarray(
            [int(rng.integers(share * bs, nbps * bs - 1))
             for _ in range(b_sz)], np.int32)
        x = jnp.asarray(rng.standard_normal((b_sz, 1, d)).astype(np.float32))
        tj, lj = jnp.asarray(table), jnp.asarray(lengths)

        dense = A.paged_gather_kv(pool, tj)
        out_d, cache_d = A.decode_attend(params, x, cfg, dict(dense), lj)
        out_p, pool_p = A.paged_decode_attend(params, x, cfg, pool, lj, tj)
        np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))
        np.testing.assert_array_equal(
            np.asarray(cache_d["k"]),
            np.asarray(A.paged_gather_kv(pool_p, tj)["k"]))
        np.testing.assert_array_equal(
            np.asarray(cache_d["v"]),
            np.asarray(A.paged_gather_kv(pool_p, tj)["v"]))

        kcfg = dataclasses.replace(cfg, paged_kernel=True)
        out_k, pool_k = A.paged_decode_attend(params, x, kcfg, pool, lj, tj)
        np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_k))
        np.testing.assert_array_equal(np.asarray(pool_p["k"]),
                                      np.asarray(pool_k["k"]))
        np.testing.assert_array_equal(np.asarray(pool_p["v"]),
                                      np.asarray(pool_k["v"]))


# ------------------------------------------------------- serve parity ------

def paged_scfg(pc, batch=2, max_len=64, bs=8, **kw):
    return ServeConfig(batch=batch, max_len=max_len, prefill_chunk=pc,
                       prefill_interleave=8,
                       paged_kv=PagedKVConfig(block_size=bs), **kw)


def dense_scfg(pc, batch=2, max_len=64, **kw):
    return ServeConfig(batch=batch, max_len=max_len, prefill_chunk=pc,
                       prefill_interleave=8, **kw)


class TestServeParity:
    """Paged serve is bitwise the dense per-slot serve: greedy tokens and
    controller telemetry, monolithic and chunked, every strategy, and on
    the 2x4 mesh (the ISSUE acceptance bar)."""

    PLENS = (5, 13, 9, 17)

    def _run(self, cfg, scfg, mesh=None):
        srv = Server(lm, cfg, scfg, params_for(cfg), mesh=mesh)
        done = srv.serve(make_requests(np.random.default_rng(3), self.PLENS))
        return srv, outs(done)

    @pytest.mark.parametrize("strategy", ["dense", "gather", "pallas"])
    @pytest.mark.parametrize("pc", [0, 8])
    def test_tokens_bitwise(self, strategy, pc):
        cfg = CFG if strategy == "dense" else sparse_cfg(strategy)
        _, want = self._run(cfg, dense_scfg(pc))
        srv, got = self._run(cfg, paged_scfg(pc))
        assert_same_tokens(want, got, f"{strategy} pc={pc}")
        srv.kv_pool.check_invariants()
        assert srv.paged_stats()["free_blocks"] > 0

    def test_controller_telemetry_bitwise(self):
        ccfg = ControllerConfig(enabled=True, target_density=0.25,
                                audit_period=4)
        cfg = sparse_cfg("gather")
        srv_d, want = self._run(cfg, dense_scfg(8, controller=ccfg))
        srv_p, got = self._run(cfg, paged_scfg(8, controller=ccfg))
        assert_same_tokens(want, got)
        for name in ("alphas", "density_ema", "fn_ema", "union_ema",
                     "predicted_ema"):
            np.testing.assert_array_equal(
                getattr(srv_d.controller.state, name),
                getattr(srv_p.controller.state, name), err_msg=name)

    def test_pallas_kernel_route_bitwise(self):
        kcfg = CFG.replace(name="tiny-paged-kern", paged_attn_kernel=True)
        _PARAMS[kcfg.name] = params_for(CFG)      # same weights, new route
        _, want = self._run(CFG, dense_scfg(8))
        srv, got = self._run(kcfg, paged_scfg(8))
        assert_same_tokens(want, got, "paged_attn_kernel")
        srv.kv_pool.check_invariants()

    @needs8
    def test_mesh_2x4_tokens_bitwise(self):
        cfg = sparse_cfg("gather")
        cfg = cfg.replace(name="tiny-paged-mesh", sparse=dataclasses.replace(
            cfg.sparse, tp_shards=4, dp_shards=2))
        _, want = self._run(cfg, dense_scfg(8),
                            mesh=make_mesh((2, 4), ("data", "model")))
        srv, got = self._run(cfg, paged_scfg(8),
                             mesh=make_mesh((2, 4), ("data", "model")))
        assert_same_tokens(want, got, "2x4 mesh")
        srv.kv_pool.check_invariants()


# ------------------------------------------------------- prefix reuse ------

class TestPrefixReuse:
    def test_trie_reuse_bitwise_and_saves_chunks(self):
        """A second request sharing a committed prompt prefix admits with
        most chunks skipped and emits bitwise the tokens of a from-scratch
        serve (adopted blocks are prefill-origin: re-prefill is the
        oracle)."""
        rng = np.random.default_rng(7)
        scfg = paged_scfg(16, max_len=128)
        srv = Server(lm, CFG, scfg, params_for(CFG))
        sys_prompt = rng.integers(0, CFG.vocab, 70)
        ra = Request(uid=0, prompt=np.concatenate(
            [sys_prompt, rng.integers(0, CFG.vocab, 12)]), max_new=4)
        srv.serve([ra])
        rb_prompt = np.concatenate([sys_prompt,
                                    rng.integers(0, CFG.vocab, 9)])
        run0 = srv.prefill_chunks_run
        [rb] = srv.serve([Request(uid=1, prompt=rb_prompt, max_new=5)])
        stats = srv.paged_stats()
        # shared full blocks: 70//8 = 8 -> 64 tokens, chunk-aligned at 64;
        # plen 79 -> 5 chunks total, 4 skipped, 1 re-run
        assert stats["reuse_hits"] == 1 and stats["reused_tokens"] == 64
        assert srv.prefill_chunks_skipped == 4
        assert srv.prefill_chunks_run - run0 == 1
        srv.kv_pool.check_invariants()

        fresh = Server(lm, CFG, scfg, params_for(CFG))
        [want] = fresh.serve([Request(uid=1, prompt=rb_prompt, max_new=5)])
        np.testing.assert_array_equal(rb.out, want.out)

    def test_trie_reuse_90pct_fewer_chunks(self):
        """The headline acceptance number: a long shared prefix admits
        with >= 90% of its prefill chunks skipped."""
        rng = np.random.default_rng(8)
        scfg = paged_scfg(16, max_len=256, bs=16)
        srv = Server(lm, CFG, scfg, params_for(CFG))
        shared = rng.integers(0, CFG.vocab, 160)
        srv.serve([Request(uid=0, prompt=np.concatenate(
            [shared, rng.integers(0, CFG.vocab, 2)]), max_new=2)])
        run0 = srv.prefill_chunks_run
        srv.serve([Request(uid=1, prompt=np.concatenate(
            [shared, rng.integers(0, CFG.vocab, 3)]), max_new=2)])
        ran = srv.prefill_chunks_run - run0
        skipped = srv.prefill_chunks_skipped
        assert skipped / (skipped + ran) >= 0.90, (skipped, ran)

    def test_session_continuation_and_sticky_tier(self):
        rng = np.random.default_rng(9)
        scfg = paged_scfg(16, max_len=128)
        srv = Server(lm, CFG, scfg, params_for(CFG))
        p1 = rng.integers(0, CFG.vocab, 40)
        [r1] = srv.serve([Request(uid=0, prompt=p1, max_new=6,
                                  sla="quality", session_id="s0")])
        p2 = np.concatenate([p1, r1.out, rng.integers(0, CFG.vocab, 5)])
        run0 = srv.prefill_chunks_run
        # the stored tier overrides the request's asked-for tier: the whole
        # conversation pins to one point on the accuracy/sparsity curve
        r2 = Request(uid=1, prompt=p2, max_new=4, sla="latency",
                     session_id="s0")
        [r2] = srv.serve([r2])
        assert r2.sla == "quality"
        # history 45 tokens -> 5 full session blocks (40 tokens, all
        # prefill-origin with max_new < block), reuse boundary 32 -> 2 of
        # the 4 turn-2 chunks skipped
        assert srv.prefill_chunks_skipped == 2
        assert srv.prefill_chunks_run - run0 == 2
        assert srv.kv_pool.lookup_session("s0") is not None
        srv.kv_pool.check_invariants()

        # adopted blocks were prefill-origin: from-scratch is the oracle
        fresh = Server(lm, CFG, scfg, params_for(CFG))
        [want] = fresh.serve([Request(uid=1, prompt=p2, max_new=4,
                                      sla="quality")])
        np.testing.assert_array_equal(r2.out, want.out)

    def test_session_turn2_reproducible(self):
        """Multi-turn determinism when decode-origin blocks are adopted
        (history spans full reply blocks): two fresh servers running the
        identical two-turn schedule agree bitwise — the continuation
        oracle (same cache, same suffix chunks) is the schedule itself."""
        rng = np.random.default_rng(10)
        scfg = paged_scfg(16, max_len=128)
        p1 = rng.integers(0, CFG.vocab, 38)
        suffix = rng.integers(0, CFG.vocab, 7)

        def run_two_turns():
            srv = Server(lm, CFG, scfg, params_for(CFG))
            [r1] = srv.serve([Request(uid=0, prompt=p1, max_new=12,
                                      session_id="s0")])
            p2 = np.concatenate([p1, r1.out, suffix])
            [r2] = srv.serve([Request(uid=1, prompt=p2, max_new=5,
                                      session_id="s0")])
            srv.kv_pool.check_invariants()
            return r2.out, srv.paged_stats()

        out_a, stats_a = run_two_turns()
        out_b, stats_b = run_two_turns()
        np.testing.assert_array_equal(out_a, out_b)
        assert stats_a["reuse_hits"] == stats_b["reuse_hits"] == 1

    def test_cow_divergence_past_reuse_boundary(self):
        """A matched prefix extending past the chunk-aligned boundary
        adopts those blocks for writing: pinned originals fork (COW) and
        the re-run chunks rewrite the copies — tokens still bitwise the
        from-scratch serve."""
        rng = np.random.default_rng(11)
        scfg = paged_scfg(16, max_len=128)
        srv = Server(lm, CFG, scfg, params_for(CFG))
        common = rng.integers(0, CFG.vocab, 24)   # 3 full blocks, boundary 16
        srv.serve([Request(uid=0, prompt=np.concatenate(
            [common, rng.integers(0, CFG.vocab, 10)]), max_new=3,
            session_id="keep")])                  # session pins the originals
        pb = np.concatenate([common, rng.integers(0, CFG.vocab, 13)])
        [rb] = srv.serve([Request(uid=1, prompt=pb, max_new=4)])
        stats = srv.paged_stats()
        assert stats["cow_forks"] >= 1, stats
        srv.kv_pool.check_invariants()
        fresh = Server(lm, CFG, scfg, params_for(CFG))
        [want] = fresh.serve([Request(uid=1, prompt=pb, max_new=4)])
        np.testing.assert_array_equal(rb.out, want.out)

    def test_cow_candidates_referenced_at_match_time(self):
        """Race regression: _match_reuse must take a reference on its COW
        candidates, not just the adopted blocks.  cow_ids are consumed by
        place() only after the whole chunked prefill, and an eviction
        cascade inside that window (another slot's alloc, store_session)
        could otherwise reclaim a parked candidate onto the free list and
        re-issue it — place() would then adopt a block another slot
        exclusively owns (stale id -> alloc AssertionError or silent
        cross-request KV corruption)."""
        rng = np.random.default_rng(20)
        scfg = paged_scfg(16, max_len=128)
        srv = Server(lm, CFG, scfg, params_for(CFG))
        p1 = rng.integers(0, CFG.vocab, 40)       # 5 full blocks (bs=8)
        srv.serve([Request(uid=0, prompt=p1, max_new=3)])
        pool = srv.kv_pool
        # no session: the committed prompt blocks are parked at refcount 0
        r2 = Request(uid=1, prompt=p1, max_new=3)  # plen 40, boundary 32:
        meta = srv._match_reuse(r2, srv._tier_of(r2), len(r2.prompt))
        held = list(meta["ids"]) + list(meta["cow_ids"])
        assert meta["adopted"] == 4 and len(meta["cow_ids"]) == 1
        for b in held:                 # every matched block referenced NOW
            assert pool.refcount[b] >= 1, (b, held)
        # drain the allocator dry (it reclaims parked blocks, then raises):
        # none of the held blocks may be re-issued out from under the match
        grabbed = []
        with pytest.raises(RuntimeError, match="exhausted"):
            while True:
                grabbed.append(pool.alloc())
        assert not set(grabbed) & set(held), (grabbed, held)
        for b in grabbed + held:
            pool.release(b)
        pool.check_invariants()

    def test_sticky_tier_disables_uniform_alpha_fast_path(self):
        """A turn-2 request declaring the zero-offset default tier while
        its session is sticky on 'quality' must NOT decode via the legacy
        no-alphas jit: the fast-path check sees the resolved (sticky)
        tiers, so the stored tier's alpha offset actually reaches the
        decode step — tokens match a from-scratch quality serve."""
        rng = np.random.default_rng(21)
        cfg = sparse_cfg("masked")
        scfg = paged_scfg(16, max_len=128)
        srv = Server(lm, cfg, scfg, params_for(cfg))
        p1 = rng.integers(0, cfg.vocab, 40)
        [r1] = srv.serve([Request(uid=0, prompt=p1, max_new=6,
                                  sla="quality", session_id="s0")])
        p2 = np.concatenate([p1, r1.out, rng.integers(0, cfg.vocab, 5)])
        legacy_calls = []
        orig_decode = srv.decode_fn
        srv.decode_fn = lambda *a: (legacy_calls.append(1),
                                    orig_decode(*a))[1]
        r2 = Request(uid=1, prompt=p2, max_new=4, session_id="s0")
        [r2] = srv.serve([r2])        # declared 'balanced' (zero offset)
        assert r2.sla == "quality"
        assert not legacy_calls, \
            "sticky non-zero tier decoded via the no-alphas fast path"
        srv.kv_pool.check_invariants()
        # adopted blocks are prefill-origin: from-scratch is the oracle
        fresh = Server(lm, cfg, scfg, params_for(cfg))
        [want] = fresh.serve([Request(uid=1, prompt=p2, max_new=4,
                                      sla="quality")])
        np.testing.assert_array_equal(r2.out, want.out)
        # control: session-free zero-offset requests (both slots live —
        # the fast path needs every slot active) still take the legacy jit
        srv.serve([Request(uid=2, prompt=rng.integers(0, cfg.vocab, 9),
                           max_new=3),
                   Request(uid=3, prompt=rng.integers(0, cfg.vocab, 7),
                           max_new=3)])
        assert legacy_calls

    def test_sessions_exceed_dense_slot_capacity(self):
        """The pool retains more concurrent sessions than the dense layout
        has slots: dense per-slot buffers hold batch conversations total;
        the paged pool keeps every session's blocks live at the same
        byte budget because short sessions only pin the blocks they
        wrote."""
        rng = np.random.default_rng(12)
        # pool bytes == the dense layout's batch*max_len rows
        scfg = paged_scfg(16, batch=2, max_len=128)
        srv = Server(lm, CFG, scfg, params_for(CFG))
        n_sessions = 6                            # 3x the slot count
        for s in range(n_sessions):
            srv.serve([Request(uid=s, prompt=rng.integers(0, CFG.vocab, 18),
                               max_new=3, session_id=f"s{s}")])
        stats = srv.paged_stats()
        assert stats["sessions"] == n_sessions > scfg.batch
        assert stats.get("evicted_sessions", 0) == 0
        srv.kv_pool.check_invariants()


# ------------------------------------------------- bugfix satellites -------

class TestThroughputReportGuards:
    def test_empty_queue_reports_zeros(self):
        rep = throughput_report([])
        assert rep["requests"] == 0 and rep["tokens"] == 0
        for k, v in rep.items():
            assert np.isfinite(v) and v == 0.0 or k in ("requests", "tokens")

    def test_half_stamped_requests_excluded(self):
        # hand-built / aborted requests must not poison the wall-clock
        # window with 0.0 starts (the old NaN / toks-per-nanosecond spike)
        # — and their tokens fall OUTSIDE that window, so the rate counts
        # only the served set's tokens, not every out != None straggler
        r_ok = Request(uid=0, prompt=np.arange(3), out=np.arange(4),
                       t_start=10.0, t_end=12.0, latency_s=2.0)
        r_half = Request(uid=1, prompt=np.arange(3), out=np.arange(4))
        rep = throughput_report([r_ok, r_half])
        assert rep["total_s"] == 2.0
        assert rep["tokens"] == 4
        assert rep["tok_per_s"] == pytest.approx(4 / 2.0)
        for v in rep.values():
            assert np.isfinite(v)

    def test_zero_duration_window_is_zero_rate(self):
        r = Request(uid=0, prompt=np.arange(3), out=np.arange(4),
                    t_start=5.0, t_end=5.0, latency_s=0.0)
        rep = throughput_report([r])
        assert rep["tok_per_s"] == 0.0 and np.isfinite(rep["tok_per_s"])


class TestRequestStampReset:
    def test_reserve_same_objects_bitwise(self):
        """serve() mutates Request stamps in place; re-serving the same
        objects must reset every stamp at admission and reproduce the
        tokens (the old behavior kept turn-1 stamps and skewed every
        latency percentile of the second run)."""
        rng = np.random.default_rng(13)
        scfg = dense_scfg(8)
        srv = Server(lm, CFG, scfg, params_for(CFG))
        reqs = make_requests(rng, (5, 11, 9), max_new=4)
        first = {r.uid: np.copy(r.out) for r in srv.serve(reqs)}
        stamps1 = {r.uid: (r.t_admit, r.ttft_s, r.latency_s) for r in reqs}
        second = {r.uid: np.copy(r.out) for r in srv.serve(reqs)}
        assert_same_tokens(first, second, "re-serve")
        for r in reqs:
            t_admit1, ttft1, lat1 = stamps1[r.uid]
            assert r.t_admit > t_admit1         # fresh admission stamp
            assert r.ttft_s > 0.0 and r.latency_s >= r.ttft_s
        rep = throughput_report(reqs)
        assert np.isfinite(rep["tok_per_s"]) and rep["tok_per_s"] > 0.0


class TestBenchDiffGate:
    """benchmarks/bench_diff.py: structural fields exact, timing fields
    relative-tolerance, failures only past the threshold (the nightly
    BENCH --against gate; it used to eyeball-compare floats exactly and
    never fail)."""

    @pytest.fixture(autouse=True)
    def _import(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if root not in sys.path:
            sys.path.insert(0, root)
        from benchmarks.bench_diff import compare
        self.compare = compare

    def test_timing_drift_within_tolerance_passes(self):
        old = {"m": {"tok_per_s": 100.0, "wall_s": 1.0}}
        new = {"m": {"tok_per_s": 140.0, "wall_s": 0.8}}
        assert self.compare(old, new, rel_tol=0.5) == []

    def test_timing_drift_past_tolerance_fails(self):
        old = {"m": {"tok_per_s": 100.0}}
        new = {"m": {"tok_per_s": 10.0}}
        fails = self.compare(old, new, rel_tol=0.5)
        assert len(fails) == 1 and "drift" in fails[0]

    def test_structural_fields_exact(self):
        old = {"shape": {"d": 64}, "backend": "cpu",
               "chunk_traces": {"(8, True)": 1}, "generated_unix": 1.0}
        new = {"shape": {"d": 64}, "backend": "cpu",
               "chunk_traces": {"(8, True)": 2}, "generated_unix": 9.0}
        fails = self.compare(old, new, rel_tol=10.0)
        assert len(fails) == 1 and "chunk_traces" in fails[0]

    def test_missing_key_is_structural(self):
        fails = self.compare({"a": 1, "b": 2}, {"a": 1}, rel_tol=0.5)
        assert fails and "removed" in fails[0]

    def test_nested_timing_dict_tolerated(self):
        old = {"buckets": [{"dispatches": 2, "wall_us": {"gather": 100.0}}]}
        new = {"buckets": [{"dispatches": 2, "wall_us": {"gather": 130.0}}]}
        assert self.compare(old, new, rel_tol=0.5) == []
        bad = {"buckets": [{"dispatches": 3, "wall_us": {"gather": 130.0}}]}
        assert len(self.compare(old, bad, rel_tol=0.5)) == 1
