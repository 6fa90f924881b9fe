"""Serving runtime: prefill + decode with KV caches, SparseInfer decode
strategies, and a slot-refill continuous batching scheduler.

The paper's setting (§V): decode-phase GEMVs dominate; SparseInfer predicts
per-token activation sparsity and skips neuron rows.  Here the serve path is
generic over the model family; the SparseInfer strategy is picked by
``ModelConfig.sparse`` (dense | masked | gather | pallas).

Scheduling (DESIGN.md §5): the default scheduler keeps the jitted decode
step's batch dimension fixed and treats each batch index as a *slot*.  Every
slot holds one request at its own sequence position (``cache_len`` enters the
jit as a traced (B,) vector); when a request finishes, its slot is refilled
from the queue between decode steps — a batch-1 prefill splices the new
request's caches into the slot, with no retrace of the decode step — so no
request ever waits for the chunk's slowest.  Each request's ``sla`` tier maps
to a per-slot alpha column of the (L, B) alpha matrix, letting every request
pick its own point on the paper's accuracy/sparsity curve.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (DEFAULT_SLA_TIERS, ControllerConfig,
                                MetricsConfig, ModelConfig, PagedKVConfig,
                                SLATier)
# Alpha column for a dead (drained) slot — and, since the chunked-prefill
# scheduler, for a slot mid-prefill and for pad tokens inside a prefill
# chunk: margin = N_neg - alpha*N_pos with a huge negative alpha is positive
# for every neuron (N_neg + N_pos = d_valid >= 1), so the row predicts
# all-sparse and contributes NOTHING to the gather/pallas union selection —
# it must not consume shared capacity or perturb live requests' row
# selection (DESIGN.md §5/§9).  Canonical home is core.sparse_mlp (the model
# layer dead-alphas prefill pad tokens with it); re-exported here because
# the scheduler and its tests have always spelled it server.DEAD_SLOT_ALPHA.
from repro.core.sparse_mlp import DEAD_SLOT_ALPHA  # noqa: F401 (re-export)
from repro.models.common import greedy_sample
from repro.runtime.controller import (AlphaController, DistributedController,
                                      aggregate_tier_stats, restore_controller,
                                      save_controller)
from repro.runtime.faults import InjectedFault
from repro.runtime.kv_pool import KVPool, PoolExhausted
from repro.runtime.metrics import MetricsHub


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch: int = 8
    max_len: int = 256
    max_new_tokens: int = 32
    greedy: bool = True
    # Slot-refill continuous batching (DESIGN.md §5).  False falls back to
    # the legacy chunked scheduler (fixed chunks run to completion) — kept
    # for A/B benchmarks and the scheduler parity tests.
    slot_refill: bool = True
    # Per-request SLA tiers: Request.sla names one of these; the tier's
    # alpha offset (and, under a per-tier controller, its density target)
    # applies to every token the request decodes.
    sla_tiers: tuple = DEFAULT_SLA_TIERS
    # Online adaptive-alpha feedback loop (DESIGN.md §4). Off by default:
    # the static-AlphaSchedule path below stays bit-identical when disabled.
    controller: ControllerConfig = dataclasses.field(
        default_factory=ControllerConfig)
    # Trace every capacity bucket's decode step up front (one discarded
    # decode call per bucket before the serve loop) so no request ever pays
    # a mid-stream compile when the controller first switches buckets.
    warm_buckets: bool = False
    # ---- chunked prefill (DESIGN.md §9) ---------------------------------
    # Fixed prefill chunk size in tokens (MXU-aligned: 64/128).  0 keeps the
    # legacy monolithic batch-1 prefill (byte-exact seed behavior).  >0 and
    # the slot-refill scheduler streams each admitted prompt through the
    # pre-jitted chunk executable in order — one trace per chunk SHAPE, not
    # per prompt length — interleaving chunks with live decode steps so a
    # long admission never stalls resident requests' ITL.  Must divide
    # max_len.  The legacy chunked scheduler (slot_refill=False) instead
    # pads each batch's prompt length up to the chunk ladder, bounding its
    # jit cache at max_len/prefill_chunk shapes.
    prefill_chunk: int = 0
    # Max prefill chunks advanced per decode-loop iteration: the TTFT-vs-ITL
    # knob.  Higher drains admissions faster (better TTFT) at the cost of
    # more prefill compute squeezed between decode steps (worse ITL).
    prefill_interleave: int = 1
    # Controller persistence (DESIGN.md §8): directory for the adaptive
    # controller's state checkpoints (checkpoint.manager atomic-rename
    # layout).  On construction the server restores the latest snapshot if
    # one exists — alphas/EMAs survive restarts and elastic events; a
    # snapshot is written after every serve() drain (and on demand via
    # ``Server.save_controller``).  Empty = no persistence.
    controller_ckpt: str = ""
    # ---- paged KV pool (DESIGN.md §10) ----------------------------------
    # Replace the per-slot dense max_len KV buffers with a global block
    # pool + per-slot block tables: resident bytes follow tokens resident
    # instead of slots × max_len, committed prompt blocks are shared
    # through a prefix trie (repeated system prompts admit by reference),
    # and Request.session_id retains a finished request's chain for
    # multi-turn continuation.  Requires the slot-refill scheduler and a
    # family in the model module's PAGED_KV_FAMILIES; block_size must
    # divide max_len (and prefill_chunk must be a block multiple when
    # chunked prefill is on).  None keeps the dense per-slot caches —
    # the bitwise reference the paged path is pinned against.
    paged_kv: Optional[PagedKVConfig] = None
    # ---- overload robustness (DESIGN.md §11) ----------------------------
    # Admission control: serve() accepts at most this many queued requests;
    # the excess is recorded shed ("queue_depth") up front instead of
    # deepening an unbounded backlog.  0 = unbounded.
    max_queue_depth: int = 0
    # Fills Request.deadline_s for requests that declare none (0 = no
    # deadline).  Expired requests shed — queued, mid-prefill or resident —
    # with whatever tokens they already emitted.
    default_deadline_s: float = 0.0
    # Tier-aware preemption (needs paged_kv): on pool exhaustion — and on
    # deadline pressure at the queue head — the lowest-priority victim's
    # prompt blocks park in the prefix trie (evictable yet matchable, so
    # resume re-admits them by reference) and the request requeues.  Off,
    # pool exhaustion stays the legacy hard PoolExhausted.
    preempt: bool = False
    # KVPool.pressure() at or above which slot refills defer (admission
    # backpressure): new admissions above the gate would only feed the
    # eviction cascade.  Never defers when nothing is resident, so the
    # scheduler always makes progress.  1.0 disables the gate (the
    # default: gating changes admission interleaving, which under a
    # controller changes telemetry — it must be an explicit choice);
    # 0.8-0.95 is the useful overload range.
    pressure_gate: float = 1.0
    # Per-request preemption cap: past it the victim sheds ("pool") instead
    # of requeueing — the livelock guard for a pool too small to ever hold
    # the request (it would otherwise thrash park/resume forever).
    max_preemptions: int = 4
    # ---- observability (DESIGN.md §12) ----------------------------------
    # MetricsHub wiring: counters/gauges/histograms, serve-phase tracing,
    # JSONL + exposition sinks, and the retrace watchdog.  Disabled (the
    # default) the hub is a strict no-op and the serve path is bitwise the
    # metrics-free one (pinned by tests/test_metrics.py).
    metrics: MetricsConfig = dataclasses.field(default_factory=MetricsConfig)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (prompt_len,)
    max_new: int = 32
    sla: str = "balanced"        # ServeConfig.sla_tiers entry
    session_id: Optional[str] = None  # paged serving: retain this request's
                                 # KV chain under the id; a later request
                                 # with the same id whose prompt extends the
                                 # stored history admits by reference
                                 # (prefix reuse) and inherits the session's
                                 # SLA tier (sticky; DESIGN.md §10)
    out: Optional[np.ndarray] = None
    latency_s: float = 0.0       # admission -> last token (wall clock,
                                 # INCLUDES queue wait — the documented
                                 # contract; it used to silently run from
                                 # dequeue, under-reporting loaded-server
                                 # latency by the whole queue wait)
    t_admit: float = 0.0         # perf_counter at admission (serve() entry)
    t_start: float = 0.0         # perf_counter at dequeue (service start)
    t_end: float = 0.0           # perf_counter at completion
    queue_wait_s: float = 0.0    # admission -> dequeue
    ttft_s: float = 0.0          # admission -> first token emitted
    deadline_s: float = 0.0      # SLA deadline relative to admission
                                 # (serve() entry); 0 = none.  Unset, it is
                                 # filled from ServeConfig.default_deadline_s.
                                 # Past it the request sheds at the next
                                 # scheduler boundary (DESIGN.md §11)
    outcome: str = ""            # terminal scheduler outcome: "completed" |
                                 # "shed" ("" = never served)
    shed_reason: str = ""        # for shed outcomes: "deadline" | "pool" |
                                 # "queue_depth" | "fault"
    preemptions: int = 0         # times parked + requeued before the
                                 # terminal outcome (DESIGN.md §11)


def _splice_slot(full, one, slot):
    """Copy a batch-1 cache pytree into batch slot ``slot`` of a full-batch
    cache pytree.  The batch axis position varies per leaf (KV caches carry
    it behind the stacked layer dims, SSM states behind (group, layer)), so
    it is located as the single axis where the shapes differ.  Traceable:
    ``slot`` may be a traced scalar, so the scheduler jits one splice for
    all slots (the shape logic is static)."""
    def leaf(f, o):
        if f.shape == o.shape:           # batch == 1: the slot IS the batch
            return o.astype(f.dtype)
        diffs = [i for i, (a, b) in enumerate(zip(f.shape, o.shape))
                 if a != b]
        if len(diffs) != 1 or o.shape[diffs[0]] != 1:
            raise ValueError(f"cannot locate batch axis: {f.shape} vs "
                             f"{o.shape}")
        starts = [jnp.int32(0)] * f.ndim
        starts[diffs[0]] = jnp.asarray(slot, jnp.int32)
        return jax.lax.dynamic_update_slice(f, o.astype(f.dtype), starts)
    return jax.tree.map(leaf, full, one)


class Server:
    """Slot-refill continuous batching: finished slots are refilled from the
    queue between decode steps (batch dim stays fixed for the jit, each slot
    decodes at its own ``cache_len``); per-request SLA tiers select per-slot
    alphas (DESIGN.md §5)."""

    def __init__(self, model_mod, cfg: ModelConfig, scfg: ServeConfig,
                 params: dict, extra_inputs: Optional[dict] = None,
                 mesh: Optional[jax.sharding.Mesh] = None):
        """``mesh``: sharded serving mode over a 2D ``(data, model)`` mesh
        (DESIGN.md §8).  The sparse decode runs under shard_map over both
        axes — ``cfg.sparse.tp_shards`` / ``dp_shards`` define the SEMANTIC
        shard grid (explicit config values win; unset fields default to the
        mesh axis sizes, which must evenly divide them) — so results are
        bitwise-identical to the single-device emulation of the same
        config on any placement.  Params are placed row-sharded over
        'model', the KV cache and the per-step slot arrays (tokens, cache
        lengths, the SLA alpha matrix) partition their batch-slot dim over
        'data', and all jitted steps trace inside the mesh context."""
        self.mod = model_mod
        self.mesh = mesh
        self._slot_sh = None
        self._grid_warned: set = set()
        if scfg.prefill_chunk:
            if scfg.prefill_chunk < 1 or scfg.max_len % scfg.prefill_chunk:
                raise ValueError(
                    f"prefill_chunk={scfg.prefill_chunk} must be positive "
                    f"and divide max_len={scfg.max_len} (so every padded "
                    "prompt fits the cache; DESIGN.md §9)")
            if scfg.prefill_interleave < 1:
                raise ValueError(
                    f"prefill_interleave={scfg.prefill_interleave} must be "
                    ">= 1 (chunks per decode-loop iteration)")
        if scfg.preempt and scfg.paged_kv is None:
            raise ValueError(
                "ServeConfig.preempt needs the paged KV pool (paged_kv): "
                "preemption parks the victim's block chain in the prefix "
                "trie so resume re-admits by reference (DESIGN.md §11)")
        if not 0.0 < scfg.pressure_gate <= 1.0:
            raise ValueError(
                f"pressure_gate={scfg.pressure_gate} must be in (0, 1]")
        if scfg.max_queue_depth < 0 or scfg.default_deadline_s < 0.0 \
                or scfg.max_preemptions < 1:
            raise ValueError(
                "max_queue_depth/default_deadline_s must be >= 0 and "
                f"max_preemptions >= 1; got {scfg.max_queue_depth}/"
                f"{scfg.default_deadline_s}/{scfg.max_preemptions}")
        if mesh is not None:
            from repro.sharding import rules as RR
            from repro.sharding import sparse as SSP
            if int(np.prod(mesh.devices.shape)) <= 1:
                raise ValueError(
                    "mesh serving needs > 1 devices across the ('data', "
                    f"'model') axes (got mesh axes {mesh.axis_names}, "
                    f"shape {mesh.devices.shape})")
            if not cfg.sparse.enabled or cfg.sparse.strategy not in (
                    "masked", "gather", "pallas"):
                raise ValueError(
                    "mesh serving shards the SparseInfer decode strategies; "
                    f"got enabled={cfg.sparse.enabled} "
                    f"strategy={cfg.sparse.strategy!r} (DESIGN.md §8)")
            ds, ms = SSP.resolve_grid(cfg.sparse, mesh, scfg.batch)
            SSP.validate_shardable(cfg.sparse, cfg.d_ff, ms)
            cfg = cfg.replace(sparse=dataclasses.replace(
                cfg.sparse, tp_shards=ms, dp_shards=ds))
            tok_sh = RR.slot_sharding(mesh, 2, 0)
            if tok_sh is not None:
                self._slot_sh = (tok_sh, RR.slot_sharding(mesh, 1, 0),
                                 RR.slot_sharding(mesh, 2, 1))
        elif cfg.sparse.dp_shards and scfg.batch % cfg.sparse.dp_shards:
            raise ValueError(
                f"batch {scfg.batch} not divisible by dp_shards="
                f"{cfg.sparse.dp_shards} (DESIGN.md §8)")
        self.cfg = cfg
        self.scfg = scfg
        self.params = (model_mod.prepare_sparse(params, cfg.sparse)
                       if cfg.sparse.enabled else params)
        if mesh is not None:
            from repro.sharding import sparse as SSP
            with mesh:
                self.params = SSP.place_serve_params(self.params, mesh)
        self.extra = extra_inputs or {}
        self._tier_index = {t.name: i for i, t in enumerate(scfg.sla_tiers)}
        self._tier_offsets = np.asarray(
            [t.alpha_offset for t in scfg.sla_tiers], np.float32)

        def _prefill(params, tokens, *extra):
            return self.mod.prefill(params, cfg, tokens, *extra,
                                    max_len=scfg.max_len)

        # the trailing ``table`` argument selects the paged-pool decode
        # (DESIGN.md §10): the paged serve path always passes it and the
        # dense path never does, so each mode still compiles exactly one
        # trace of its decode step.  The kwarg is only forwarded when a
        # table is present — non-LM model modules (vlm, encdec) don't
        # accept it and never run paged.
        def _decode(params, tok, caches, length, table=None):
            kw = {} if table is None else {"block_table": table}
            logits, caches = self.mod.decode_step(params, cfg, tok, caches,
                                                  length, **kw)
            return greedy_sample(logits), caches

        def _decode_alphas(params, tok, caches, length, alphas, table=None):
            kw = {} if table is None else {"block_table": table}
            logits, caches = self.mod.decode_step(params, cfg, tok, caches,
                                                  length, alphas=alphas, **kw)
            return greedy_sample(logits), caches

        self.prefill_fn = jax.jit(_prefill)
        self.decode_fn = jax.jit(_decode)
        # controller-off SLA path: static schedule + per-slot tier offsets
        self.decode_alpha_fn = jax.jit(_decode_alphas)
        # slot index is traced: one compiled splice serves every refill
        self.splice_fn = jax.jit(_splice_slot)

        # ---- chunked prefill executables (DESIGN.md §9) ------------------
        # The sequence offset enters the jit as a traced scalar, so ONE
        # executable serves every chunk of a given shape — the per-prompt-
        # length trace cache of the monolithic batch-1 prefill is gone
        # structurally.  ``_prefill_traces`` counts (re)traces per chunk
        # shape (the no-retrace regression tests read it).
        self._prefill_traces: collections.Counter = collections.Counter()
        fams = getattr(model_mod, "CHUNK_PREFILL_FAMILIES", ())
        self._chunk_prefill = bool(scfg.prefill_chunk) and cfg.family in fams
        if scfg.prefill_chunk and scfg.slot_refill and not self._chunk_prefill:
            warnings.warn(
                f"prefill_chunk={scfg.prefill_chunk} set but family "
                f"{cfg.family!r} has no chunked prefill (supported: "
                f"{fams}); admissions run the monolithic batch-1 prefill "
                "(DESIGN.md §9)", stacklevel=2)

        def _mk_prefill_chunk(collect: bool):
            def _chunk(params, toks, caches, offset, valid, alphas, *ex):
                self._prefill_traces[(int(toks.shape[1]), collect)] += 1
                return self.mod.prefill_chunk(
                    params, cfg, toks, caches, offset, valid, *ex,
                    alphas=alphas, collect_stats=collect)
            return jax.jit(_chunk)

        self.prefill_chunk_fn = None
        self.prefill_chunk_stats_fn = None
        self.encode_fn = None
        if self._chunk_prefill:
            self.prefill_chunk_fn = _mk_prefill_chunk(False)
            self.prefill_chunk_stats_fn = _mk_prefill_chunk(True)
            if hasattr(model_mod, "encode"):
                # enc-dec: the encoder runs ONCE per admission; chunks
                # consume the precomputed encoder states
                self.encode_fn = jax.jit(
                    lambda p, f: self.mod.encode(p, cfg, f))

        # ---- paged KV pool (DESIGN.md §10) --------------------------------
        # Device side: one global block pool per layer (leaves
        # (L, N, block, ...)) shared by every slot, gathered/scattered
        # through per-slot block tables.  Host side: the KVPool manager
        # (allocation, prefix trie, sessions, COW).  Both persist across
        # serve() calls so sessions resume and committed prefixes keep
        # admitting by reference.
        self.kv_pool: Optional[KVPool] = None
        self._pool = None
        self.prefill_chunks_run = 0       # admission chunks executed
        self.prefill_chunks_skipped = 0   # admission chunks saved by reuse
        # ---- overload accounting + fault injection (DESIGN.md §11) -------
        self.faults = None                # runtime.faults.FaultInjector
        self.preempt_count = 0            # victims parked + requeued
        self.shed_count = 0               # terminal sheds (all reasons)
        self.admissions_deferred = 0      # refills held back by the gate
        # ---- observability hub (DESIGN.md §12) ---------------------------
        # Shares the scheduler's clock (_now — wall, or the fault
        # injector's virtual clock once attach_faults binds one), so spans
        # and events line up with deadline and queue-wait accounting.
        # Disabled (the default) every hub method is a no-op.
        self.metrics = MetricsHub(scfg.metrics, clock=self._now)
        if scfg.paged_kv is not None:
            pk = scfg.paged_kv
            pfams = getattr(model_mod, "PAGED_KV_FAMILIES", ())
            if not scfg.slot_refill:
                raise ValueError("paged_kv needs the slot-refill scheduler "
                                 "(slot_refill=True; DESIGN.md §10)")
            if cfg.family not in pfams:
                raise ValueError(
                    f"paged_kv: family {cfg.family!r} has no paged decode "
                    f"path (supported: {pfams})")
            if pk.block_size < 1 or scfg.max_len % pk.block_size:
                raise ValueError(
                    f"paged_kv.block_size={pk.block_size} must be positive "
                    f"and divide max_len={scfg.max_len}")
            if scfg.prefill_chunk and scfg.prefill_chunk % pk.block_size:
                raise ValueError(
                    f"prefill_chunk={scfg.prefill_chunk} must be a multiple "
                    f"of paged_kv.block_size={pk.block_size} so trie-aligned "
                    "reuse lands on chunk boundaries (DESIGN.md §10)")
            nbps = scfg.max_len // pk.block_size
            self._nbps = nbps
            self._init_paged_state()

            bs_ = pk.block_size

            # seed: gather adopted blocks into a batch-1 dense scratch (the
            # chunked-prefill layout) — non-reused lanes point at the NULL
            # block, whose zeros read exactly like init_caches
            def _seed(pool, tab):
                def leaf(p):
                    g = p[:, tab]                       # (L, nbps, bs, ...)
                    return g.reshape((p.shape[0], 1, nbps * bs_)
                                     + p.shape[3:])
                return jax.tree.map(leaf, pool)

            # commit: scatter a finished batch-1 prefill into the pool —
            # the table holds this slot's owned block ids at owned lanes
            # and TRASH elsewhere (reused lanes must not be rewritten;
            # TRASH collisions are harmless, it is never gathered live)
            def _commit(pool, one, tab):
                def leaf(p, o):
                    upd = o.reshape((o.shape[0], nbps, bs_) + o.shape[3:])
                    return p.at[:, tab].set(upd.astype(p.dtype))
                return jax.tree.map(leaf, pool, one)

            self.seed_fn = jax.jit(_seed)
            self.commit_fn = jax.jit(_commit)

        # ---- adaptive-alpha controller wiring (DESIGN.md §4/§5) ----------
        # The controller lives across generate()/serve() calls so adaptation
        # carries over between requests.  Alphas enter the jitted step as a
        # traced (L,) — or (L, B) per-slot — argument: updating them never
        # retraces.  Audit steps re-dispatch through the masked strategy
        # (full gate matmul => exact false negatives, exact paper skip
        # semantics for the emitted token).  With ``per_tier`` the state is
        # (T, L): one alpha vector and density target per SLA tier.
        self.controller: Optional[AlphaController] = None
        if (cfg.sparse.capacity_buckets
                and not (scfg.controller.enabled and cfg.sparse.enabled)):
            # the ladder is driven by the controller's union-demand hint;
            # without it decoding silently runs the static capacity_frac
            warnings.warn(
                "SparseInferConfig.capacity_buckets set but the controller "
                "is disabled: the bucket ladder needs capacity_hint to pick "
                "buckets — decoding uses the static capacity_frac "
                "(DESIGN.md §2)", stacklevel=2)
        if scfg.controller.enabled and cfg.sparse.enabled:
            if cfg.family == "xlstm":
                raise ValueError("xlstm has no SparseInfer MLP decode path; "
                                 "controller unsupported")
            self._build_controller_fns()
        # ---- controller persistence (DESIGN.md §8) -----------------------
        if cfg.sparse.tp_shards and cfg.sparse.strategy == "pallas":
            # construction-time grid check for the static capacity (ladder
            # buckets are checked as they activate); deduped per (bucket,
            # shard) so later bucket switches never re-warn
            ms = cfg.sparse.tp_shards
            self._check_shard_grids((cfg.sparse.shard_capacity(cfg.d_ff),)
                                    * ms)
        self._ckpt_mgr = None
        if scfg.controller_ckpt and scfg.controller.enabled \
                and cfg.sparse.enabled:
            from repro.checkpoint.manager import CheckpointManager
            self._ckpt_mgr = CheckpointManager(scfg.controller_ckpt)
        self._init_controller_state()
        self._cfg0 = self.cfg   # pristine config; reset() restores it

    def _init_controller_state(self) -> None:
        """Fresh controller state — construction and :meth:`reset` share
        this, so a reset server's controller is bitwise a new server's."""
        cfg, scfg = self.cfg, self.scfg
        if not (scfg.controller.enabled and cfg.sparse.enabled):
            self.controller = None
            return
        tiers = scfg.sla_tiers if scfg.controller.per_tier else None
        # NOTE: gather no longer blocks per-tier control — since PR 4 it
        # reports TRUE per-slot realized density (the token's predicted
        # groups that made the union selection), same contract as the
        # pallas kernel's in-kernel counter (DESIGN.md §4/§5).
        # pallas emits the false-negative proxy natively every step:
        # no masked-path audit dispatches at all (DESIGN.md §4)
        ctl = AlphaController(
            scfg.controller, cfg.sparse.alpha_schedule(),
            self._n_controlled_layers(), tiers=tiers,
            native_fn=cfg.sparse.strategy == "pallas")
        if cfg.sparse.tp_shards:
            # sharded strategies (mesh or emulated) ride per-shard
            # realized densities + union demands along the telemetry:
            # wrap for skew diagnosis, per-shard bucket hints and the
            # key strip before aggregation
            ctl = DistributedController(
                ctl, cfg.sparse.tp_shards,
                n_data_shards=max(1, cfg.sparse.dp_shards or 1))
        self.controller = ctl
        self._active_cap = self._initial_cap
        if self._ckpt_mgr is not None and restore_controller(ctl,
                                                             self._ckpt_mgr):
            # restored union/density EMAs immediately steer the bucket
            # ladder: the first _select_bucket call uses them
            self._select_bucket()

    def _init_paged_state(self) -> None:
        """Fresh host pool manager + device block pool — construction and
        :meth:`reset` share this (the jitted seed/commit/decode fns are
        pure and survive resets untouched)."""
        pk = self.scfg.paged_kv
        n_blocks = (pk.pool_blocks
                    or self.scfg.batch * self._nbps + KVPool._RESERVED)
        self.kv_pool = KVPool(n_blocks, pk.block_size,
                              max_sessions=pk.max_sessions,
                              prefix_cache=pk.prefix_cache)
        self._pool = self.mod.init_kv_pool(self.cfg, n_blocks, pk.block_size)

    def reset(self) -> None:
        """Serve-abort recovery (DESIGN.md §11): restore every piece of
        cross-serve mutable state — controller (+ its checkpoint restore),
        KV pool manager and device pool, capacity bucket, counters — to
        its fresh-construction value, so the next serve() on this server
        is bitwise-identical to one on a newly built server.  serve()
        invokes this automatically when the scheduler raises; jitted
        executables are pure functions and are kept."""
        if self.cfg is not self._cfg0:
            # maybe_adapt_capacity re-jitted toward a hint mid-serve:
            # restore the pristine config and its executables
            self.cfg = self._cfg0
            if self.scfg.controller.enabled and self.cfg.sparse.enabled:
                self._build_controller_fns()
        self._init_controller_state()
        if self.scfg.paged_kv is not None:
            self._init_paged_state()
        self.prefill_chunks_run = 0
        self.prefill_chunks_skipped = 0
        self.preempt_count = 0
        self.shed_count = 0
        self.admissions_deferred = 0

    # ------------------------------------------------ fault plumbing (§11) --
    def attach_faults(self, injector) -> None:
        """Install a ``runtime.faults.FaultInjector``: its armed points
        fire via ``_fault`` and, with ``virtual_clock``, the scheduler's
        entire notion of time (deadlines, stamps, queue waits) comes from
        ``injector.now()`` advanced one tick per loop iteration — overload
        runs become deterministic functions of scheduling decisions.  The
        metrics hub rebinds to the same source, so spans, events and trace
        timestamps share the scheduler's clock (DESIGN.md §12)."""
        self.faults = injector
        self.metrics.bind_clock(injector.time_source())

    def _now(self) -> float:
        f = self.faults
        if f is not None and f.virtual_clock:
            return f.now()
        return time.perf_counter()

    def _tick(self) -> None:
        f = self.faults
        if f is not None and f.virtual_clock:
            f.tick()

    def _fault(self, point: str, uid: Optional[int] = None) -> None:
        if self.faults is not None:
            self.faults.check(point, uid)

    def _build_controller_fns(self) -> None:
        """(Re)build the stats-collecting decode jits against the CURRENT
        self.cfg: one per capacity bucket when the config carries a
        ``capacity_buckets`` ladder (DESIGN.md §2), else a single fn.
        Sharded configs key the dict by per-shard bucket TUPLES (one
        executable per tuple — the full len(ladder)**tp_shards product when
        it fits ``ControllerConfig.bucket_tuple_cap``, else uniform tuples
        only, DESIGN.md §8).  Each fn is jitted once and cached — the
        controller then switches buckets between decode steps with a dict
        lookup, never a retrace.  ``_trace_counts`` counts (re)traces per
        bucket key (the no-retrace regression tests read it)."""
        cfg = self.cfg
        self._trace_counts: collections.Counter = collections.Counter()

        def make_ctrl(cfg_b, cap_key):
            def _decode_ctrl(params, tok, caches, length, alphas,
                             table=None):
                self._trace_counts[cap_key] += 1   # trace-time side effect
                logits, caches, stats = self.mod.decode_step(
                    params, cfg_b, tok, caches, length, alphas=alphas,
                    collect_stats=True, block_table=table)
                return greedy_sample(logits), caches, stats
            return jax.jit(_decode_ctrl)

        self._bucket_fns: dict = {}
        self._warmed_buckets = False
        self._local_ladder: tuple = ()
        self._per_shard_buckets = False
        ms = max(1, cfg.sparse.tp_shards or 1)
        if (cfg.sparse.capacity_buckets
                and cfg.sparse.strategy in ("gather", "pallas")
                and cfg.sparse.tp_shards):
            import itertools

            from repro.sharding import sparse as SSP
            # every ladder bucket must split evenly across the shards on
            # EVERY placement — the mesh path validates at construction,
            # and the meshless (emulated) path must reject the same
            # configs rather than silently flooring a bucket
            SSP.validate_shardable(cfg.sparse, cfg.d_ff, ms)
            sc = self.scfg.controller
            ladder = cfg.sparse.capacity_ladder(cfg.d_ff)
            local = tuple(capg // ms for capg in ladder)
            self._local_ladder = local
            n_tuples = len(local) ** ms
            self._per_shard_buckets = (sc.per_shard_buckets
                                       and n_tuples <= sc.bucket_tuple_cap)
            if sc.per_shard_buckets and not self._per_shard_buckets:
                warnings.warn(
                    f"per-shard bucket ladder would need {n_tuples} "
                    f"executables (len(ladder)={len(local)} ** tp_shards="
                    f"{ms}) > bucket_tuple_cap={sc.bucket_tuple_cap}: "
                    "falling back to uniform bucket tuples (every shard "
                    "shares one ladder rung) — shrink the ladder or raise "
                    "the cap (DESIGN.md §8)", stacklevel=2)
            tuples = (itertools.product(local, repeat=ms)
                      if self._per_shard_buckets
                      else [(c,) * ms for c in local])
            for t in tuples:
                t = tuple(t)
                cfg_b = cfg.replace(sparse=dataclasses.replace(
                    cfg.sparse, capacity_override=max(t) * ms,
                    shard_bucket_caps=t))
                self._bucket_fns[t] = make_ctrl(cfg_b, t)
            self._active_cap = (max(local),) * ms  # start at the widest
            self._check_shard_grids(self._active_cap)
        elif (cfg.sparse.capacity_buckets
                and cfg.sparse.strategy in ("gather", "pallas")):
            for capg in cfg.sparse.capacity_ladder(cfg.d_ff):
                cfg_b = cfg.replace(sparse=dataclasses.replace(
                    cfg.sparse, capacity_override=capg))
                self._bucket_fns[capg] = make_ctrl(cfg_b, capg)
            self._active_cap = max(self._bucket_fns)  # start at the widest
        else:
            if cfg.sparse.capacity_buckets:
                # mirror of the controller-disabled warning in __init__:
                # the ladder only exists for the capacity-selected union
                # strategies — masked/dense decode must not silently drop it
                warnings.warn(
                    "SparseInferConfig.capacity_buckets set but strategy="
                    f"{cfg.sparse.strategy!r} has no capacity selection — "
                    "the ladder applies to gather/pallas only; decoding "
                    "runs without buckets (DESIGN.md §2)", stacklevel=2)
            self._bucket_fns[0] = make_ctrl(cfg, 0)
            self._active_cap = 0
        self._initial_cap = self._active_cap   # reset() restores this

        audit_cfg = cfg.replace(sparse=dataclasses.replace(
            cfg.sparse, strategy="masked"))

        def _decode_audit(params, tok, caches, length, alphas, table=None):
            logits, caches, stats = self.mod.decode_step(
                params, audit_cfg, tok, caches, length, alphas=alphas,
                collect_stats=True, block_table=table)
            return greedy_sample(logits), caches, stats

        self.decode_audit_fn = jax.jit(_decode_audit)

    def _mesh_ctx(self):
        """Mesh context for every trace/execute in mesh mode (sharding
        constraints and the shard_map dispatch both read the ambient mesh);
        a no-op single-device."""
        return self.mesh if self.mesh is not None else contextlib.nullcontext()

    def _put_slots(self, tok, lengths, alphas=None, table=None):
        """Per-step slot arrays onto the mesh, batch-slot dim partitioned
        over the 'data' axis (DESIGN.md §8): tokens (B, 1), cache lengths
        (B,), and the (L, B) alpha matrix each land pre-sharded so the
        jitted decode step never re-lays them out.  Placement only — the
        values are identical without a mesh; the decoded tokens are too in
        float32, but not in bf16, where the partitioned reductions round
        differently (DESIGN.md §8)."""
        jt, jl = jnp.asarray(tok), jnp.asarray(lengths)
        ja = None if alphas is None else jnp.asarray(alphas)
        jtab = None if table is None else jnp.asarray(table)
        if self._slot_sh is not None:
            tok_sh, len_sh, a_sh = self._slot_sh
            jt = jax.device_put(jt, tok_sh)
            jl = jax.device_put(jl, len_sh)
            if ja is not None:
                ja = jax.device_put(ja, a_sh)
            if jtab is not None:
                # block tables are slot arrays: (B, nbps) batch-slot dim
                # over 'data', like the tokens (DESIGN.md §8/§10)
                jtab = jax.device_put(jtab, tok_sh)
        if table is None:
            return jt, jl, ja
        return jt, jl, ja, jtab

    def save_controller(self, step: Optional[int] = None) -> Optional[int]:
        """Checkpoint the controller state (no-op without
        ``ServeConfig.controller_ckpt``).  Returns the step written."""
        if self._ckpt_mgr is None or self.controller is None:
            return None
        return save_controller(self.controller, self._ckpt_mgr, step)

    # ------------------------------------------------ observability (§12) --
    def publish_gauges(self) -> None:
        """Refresh the hub's gauge families from host state: controller
        per-tier / per-layer alphas and densities, per-(layer, shard)
        EMAs, KV-pool occupancy/pressure/counters, and the active capacity
        bucket(s) with their d_ff occupancy.  Plain-numpy reads of values
        the serve loop already materialized — no device syncs.  The
        scheduler calls this every ``MetricsConfig.cadence`` decode steps
        and at each drain boundary; benchmarks call it before snapshotting."""
        hub = self.metrics
        if not hub.enabled:
            return
        if self.controller is not None:
            self.controller.publish_metrics(hub)
        if self.kv_pool is not None:
            self.kv_pool.publish_metrics(hub)
        hub.set_gauge("admissions_deferred", self.admissions_deferred)
        cap = getattr(self, "_active_cap", None)
        g = self.cfg.sparse.group_size
        if isinstance(cap, tuple):
            k_local = self.cfg.d_ff // max(1, len(cap))
            for s, c in enumerate(cap):
                hub.set_gauge("capacity_bucket_groups", int(c), shard=s)
                hub.set_gauge("bucket_occupancy",
                              min(1.0, int(c) * g / max(1, k_local)),
                              shard=s)
        elif isinstance(cap, (int, np.integer)) and cap:
            hub.set_gauge("capacity_bucket_groups", int(cap))
            hub.set_gauge("bucket_occupancy",
                          min(1.0, int(cap) * g / self.cfg.d_ff))

    def _serve_epilogue(self) -> None:
        """Post-drain observability boundary (DESIGN.md §12): refresh the
        gauge families, stamp the drain event, arm the retrace watchdog —
        by the end of the first drain every executable this configuration
        needs has been traced, so any later compile is exactly the retrace
        the serve invariant forbids — and flush the configured sinks."""
        hub = self.metrics
        if not hub.enabled:
            return
        self.publish_gauges()
        hub.event("serve_end",
                  completed=int(hub.counter_value("requests_completed")),
                  shed=self.shed_count, preemptions=self.preempt_count,
                  retraces_post_warmup=hub.watchdog.retraces_post_warmup)
        if self.scfg.metrics.watchdog:
            hub.watchdog.arm()
        hub.flush()

    def metrics_report(self) -> dict:
        """Hub snapshot + watchdog state for launcher reports and
        benchmark studies (cheap and JSON-ready; empty-ish when the hub
        is disabled)."""
        hub = self.metrics
        rep: dict = {"enabled": hub.enabled,
                     "watchdog": hub.watchdog.report()}
        if hub.enabled:
            rep["snapshot"] = hub.snapshot()
            rep["events"] = len(hub.events())
        return rep

    @property
    def decode_ctrl_fn(self):
        """The stats-collecting decode jit for the ACTIVE capacity bucket."""
        return self._bucket_fns[self._active_cap]

    @staticmethod
    def _pick_rung(ladder: tuple, need: int) -> int:
        """Smallest ladder rung covering ``need`` groups (widest if none)."""
        for rung in ladder:          # capacity_ladder is sorted ascending
            if rung >= need:
                return rung
        return ladder[-1]

    def _check_shard_grids(self, caps: tuple) -> None:
        """Check — once per (bucket, shard), deduplicated across decode
        steps and bucket switches — that a shard's pallas kernel grid tiles
        at its local dims for its ACTIVE bucket.  On a TPU an untileable
        grid raises (the compiled kernels never substitute the jnp
        oracle); in interpret mode it warns that the oracle runs."""
        if self.cfg.sparse.strategy != "pallas":
            return
        from repro.core.predictor import packed_width
        from repro.kernels import ops as kops
        ds = max(1, self.cfg.sparse.dp_shards or 1)
        for s, capg in enumerate(caps):
            key = (capg, s)
            if key in self._grid_warned:
                continue
            self._grid_warned.add(key)
            try:
                kops.choose_blocks(self.cfg.d_ff,
                                   packed_width(self.cfg.d_model),
                                   max(1, self.scfg.batch // ds),
                                   group_size=self.cfg.sparse.group_size,
                                   n_shards=len(caps),
                                   capacity_groups=capg)
            except ValueError as e:
                msg = (f"sharded pallas predictor grid is degenerate for "
                       f"shard {s} at bucket {capg} local groups ({e})")
                if jax.default_backend() == "tpu":
                    raise ValueError(msg + ": no TPU kernel tiling") from e
                warnings.warn(msg + "; interpret mode runs the jnp oracle",
                              stacklevel=2)

    def _select_bucket(self):
        """Pick the smallest pre-jitted capacity bucket covering the
        controller's union-demand hint (DESIGN.md §2/§4) — per SHARD under
        the sharded bucket-tuple ladder: each model shard's local rung is
        sized to its own union-demand EMA (``shard_capacity_hints``), so a
        skewed shard widens only itself (DESIGN.md §8).  Pure host-side
        arithmetic + dict lookup between decode steps — switching buckets
        (or bucket tuples) never retraces the jitted decode step."""
        ctl = self.controller
        if ctl is None or len(self._bucket_fns) <= 1 or ctl.state.steps == 0:
            return self._active_cap
        g = self.cfg.sparse.group_size
        if isinstance(self._active_cap, tuple):
            ms = len(self._active_cap)
            if (self._per_shard_buckets
                    and isinstance(ctl, DistributedController)
                    and ctl._shard_steps > 0):
                hints = ctl.shard_capacity_hints(self.cfg.d_ff)
                needs = [-(-int(h) // g) for h in hints]      # local groups
            else:
                need = -(-ctl.capacity_hint(self.cfg.d_ff) // g)
                needs = [-(-need // ms)] * ms                 # global -> C/ms
            t = tuple(self._pick_rung(self._local_ladder, n) for n in needs)
            if t not in self._bucket_fns:      # uniform-only fallback mode
                t = (max(t),) * ms
            self._active_cap = t
            self._check_shard_grids(t)
            return t
        need = -(-ctl.capacity_hint(self.cfg.d_ff) // g)  # neurons -> groups
        for capg in sorted(self._bucket_fns):
            if capg >= need:
                self._active_cap = capg
                break
        else:
            self._active_cap = max(self._bucket_fns)
        return self._active_cap

    def _warm_bucket_ladder(self, tok, caches, lengths, alphas,
                            table=None) -> None:
        """Trace+compile every capacity bucket's decode step up front with
        the serve loop's real shapes (results discarded — caches are pure
        values, nothing advances).  One-time cost so the controller's first
        bucket switches never stall a live request; idempotent until the
        fns are rebuilt."""
        if self._warmed_buckets or len(self._bucket_fns) <= 1:
            self._warmed_buckets = True
            return
        for fn in self._bucket_fns.values():
            args = (self.params, jnp.asarray(tok), caches,
                    jnp.asarray(lengths), jnp.asarray(alphas))
            if table is not None:
                args += (jnp.asarray(table),)
            fn(*args)
        self._warmed_buckets = True

    def maybe_adapt_capacity(self) -> bool:
        """Legacy capacity adaptation: re-jit toward the controller's hint
        (DESIGN.md §4).  Capacity is a static shape under jit, so this can
        only move where a re-jit is acceptable — the scheduler calls it at
        refill boundaries.  Superseded by the pre-jitted bucket ladder
        (``_select_bucket``) whenever ``capacity_buckets`` is configured:
        then this is a no-op.  Returns True when the effective capacity
        changed (and the controller decode fns were rebuilt)."""
        ctl, sc = self.controller, self.scfg.controller
        if ctl is None or not sc.adapt_capacity or ctl.state.steps == 0:
            return False
        if len(self._bucket_fns) > 1 or 0 not in self._bucket_fns:
            return False              # the bucket ladder owns capacity
        k = self.cfg.d_ff
        hint = ctl.capacity_hint(k)
        sp = dataclasses.replace(self.cfg.sparse,
                                 capacity_frac=min(1.0, hint / k))
        new_cfg = self.cfg.replace(sparse=sp)
        if new_cfg.sparse.capacity(k) == self.cfg.sparse.capacity(k):
            return False
        if new_cfg.sparse.tp_shards:
            # the hint-derived capacity must still split evenly across the
            # TP shards (DESIGN.md §8); a non-shardable value would raise at
            # the re-jit trace mid-serve — keep the current capacity instead
            try:
                new_cfg.sparse.shard_capacity(k)
            except ValueError:
                return False
        self.cfg = new_cfg
        self._build_controller_fns()
        return True

    def _n_controlled_layers(self) -> int:
        """Length of the per-layer alpha/stats vectors for this family (must
        match what decode_step consumes/emits)."""
        if self.cfg.family == "hybrid":
            n_inv = (self.cfg.n_layers // self.cfg.attn_every)
            return n_inv
        return self.cfg.n_layers

    # ------------------------------------------------------- alpha plumbing
    def _tier_of(self, req: Request) -> int:
        try:
            return self._tier_index[req.sla]
        except KeyError:
            raise ValueError(
                f"request {req.uid}: unknown SLA tier {req.sla!r} "
                f"(configured: {sorted(self._tier_index)})") from None

    def _pad_layers(self, a: np.ndarray) -> np.ndarray:
        """Pad a controller-width alpha array up to n_layers rows (hybrid's
        controller width is the invocation-group count; decode_step slices
        back down, so padded rows are never consumed)."""
        n = self.cfg.n_layers
        if a.shape[0] == n:
            return np.asarray(a, np.float32)
        out = np.ones((n,) + a.shape[1:], np.float32)
        out[: a.shape[0]] = a
        return out

    def _slot_alpha_matrix(self, tier_idx: np.ndarray,
                           active: Optional[np.ndarray] = None) -> np.ndarray:
        """(n_layers, B) per-layer-per-slot alphas for the jitted step.
        Dead slots (``active`` False) get the neutralizing alpha so they
        predict all-sparse and stay out of the union selection."""
        ctl = self.controller
        if ctl is None:
            base = self.cfg.sparse.alpha_schedule().alphas(self.cfg.n_layers)
            mat = (base[:, None] +
                   self._tier_offsets[tier_idx][None, :]).astype(np.float32)
        elif ctl.tiers:
            mat = self._pad_layers(ctl.slot_alphas(tier_idx))
        else:
            # untiered controller: adapted vector + static tier offsets
            a = self._pad_layers(ctl.alphas())
            mat = (a[:, None] +
                   self._tier_offsets[tier_idx][None, :]).astype(np.float32)
        if active is not None and not active.all():
            mat = mat.copy()
            mat[:, ~np.asarray(active, bool)] = DEAD_SLOT_ALPHA
        return mat

    def _prefill_alphas(self, t: int) -> np.ndarray:
        """(n_layers,) alpha vector for one request's prefill chunks: the
        same schedule + tier plumbing as the decode slots, for a single
        request on tier ``t`` (pad positions inside a chunk are dead-alpha'd
        by the model layer itself)."""
        ctl = self.controller
        if ctl is None:
            base = self.cfg.sparse.alpha_schedule().alphas(self.cfg.n_layers)
            return (base + self._tier_offsets[t]).astype(np.float32)
        if ctl.tiers:
            return self._pad_layers(ctl.slot_alphas(np.asarray([t])))[:, 0]
        return (self._pad_layers(ctl.alphas())
                + self._tier_offsets[t]).astype(np.float32)

    def _prefill_salt(self, t: int) -> bytes:
        """Trie hash salt: everything besides the tokens that determines a
        prefill-origin block's content.  Dense prefill is a pure function
        of the tokens — empty salt.  Sparse prefill skips MLP rows by the
        per-layer alpha vector, so the (tier- and controller-dependent)
        prefill alphas fold in: a committed block only matches a request
        that would have prefilled it bitwise-identically (DESIGN.md §10)."""
        sp = self.cfg.sparse
        if not (sp.enabled and sp.sparse_prefill
                and not (sp.tp_shards or sp.dp_shards)):
            return b""
        return np.asarray(self._prefill_alphas(t), np.float32).tobytes()

    def _match_reuse(self, r: Request, t: int, plen: int) -> dict:
        """Longest admissible-by-reference prefix for a paged admission
        (DESIGN.md §10).  Two candidate sources, best coverage wins:

        * the request's own session chain — valid over decode-written
          reply KV too, because the reuse semantics there are
          *continuation* of the retained cache (salt-free: the suffix
          chunks run with current alphas either way);
        * the prefix trie of committed prompt blocks (salt-checked: a hit
          guarantees the block's content is bitwise what this request's
          own prefill would have produced).

        The reuse boundary is chunk-aligned and always leaves the final
        chunk to re-run: it produces the first-token logits, and rewrites
        its (matched) blocks bitwise-identically.  Matched full blocks
        past the boundary come back as ``cow_ids`` — a reference is taken
        on them HERE, and place() adopts them for writing, forking the
        shared originals (copy-on-write)."""
        pool = self.kv_pool
        pc = self.scfg.prefill_chunk
        bs = pool.block_size
        prompt = np.asarray(r.prompt, np.int32)
        salt = self._prefill_salt(t)
        meta: dict = {"adopted": 0, "ids": [], "cow_ids": [],
                      "hashes": pool.block_hashes(salt, prompt)}
        if not (pool.prefix_cache and pc and self._chunk_prefill):
            return meta
        ids: list[int] = []
        sess = pool.lookup_session(r.session_id) if r.session_id else None
        if sess is not None:
            hist = sess["history"]
            n = min(plen, len(hist))
            eq = prompt[:n] == hist[:n]
            m = n if eq.all() else int(np.argmax(~eq))
            ids = sess["chain"][: m // bs]
        tids = pool.match_prefix(salt, prompt)
        if len(tids) > len(ids):
            ids = tids
        if not ids:
            return meta
        # final chunk always re-runs: cap at the last chunk boundary below
        # plen, then align the adoption down to whole chunks
        r_max = ((plen - 1) // pc) * pc
        nb_re = (min(len(ids) * bs, r_max) // pc) * (pc // bs)
        # reference EVERY matched block now — adopted AND cow candidates.
        # cow_ids are not consumed until place() runs after the whole
        # chunked prefill; un-refed, any eviction cascade in that window
        # (pool alloc for another slot, store_session) could reclaim a
        # parked or session-evicted candidate onto the free list and
        # re-issue it, leaving a stale id here that place() would adopt
        # while another slot exclusively owns the block
        for b in ids:
            pool.incref(b)
        meta["adopted"] = nb_re
        meta["ids"] = ids[:nb_re]
        meta["cow_ids"] = ids[nb_re:]
        if nb_re:
            pool.stats["reuse_hits"] += 1
            pool.stats["reused_blocks"] += nb_re
            pool.stats["reused_tokens"] += nb_re * bs
        return meta

    def paged_stats(self) -> dict:
        """Pool occupancy/reuse counters + admission chunk accounting
        (empty without ``ServeConfig.paged_kv``)."""
        if self.kv_pool is None:
            return {}
        return {**self.kv_pool.snapshot(),
                "prefill_chunks_run": self.prefill_chunks_run,
                "prefill_chunks_skipped": self.prefill_chunks_skipped,
                "preemptions": self.preempt_count,
                "shed": self.shed_count,
                "admissions_deferred": self.admissions_deferred}

    def _slot_extra(self, i: int, extra: tuple) -> tuple:
        """Per-slot extra model inputs for a chunked prefill: batch-1 slices
        of ``extra_inputs`` — except the enc-dec encoder input, which is
        encoded ONCE here so every chunk reuses the states."""
        ex = tuple(e[i:i + 1] for e in extra)
        if self.encode_fn is not None and ex:
            return (self.encode_fn(self.params, ex[0]),) + ex[1:]
        return ex

    def _observe_step(self, stats: dict, tier_idx: np.ndarray,
                      active: Optional[np.ndarray], audit: bool) -> None:
        """Fold one decode step's (L, B) telemetry into the controller:
        per-tier aggregation when tiered, masked batch mean otherwise
        (``active`` None means every slot is live — generate())."""
        ctl = self.controller
        with self.metrics.span("serve.sync"):
            stats = {k: np.asarray(v) for k, v in stats.items()}
        if isinstance(ctl, DistributedController):
            # strip (and, off-audit, fold into the skew EMAs) the per-shard
            # rider before the (L, B) aggregation paths see the dict
            stats = ctl.consume_shard_stats(stats, active, fold=not audit)
        if ctl.tiers:
            agg, counts = aggregate_tier_stats(stats, tier_idx, ctl.n_tiers,
                                               active)
            ctl.observe(agg, audit=audit, tier_counts=counts)
        else:
            sel = slice(None) if active is None else active
            ctl.observe({k: v[:, sel].mean(-1) for k, v in stats.items()},
                        audit=audit)

    def _uniform_alpha_serve(self, requests: list[Request]) -> bool:
        """True when every request decodes with the unmodified schedule, so
        the legacy no-alphas decode jit (bit-identical to the seed path)
        can serve the whole queue."""
        if self.controller is not None:
            return False
        if not self.cfg.sparse.enabled or self.cfg.family == "xlstm":
            return True
        return all(self._tier_offsets[self._tier_of(r)] == 0.0
                   for r in requests)

    # ----------------------------------------------------------- single ---
    def generate(self, prompts: np.ndarray, max_new: int) -> np.ndarray:
        """prompts: (B, P) int32 -> (B, max_new) generated ids (greedy).

        One fixed batch run to completion (the chunked scheduler's inner
        loop; also the reference path for scheduler parity tests).  All
        slots share the 'balanced' alpha; a tiered controller contributes
        its balanced-tier vector."""
        with self._mesh_ctx():
            return self._generate(prompts, max_new)

    def _generate(self, prompts: np.ndarray, max_new: int) -> np.ndarray:
        b, plen = prompts.shape
        extra = tuple(self.extra.values())
        logits, caches = self.prefill_fn(self.params, jnp.asarray(prompts),
                                         *extra)
        tok = greedy_sample(logits)[:, None]
        out = [tok]
        length = jnp.int32(plen)
        ctl = self.controller
        bal = self._tier_index.get("balanced")
        if bal is None:
            if ctl is not None and ctl.tiers:
                raise ValueError(
                    "generate() runs the whole batch on the 'balanced' tier "
                    "but ServeConfig.sla_tiers has no such tier "
                    f"({sorted(self._tier_index)}); use serve() with "
                    "per-request SLAs or add a 'balanced' tier")
            bal = 0
        for _ in range(max_new - 1):
            if ctl is None:
                tok, caches = self.decode_fn(self.params, tok, caches, length)
            else:
                audit = ctl.is_audit_step()
                self._select_bucket()  # between-step capacity bucket switch
                fn = self.decode_audit_fn if audit else self.decode_ctrl_fn
                if ctl.tiers:
                    alphas = self._slot_alpha_matrix(np.full(b, bal))
                else:
                    alphas = self._pad_layers(ctl.alphas())
                if self.scfg.warm_buckets and not self._warmed_buckets:
                    self._warm_bucket_ladder(tok, caches, length,
                                            alphas)
                tok, caches, stats = fn(self.params, tok, caches, length,
                                        jnp.asarray(alphas))
                # stats come back (L, B); aggregate over the uniform batch
                self._observe_step(stats, np.full(b, bal), None, audit)
            tok = tok[:, None]
            out.append(tok)
            length = length + 1
        return np.asarray(jnp.concatenate(out, axis=1))

    # ------------------------------------------------------ batched queue --
    def serve(self, requests: list[Request]) -> list[Request]:
        """Run a queue of requests through the scheduler.  Slot-refill
        continuous batching by default (each request measured and retired
        individually); ``ServeConfig.slot_refill=False`` selects the legacy
        chunked scheduler."""
        # validate the whole queue BEFORE any work: a bad request must not
        # abort a half-served batch (and the chunked path would otherwise
        # silently clamp oversized cache writes)
        t_adm = self._now()           # admission: latency clocks start HERE
        for r in requests:
            self._tier_of(r)
            # reset EVERY serve-set stamp, not just t_admit: Request objects
            # are mutated in place during serve(), so a re-served object
            # would otherwise leak the previous run's t_start/t_end/ttft
            # into this run's report (stale t_end > 0 even counts it as
            # served before its slot ever finishes)
            r.t_admit = t_adm
            r.t_start = r.t_end = 0.0
            r.queue_wait_s = r.ttft_s = r.latency_s = 0.0
            r.out = None
            r.outcome = r.shed_reason = ""
            r.preemptions = 0
            if r.deadline_s <= 0.0 and self.scfg.default_deadline_s > 0.0:
                r.deadline_s = self.scfg.default_deadline_s
            if len(r.prompt) + r.max_new > self.scfg.max_len:
                raise ValueError(
                    f"request {r.uid}: prompt {len(r.prompt)} + max_new "
                    f"{r.max_new} exceeds max_len {self.scfg.max_len}")
        # bounded queue depth (DESIGN.md §11): overflow sheds NOW, before
        # any compute — the client sees the rejection immediately instead
        # of a deadline miss after minutes in a hopeless backlog
        hub = self.metrics
        hub.event("serve_start", requests=len(requests))
        overflow: list[Request] = []
        mqd = self.scfg.max_queue_depth
        if mqd and len(requests) > mqd:
            requests, overflow = requests[:mqd], requests[mqd:]
            for r in overflow:
                r.outcome, r.shed_reason = "shed", "queue_depth"
                r.out = np.zeros(0, np.int32)
                hub.inc("requests_shed", reason="queue_depth")
                hub.event("shed", uid=r.uid, reason="queue_depth")
            self.shed_count += len(overflow)
        if self.scfg.slot_refill:
            try:
                with self._mesh_ctx():
                    done = self._serve_slot_refill(requests)
            except Exception:
                # serve-abort recovery (DESIGN.md §11): the scheduler died
                # mid-drain with slots/pool/controller half-mutated — reset
                # to fresh-construction state so the NEXT serve is sound,
                # then let the caller see the original failure
                self.reset()
                raise
            self.save_controller()  # persistence point (DESIGN.md §8)
            self._serve_epilogue()
            return done + overflow
        # chunk composition is deterministic, so padded-chunk overflow
        # (chunk-max prompt + chunk-max budget) is also checkable up front
        pc = self.scfg.prefill_chunk
        for c0 in range(0, len(requests), self.scfg.batch):
            chunk = requests[c0:c0 + self.scfg.batch]
            plen = max(len(r.prompt) for r in chunk)
            if pc:   # ladder-padded prompt length (satellite retrace fix)
                plen = -(-plen // pc) * pc
            need = plen + max(r.max_new for r in chunk)
            if need > self.scfg.max_len:
                raise ValueError(
                    f"chunk {c0 // self.scfg.batch}: padded prompt + chunk "
                    f"max_new = {need} exceeds max_len {self.scfg.max_len}")
        try:
            with self._mesh_ctx():
                done = self._serve_chunked(requests)
        except Exception:
            self.reset()
            raise
        self.save_controller()
        self._serve_epilogue()
        return done + overflow

    def _serve_chunked(self, requests: list[Request]) -> list[Request]:
        """Legacy scheduler: fixed chunks of scfg.batch run to completion
        (every request in a chunk waits for the chunk's slowest; uniform
        alpha — per-request SLA tiers need the slot-refill scheduler).
        Prompts in a chunk are right-aligned to the same length."""
        if any(self._tier_offsets[self._tier_of(r)] != 0.0
               for r in requests):
            warnings.warn(
                "chunked scheduler ignores per-request SLA tiers (the whole "
                "chunk decodes on the uniform schedule); use slot_refill "
                "for per-request alphas (DESIGN.md §5)", stacklevel=2)
        queue = list(requests)
        done: list[Request] = []
        while queue:
            chunk, queue = queue[:self.scfg.batch], queue[self.scfg.batch:]
            t0 = self._now()
            plen = max(len(r.prompt) for r in chunk)
            if self.scfg.prefill_chunk:
                # pad the batch's prompt length up to the chunk ladder: the
                # prefill jit cache is then bounded at max_len/prefill_chunk
                # shapes instead of one trace per distinct prompt length
                # (the per-prompt-length retrace storm).  Right-align pad
                # semantics are unchanged — just more leading pad columns.
                pc = self.scfg.prefill_chunk
                plen = -(-plen // pc) * pc
            prompts = np.zeros((self.scfg.batch, plen), np.int32)
            for i, r in enumerate(chunk):
                prompts[i, plen - len(r.prompt):] = r.prompt
            max_new = max(r.max_new for r in chunk)
            gen = self.generate(prompts, max_new)
            t1 = self._now()
            for i, r in enumerate(chunk):
                r.out = gen[i, :r.max_new]
                r.outcome = "completed"
                r.t_start, r.t_end = t0, t1
                r.queue_wait_s = t0 - r.t_admit if r.t_admit else 0.0
                # admission -> last token (the documented latency contract;
                # dequeue-relative timing under-counted by the queue wait)
                r.latency_s = t1 - (r.t_admit if r.t_admit else t0)
                done.append(r)
            self.maybe_adapt_capacity()  # re-jit boundary (DESIGN.md §4)
        return done

    def _serve_slot_refill(self, requests: list[Request]) -> list[Request]:
        """Slot-refill continuous batching (DESIGN.md §5).

        Host-side per-slot state: the owning request, its emitted-token
        buffer and cache length.  The jitted decode step sees only fixed
        shapes — tokens (B, 1), lengths (B,), alphas (L,) or (L, B) — so
        refilling a slot (batch-1 prefill + cache splice + new column
        values) never retraces.  Per-request wall-clock latency runs from
        admission to last token."""
        scfg, B = self.scfg, self.scfg.batch
        ctl = self.controller
        hub = self.metrics          # no-op methods when disabled (§12)
        queue = collections.deque(requests)
        done: list[Request] = []
        # victim ordering for preemption/shedding (DESIGN.md §11): lowest
        # tier priority first, then fewest emitted tokens (least sunk
        # work), then slot index — fully deterministic
        prio = np.asarray([t.priority for t in scfg.sla_tiers], np.int64)

        paged = self.kv_pool is not None
        pool_mgr = self.kv_pool
        if paged:
            # resolve session-sticky SLA tiers BEFORE the uniform-alpha
            # fast-path check below: deciding `legacy` from the *declared*
            # tiers would route a zero-offset (e.g. default 'balanced')
            # turn-2 request whose session is sticky on a non-zero tier
            # down the no-alphas decode jit, silently dropping the stored
            # tier (DESIGN.md §10).  Sessions stored mid-serve can only
            # inherit tiers already resolved here, so the check stays
            # sound for same-queue multi-turn traffic too.
            for r in requests:
                sess = pool_mgr.lookup_session(r.session_id)
                if sess is not None:
                    r.sla = sess["tier"]
        legacy = self._uniform_alpha_serve(requests)
        if paged:
            # the device pool persists across serve() calls (sessions and
            # committed prefixes keep admitting by reference); ``caches``
            # aliases it for the loop and is written back at the end
            caches = self._pool
            bs_, nbps = pool_mgr.block_size, self._nbps
            table = np.full((B, nbps), KVPool.TRASH, np.int32)
            slot_meta: list[Optional[dict]] = [None] * B
        else:
            caches = self.mod.init_caches(self.cfg, B, scfg.max_len)
            table = None
        extra = tuple(self.extra.values())
        tok = np.zeros((B, 1), np.int32)
        lengths = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        tier_idx = np.zeros(B, np.int64)
        slot_req: list[Optional[Request]] = [None] * B
        slot_out: list[list[int]] = [[] for _ in range(B)]

        # per-slot chunked-prefill state (DESIGN.md §9): a slot mid-prefill
        # is NOT active — the decode union sees it exactly like a dead slot
        # (DEAD_SLOT_ALPHA via the ``active`` mask) until its last chunk
        # splices the scratch caches in and the first token lands
        pending: dict[int, dict] = {}
        alpha_mat: Optional[np.ndarray] = None  # cached off-controller matrix
        # collect prefill telemetry only where sparse prefill actually runs
        # (mlp_apply forces prefill dense under tp/dp sharding)
        prefill_stats = (ctl is not None and self.cfg.sparse.enabled
                         and self.cfg.sparse.sparse_prefill
                         and not (self.cfg.sparse.tp_shards
                                  or self.cfg.sparse.dp_shards))

        def finish(i: int) -> None:
            r = slot_req[i]
            r.out = np.asarray(slot_out[i][: r.max_new], np.int32)
            r.outcome = "completed"
            r.t_end = self._now()
            # admission -> last token (the documented latency contract; the
            # old dequeue-relative clock silently excluded the queue wait)
            r.latency_s = r.t_end - (r.t_admit if r.t_admit else r.t_start)
            hub.inc("requests_completed")
            hub.observe("latency_s", r.latency_s, tier=r.sla)
            hub.event("complete", uid=r.uid, tier=r.sla,
                      tokens=len(r.out), latency_s=r.latency_s)
            done.append(r)
            if paged:
                _release_slot(i, r)
            slot_req[i] = None
            active[i] = False

        # ---- overload handling (DESIGN.md §11) ---------------------------
        # admission back-off latch: set when a placement failed on pool
        # exhaustion with resident work to wait for, cleared by any
        # block-release event (finish/shed/preempt/kill).  While set, no
        # admission is attempted — the alternative (equal-tier admission
        # preemption) ping-pongs: each admitted request parks the other
        # until both burn their preemption budget and shed, where simply
        # waiting completes everything serially.
        pool_wait = [False]

        def _expired(r: Request, now: float) -> bool:
            return (r.deadline_s > 0.0 and r.t_admit > 0.0
                    and now - r.t_admit > r.deadline_s)

        def _shed(r: Request, reason: str, toks=None) -> None:
            """Terminal shed: returned to the caller with whatever tokens
            it emitted, excluded from served throughput (t_end stays 0)."""
            r.outcome, r.shed_reason = "shed", reason
            r.out = np.asarray(toks if toks is not None else [], np.int32)
            self.shed_count += 1
            hub.inc("requests_shed", reason=reason)
            hub.event("shed", uid=r.uid, tier=r.sla, reason=reason,
                      tokens=len(r.out))
            done.append(r)

        def _clear_slot(i: int) -> None:
            nonlocal alpha_mat
            slot_req[i] = None
            slot_out[i] = []
            active[i] = False
            alpha_mat = None              # slot composition changed

        def _shed_slot(i: int, reason: str) -> None:
            r = slot_req[i]
            if paged:
                _release_slot(i, r, store=False)
            _shed(r, reason, toks=slot_out[i])
            _clear_slot(i)

        def _preempt_slot(i: int) -> None:
            """Tier-aware preemption: park slot i's prompt blocks in the
            prefix trie (refcount 0 + committed = evictable yet matchable,
            so resume re-admits them by reference at zero re-prefill
            cost), free its decode-origin blocks, requeue the request.
            Emitted tokens are discarded and re-decoded on resume — greedy
            decode is deterministic, so under a per-slot-exact strategy
            (masked; gather when the union adds no neurons) the resumed
            output is bitwise the uninterrupted one; keeping the tokens
            and re-prefilling them would NOT be (decode-origin KV is not
            bitwise prefill KV — kv_pool module docstring).  A
            request past ``max_preemptions`` sheds instead: the livelock
            guard for a pool that can never hold it."""
            r = slot_req[i]
            if r.preemptions >= scfg.max_preemptions:
                _shed_slot(i, "pool")
                return
            _release_slot(i, r, store=False)
            r.preemptions += 1
            r.outcome = "preempted"       # transient; terminal on finish/shed
            self.preempt_count += 1
            hub.inc("preemptions", tier=r.sla)
            hub.event("preempt", uid=r.uid, tier=r.sla,
                      preemptions=r.preemptions)
            _clear_slot(i)
            queue.append(r)

        def _relieve(exclude: Optional[int] = None,
                     max_prio: Optional[int] = None) -> bool:
            """Free pool headroom by preempting the victim-ordered
            lowest-priority active slot.  ``exclude`` (the slot needing
            the block) is only chosen when it is the sole candidate —
            preempting it is then correct: the pool cannot currently hold
            it, and requeueing beats crashing.  ``max_prio`` (admission
            relief) restricts victims to STRICTLY lower priority: an
            incoming request may displace cheaper work but never a peer —
            equal tiers wait their turn (see ``pool_wait``)."""
            cands = [j for j in range(B) if active[j] and j != exclude
                     and slot_meta[j] is not None
                     and (max_prio is None or prio[tier_idx[j]] < max_prio)]
            if (not cands and max_prio is None and exclude is not None
                    and active[exclude] and slot_meta[exclude] is not None):
                cands = [exclude]
            if not cands:
                return False
            victim = min(cands, key=lambda j: (prio[tier_idx[j]],
                                               len(slot_out[j]), j))
            _preempt_slot(victim)
            return True

        def _kill_pending(i: int, reason: str) -> None:
            """Abort a mid-prefill admission (deadline expiry or injected
            slot death): drop the references _match_reuse took — adopted
            AND unconsumed cow candidates — discard the scratch, shed."""
            pool_wait[0] = False
            st = pending.pop(i)
            m = st.get("meta")
            if paged and m is not None:
                for b in m["ids"] + m.get("cow_ids", []):
                    pool_mgr.release(b)
            _shed(st["req"], reason)

        def _release_slot(i: int, r: Request, store: bool = True) -> None:
            """Retire slot i's block-table row (DESIGN.md §10): commit this
            request's prefill-origin full prompt blocks into the trie
            (dedup against existing chains), then either retain the whole
            chain — prompt AND decode-written reply blocks, incl. the
            partial tail — under the request's session, or release every
            reference (committed blocks park in the evictable LRU, decode
            blocks free immediately).  ``store=False`` (preemption and
            shedding) never stores the session: the turn is incomplete —
            but the prompt blocks still commit, which is exactly what
            makes a preempted request's resume admit by reference."""
            pool_wait[0] = False          # headroom released below
            meta = slot_meta[i]
            written = int(lengths[i])          # prompt + decoded-token KV
            n_chain = -(-written // bs_) if written else 0
            chain = [int(table[i, j]) for j in range(n_chain)]
            # full prompt blocks are prefill-origin — trie-committable;
            # decode-origin KV is NOT bitwise re-prefill content, so it
            # stays session-only (module docstring of runtime/kv_pool.py)
            n_prompt_full = meta["plen"] // bs_
            chain[:n_prompt_full] = pool_mgr.commit_chain(
                meta["hashes"][:n_prompt_full], chain[:n_prompt_full],
                owned_from=meta["adopted"])
            sid = r.session_id
            if store and sid is not None:
                hist = np.concatenate(
                    [np.asarray(r.prompt, np.int32),
                     np.asarray(slot_out[i], np.int32)])[:written]
                tier = self.scfg.sla_tiers[meta["tier"]].name
                pool_mgr.store_session(sid, chain, hist, tier)
            else:
                for b in chain:
                    pool_mgr.release(b)
            table[i, :] = KVPool.TRASH
            slot_meta[i] = None

        def place(i: int, r: Request, first: int, plen: int, t: int,
                  one, meta: Optional[dict] = None) -> bool:
            """Activate slot i with a finished prefill: splice the batch-1
            caches (dense) or scatter them into owned pool blocks (paged),
            seed the token/length/tier columns, stamp TTFT.  Returns False
            when the pool could not hold the request even after preemption
            relief — the request is shed and the slot left empty."""
            nonlocal caches, alpha_mat
            now = self._now()
            r.ttft_s = now - (r.t_admit if r.t_admit else r.t_start)
            hub.observe("ttft_s", r.ttft_s, tier=r.sla)
            hub.event("first_token", uid=r.uid, tier=r.sla, ttft_s=r.ttft_s)
            slot_req[i] = r
            slot_out[i] = [first]
            tok[i, 0] = first
            lengths[i] = plen
            tier_idx[i] = t
            active[i] = True
            if paged:
                meta = meta or {"adopted": 0, "ids": [],
                                "hashes": pool_mgr.block_hashes(
                                    self._prefill_salt(t),
                                    np.asarray(r.prompt, np.int32))}
                nb_re = meta["adopted"]
                nb_prompt = -(-plen // bs_)
                # matched blocks past the chunk-aligned reuse boundary are
                # re-run, so they are adopted for WRITING: shared/pinned
                # originals fork (copy-on-write) — no device copy needed,
                # the commit scatter below fully rewrites every owned
                # block (bitwise-identically for the matched ones).  The
                # reference on each cow_id was taken back in _match_reuse
                # (stale-id race guard); ensure_writable consumes it either
                # way — kept as the table-row ref in place, or decref'd on
                # fork.  _match_reuse only returns cow_ids for matched full
                # prompt blocks, so len(cow_ids) <= nb_prompt - nb_re and
                # this loop consumes every held reference.
                extra_ids = meta.get("cow_ids", [])
                owned = []
                j = nb_re
                while j < nb_prompt:
                    k = j - nb_re
                    try:
                        if k < len(extra_ids):
                            # raises BEFORE consuming the held reference:
                            # ensure_writable allocs the fork first, so a
                            # PoolExhausted here leaves extra_ids[k] intact
                            wid, _src = pool_mgr.ensure_writable(
                                extra_ids[k])
                        else:
                            wid = pool_mgr.alloc()
                    except PoolExhausted:
                        if not scfg.preempt:
                            raise         # legacy hard failure preserved
                        # slot i is mid-placement (no meta yet, nothing
                        # releasable) — it must never be its own victim;
                        # admission relief only displaces STRICTLY lower
                        # tiers (peers wait, see pool_wait)
                        if _relieve(exclude=i, max_prio=int(prio[t])):
                            continue      # headroom freed; retry this block
                        # can't fit now: roll back every reference this
                        # placement holds — blocks owned so far, unconsumed
                        # cow candidates, and the adopted ids never written
                        # into the table
                        for b in owned:
                            pool_mgr.release(b)
                        for b in extra_ids[k:]:
                            pool_mgr.release(b)
                        for b in meta["ids"]:
                            pool_mgr.release(b)
                        table[i, :] = KVPool.TRASH
                        _clear_slot(i)
                        if active.any() or pending:
                            # resident work will release blocks: park at
                            # the queue HEAD (FIFO order preserved) and
                            # latch admissions off until a release event
                            queue.appendleft(r)
                            pool_wait[0] = True
                        else:
                            # nothing resident to wait for — the pool
                            # simply cannot hold this request: shed
                            _shed(r, "pool")
                        return False
                    owned.append(wid)
                    j += 1
                wt = np.full(nbps, KVPool.TRASH, np.int32)
                wt[nb_re:nb_prompt] = owned
                caches = self.commit_fn(caches, one, jnp.asarray(wt))
                table[i, :nb_re] = meta["ids"][:nb_re]
                table[i, nb_re:nb_prompt] = owned
                table[i, nb_prompt:] = KVPool.TRASH
                meta["plen"] = plen
                meta["tier"] = t
                slot_meta[i] = meta
            else:
                caches = self.splice_fn(caches, one, jnp.int32(i))
            alpha_mat = None              # slot composition changed
            return True

        def admit(i: int) -> None:
            """Fill slot i from the queue, one "serve.admit" span per
            request dequeued (through placement or pending, an
            expired-at-dequeue shed included)."""
            while queue:
                if pool_wait[0]:
                    return        # exhaustion latch: wait for a release
                if (paged and scfg.pressure_gate < 1.0
                        and (active.any() or pending)
                        and pool_mgr.pressure() >= scfg.pressure_gate):
                    # admission backpressure (DESIGN.md §11): above the
                    # gate a refill would only feed the eviction cascade —
                    # defer until resident work drains.  Never defers when
                    # nothing is resident, so progress is guaranteed.
                    self.admissions_deferred += 1
                    return
                r = queue.popleft()
                with hub.span("serve.admit", uid=r.uid, slot=i):
                    if not _admit_one(i, r):
                        return

        def _admit_one(i: int, r: Request) -> bool:
            """Admit the dequeued ``r`` into slot i; True when the slot is
            still free and admission should go on to the next request.
            With chunked prefill the slot goes PENDING (scratch caches;
            chunks advance interleaved with decode steps); otherwise the
            monolithic batch-1 prefill runs at the prompt's natural length
            -> exact single-request semantics, one trace per distinct
            prompt length."""
            if _expired(r, self._now()):
                _shed(r, "deadline")  # expired while queued
                return True
            if paged:
                sess = pool_mgr.lookup_session(r.session_id)
                if sess is not None:
                    # session-sticky SLA: the stored tier binds every
                    # turn of the conversation to one point on the
                    # accuracy/sparsity curve (and, under a per-tier
                    # controller, to one adapted alpha vector) — the
                    # per-session controller state (DESIGN.md §10)
                    r.sla = sess["tier"]
            t = self._tier_of(r)      # queue pre-validated in serve()
            plen = len(r.prompt)
            now = self._now()
            r.t_start = now           # dequeue: service starts
            r.queue_wait_s = now - r.t_admit if r.t_admit else 0.0
            hub.observe("queue_wait_s", r.queue_wait_s, tier=r.sla)
            hub.event("admit", uid=r.uid, tier=r.sla, plen=plen,
                      queue_wait_s=r.queue_wait_s)
            if self._chunk_prefill:
                pc = self.scfg.prefill_chunk
                padded = -(-plen // pc) * pc
                toks = np.zeros((1, padded), np.int32)
                toks[0, :plen] = np.asarray(r.prompt, np.int32)
                st = {
                    "req": r, "tier": t, "tokens": toks, "off": 0,
                    "plen": plen,
                    "caches": self.mod.init_caches(self.cfg, 1,
                                                   scfg.max_len),
                    "extra": self._slot_extra(i, extra),
                }
                if paged:
                    st["meta"] = self._match_reuse(r, t, plen)
                    m = st["meta"]
                    if m["adopted"]:
                        # admit by reference: seed the scratch with the
                        # adopted blocks and start chunking at the
                        # reuse boundary — the skipped chunks' work is
                        # exactly what the pool already holds
                        st["off"] = m["adopted"] * bs_
                        seed = np.zeros(nbps, np.int32)   # NULL lanes
                        seed[:m["adopted"]] = m["ids"][:m["adopted"]]
                        st["caches"] = self.seed_fn(caches,
                                                    jnp.asarray(seed))
                        self.prefill_chunks_skipped += st["off"] // pc
                pending[i] = st
                return False
            prompt = jnp.asarray(
                np.asarray(r.prompt, np.int32)[None, :])
            ex = tuple(e[i:i + 1] for e in extra)
            try:
                self._fault("prefill", r.uid)
            except InjectedFault:
                _shed(r, "fault")     # injected slot death mid-prefill
                return True
            with hub.span("serve.prefill", hist="prefill_s", slot=i,
                          uid=r.uid):
                logits, one = self.prefill_fn(self.params, prompt, *ex)
                with hub.span("serve.sync"):
                    first = int(np.asarray(greedy_sample(logits))[0])
            if not place(i, r, first, plen, t, one):
                # shed on pool exhaustion: try the next, unless
                # backpressure latched
                return not pool_wait[0]
            if r.max_new <= 1:
                finish(i)     # prefill alone satisfied it; keep draining
                return True
            return False

        def advance_prefill(budget: int) -> None:
            """Run up to ``budget`` prefill chunks (round-robin over pending
            slots): ServeConfig.prefill_interleave chunks per decode-loop
            iteration is the TTFT-vs-ITL knob (DESIGN.md §9)."""
            pc = self.scfg.prefill_chunk
            while budget > 0 and pending:
                for i in sorted(pending):
                    if budget <= 0:
                        break
                    st = pending[i]
                    r = st["req"]
                    try:
                        self._fault("prefill", r.uid)
                    except InjectedFault:
                        # injected mid-prefill slot death: the admission
                        # dies cleanly (references dropped, request shed)
                        # and the slot refills from the queue
                        _kill_pending(i, "fault")
                        admit(i)
                        continue
                    with hub.span("serve.prefill_chunk",
                                  hist="prefill_chunk_s", slot=i,
                                  uid=r.uid):
                        _prefill_chunk(i, st, r, pc)
                    budget -= 1

        def _prefill_chunk(i: int, st: dict, r: Request, pc: int) -> None:
            """Slice, transfer and dispatch slot i's next chunk; after the
            last one, wait for its first token and place the request."""
            chunk_toks = jnp.asarray(
                st["tokens"][:, st["off"]:st["off"] + pc])
            al = jnp.asarray(self._prefill_alphas(st["tier"]))
            fn = (self.prefill_chunk_stats_fn if prefill_stats
                  else self.prefill_chunk_fn)
            out = fn(self.params, chunk_toks, st["caches"],
                     jnp.int32(st["off"]), jnp.int32(st["plen"]),
                     al, *st["extra"])
            if prefill_stats:
                logits, st["caches"], stats = out
                with hub.span("serve.sync"):
                    stats = {k: np.asarray(v)[:, 0]
                             for k, v in stats.items()}
                ctl.observe_prefill(stats,
                                    tier=st["tier"] if ctl.tiers else None)
            else:
                logits, st["caches"] = out
            st["off"] += pc
            self.prefill_chunks_run += 1
            if st["off"] >= st["tokens"].shape[1]:
                with hub.span("serve.sync"):
                    first = int(np.asarray(greedy_sample(logits))[0])
                del pending[i]
                if not place(i, r, first, st["plen"], st["tier"],
                             st["caches"], meta=st.get("meta")):
                    admit(i)   # requeued/shed; admit() no-ops
                    #            while the exhaustion latch holds
                elif r.max_new <= 1:
                    finish(i)
                    admit(i)   # refill: may re-enter pending

        def ensure_write_blocks() -> None:
            """Before a decode step, every live slot's write position
            (``lengths[i]``) must land in a block the slot exclusively
            owns: allocate on first touch of each block (TRASH lanes are
            the dead/pending write-off and the unallocated tail).  Under
            ``preempt``, exhaustion here preempts the lowest-priority
            victim instead of raising — possibly the needing slot itself,
            whose loop then exits with nothing to write (DESIGN.md §11).
            Terminates: every retry preempts (or sheds) one active slot."""
            for i in range(B):
                if not active[i]:
                    continue
                j = int(lengths[i]) // bs_
                while active[i] and table[i, j] == KVPool.TRASH:
                    try:
                        table[i, j] = pool_mgr.alloc()
                    except PoolExhausted:
                        if not scfg.preempt:
                            raise     # legacy hard failure preserved
                        _relieve(exclude=i)  # slot i active => a victim exists

        def schedule() -> None:
            """One pass's scheduling: deadline sheds, deadline-pressure
            preemption and the refill of empty slots."""
            self._tick()
            now = self._now()
            # deadline enforcement (DESIGN.md §11): resident and
            # mid-prefill requests past their deadline shed with whatever
            # they already emitted (queued ones shed at dequeue in admit)
            for i in range(B):
                if active[i] and _expired(slot_req[i], now):
                    _shed_slot(i, "deadline")
            for i in [j for j in sorted(pending)
                      if _expired(pending[j]["req"], now)]:
                _kill_pending(i, "deadline")
            # deadline-pressure preemption: the queue HEAD has burned half
            # its deadline waiting and a strictly-lower-priority victim is
            # resident — park the victim so the urgent request admits into
            # the freed slot on this very iteration (FIFO head first)
            if scfg.preempt and queue:
                h = queue[0]
                if (h.deadline_s > 0.0 and h.t_admit > 0.0
                        and now - h.t_admit >= 0.5 * h.deadline_s):
                    cands = [j for j in range(B) if active[j]
                             and prio[tier_idx[j]] < prio[self._tier_of(h)]]
                    if cands:
                        _preempt_slot(min(
                            cands, key=lambda j: (prio[tier_idx[j]],
                                                  len(slot_out[j]), j)))
            # refill empty slots: covers deferred admissions retrying as
            # pressure drops, and slots freed by shed/preempt above (the
            # post-decode refill in commit covers normal completions)
            if queue and not pool_wait[0]:
                for i in range(B):
                    if slot_req[i] is None and i not in pending:
                        admit(i)

        def decode() -> np.ndarray:
            """One decode step over every slot: the controller's bucket
            choice, the slot inputs' transfer, the dispatch, the
            controller's update, and the wait for the step's tokens."""
            nonlocal caches, alpha_mat
            if ctl is not None:
                with hub.span("serve.controller"):
                    audit = ctl.is_audit_step()
                    # between-step capacity-bucket switch: a host dict
                    # lookup into the pre-jitted (per-shard tuple) ladder —
                    # never a retrace
                    prev_cap = self._active_cap
                    self._select_bucket()
                    if hub.enabled and self._active_cap != prev_cap:
                        hub.inc("bucket_switches")
                        hub.event("bucket_switch", bucket=self._active_cap)
                    fn = self.decode_audit_fn if audit else self.decode_ctrl_fn
            with hub.span("serve.put_slots"):
                if ctl is not None:
                    # rebuilt per step: the controller adapts between steps
                    alphas = self._slot_alpha_matrix(tier_idx, active)
                elif legacy and active.all():
                    # uniform schedule, every slot live: the seed decode jit
                    # (bit-identical path; no alpha plumbing at all)
                    alphas = None
                else:
                    # static alphas change only at refill boundaries — cache
                    # the matrix; dead slots are neutralized out of the union
                    if alpha_mat is None:
                        alpha_mat = self._slot_alpha_matrix(tier_idx, active)
                    alphas = alpha_mat
                # tab: the block table, paged only
                jt, jl, ja, *tab = self._put_slots(tok, lengths, alphas,
                                                   table)
            with hub.span("serve.decode_dispatch"):
                if ctl is not None:
                    ntok, caches, stats = fn(self.params, jt, caches, jl, ja,
                                             *tab)
                elif alphas is None:
                    ntok, caches = self.decode_fn(self.params, jt, caches,
                                                  jl, *tab)
                else:
                    ntok, caches = self.decode_alpha_fn(
                        self.params, jt, caches, jl, ja, *tab)
            if ctl is not None:
                with hub.span("serve.controller",
                              hist="controller_update_s"):
                    self._observe_step(stats, tier_idx, active, audit)
            with hub.span("serve.sync"):
                return np.asarray(ntok)

        def commit(ntok: np.ndarray) -> None:
            """Append the step's tokens, finish the slots that are done
            and refill them from the queue."""
            nonlocal alpha_mat
            refill = []
            for i in range(B):
                if not active[i]:
                    continue
                slot_out[i].append(int(ntok[i]))
                tok[i, 0] = int(ntok[i])
                lengths[i] += 1
                if len(slot_out[i]) >= slot_req[i].max_new:
                    finish(i)
                    refill.append(i)
            if refill:
                alpha_mat = None             # slot composition changed
                if queue:
                    self.maybe_adapt_capacity()  # re-jit (DESIGN.md §4)
                    for i in refill:
                        admit(i)

        # Every pass of the loop is one "serve.iteration" profiler step
        # (pass 0: the initial fill); its children cover the pass's host
        # work, and "serve.sync" every wait on device results, so a
        # profiler capture puts each device idle gap down to a phase.
        it = 0
        with hub.span("serve.iteration", step_num=it), \
                hub.span("serve.schedule"):
            for i in range(B):
                admit(i)
            if (ctl is not None and scfg.warm_buckets
                    and not self._warmed_buckets and active.any()):
                if paged:
                    ensure_write_blocks()
                self._warm_bucket_ladder(
                    tok, caches, lengths,
                    self._slot_alpha_matrix(tier_idx, active),
                    table=table if paged else None)
        # queue can be non-empty with every slot idle (admissions deferred
        # by the pressure gate, or slots freed by shed/preempt): the loop
        # runs until all three drain.  Each iteration either decodes,
        # prefills, admits, or sheds — and the virtual clock ticks
        # regardless — so it always terminates.
        step_n = 0                    # decode steps (gauge-publish cadence)
        while active.any() or pending or queue:
            it += 1
            with hub.span("serve.iteration", step_num=it):
                with hub.span("serve.schedule"):
                    schedule()
                if pending:
                    # interleave admissions with decode: ≤
                    # prefill_interleave chunks per iteration so a long
                    # admission never stalls the resident requests for its
                    # whole prompt (DESIGN.md §9)
                    advance_prefill(scfg.prefill_interleave)
                    if not active.any():
                        continue     # nothing decoding yet — keep prefilling
                if not active.any():
                    continue         # deferred/shed everything this pass
                if paged:
                    with hub.span("serve.schedule"):
                        ensure_write_blocks()
                    if not active.any():
                        continue     # exhaustion relief preempted every slot
                self._fault("decode")   # armed decode faults are FATAL: they
                #                         abort serve() and exercise reset()
                # the decode phase ends at host materialization (np.asarray
                # blocks on the step's device work); under the virtual clock
                # the span is 0-duration and purely structural
                with hub.span("serve.decode", hist="decode_step_s"):
                    ntok = decode()
                with hub.span("serve.commit"):
                    step_n += 1
                    if hub.enabled and step_n % scfg.metrics.cadence == 0:
                        self.publish_gauges()
                    commit(ntok)
        if paged:
            # the pool outlives the drain: sessions + committed prefixes
            # admit by reference in later serve() calls (DESIGN.md §10)
            self._pool = caches
        return done


def throughput_report(requests: list[Request]) -> dict:
    """Aggregate a served queue: tokens over TRUE wall-clock (first
    admission to last completion — concurrent requests share that window;
    summing per-request latencies would count each decode step once per
    co-resident request and deflate tok/s by ~the batch factor), plus
    per-request latency percentiles.

    Built on an ephemeral exact-mode ``runtime.metrics.MetricsHub``
    (``hist_max_exact=0`` — never folds to buckets), so the report's
    nearest-rank percentiles are EXACT for any queue size while routing
    through the same histogram machinery the live sinks use
    (DESIGN.md §12)."""
    # served = completion stamped and consistent: a half-stamped request
    # (hand-built, or aborted mid-serve) would otherwise poison the
    # wall-clock window.  t_start may legitimately be 0.0 (clock origin),
    # so the gate is on t_end, not both endpoints.
    served = [r for r in requests
              if r.t_end > 0.0 and r.t_end >= r.t_start]
    # tokens counted over the SAME served set that defines the window: an
    # unstamped request's tokens fall outside it and would inflate tok/s
    toks = sum(len(r.out) for r in served if r.out is not None)
    wall = (max(r.t_end for r in served) - min(r.t_start for r in served)
            if served else 0.0)
    hub = MetricsHub(MetricsConfig(enabled=True, hist_max_exact=0,
                                   watchdog=False))
    for r in served:
        hub.observe("latency_s", r.latency_s)
        # TTFT / queue wait only exist where the scheduler stamped them
        # (requests built by hand for report tests carry the 0.0 defaults)
        if r.ttft_s > 0.0:
            hub.observe("ttft_s", r.ttft_s)
        if r.t_admit > 0.0:
            hub.observe("queue_wait_s", r.queue_wait_s)

    def pct(name: str, q: float) -> float:
        # exact nearest-rank (metrics.nearest_rank_pct semantics: ceil(q*n)
        # with float fuzz rounded away — int(q*n) would report the max as
        # p95 for every n <= 20)
        return hub.percentile(name, q)
    # overload outcomes (DESIGN.md §11): every request the scheduler
    # touched ends "completed" or "shed" (with a reason); preemptions
    # count park+requeue events — a preempted-then-completed request
    # appears in both "completed" and "preempted"
    n_shed = sum(1 for r in requests if r.outcome == "shed")
    shed_reasons: dict = {}
    for r in requests:
        if r.outcome == "shed" and r.shed_reason:
            k = f"shed_{r.shed_reason}"   # flat numeric keys: every report
            shed_reasons[k] = shed_reasons.get(k, 0) + 1   # value is scalar
    # an empty/instant window reports an exact 0.0 rate — never NaN, never
    # the absurd toks/1e-9 spike the old clamp produced for zero-duration
    # (e.g. all-cache-hit or hand-stamped) queues
    return {"requests": len(requests), "tokens": toks,
            "completed": sum(1 for r in requests
                             if r.outcome == "completed"),
            "shed": n_shed,
            "shed_rate": float(n_shed / len(requests)) if requests else 0.0,
            **shed_reasons,
            "preempted": sum(1 for r in requests if r.preemptions > 0),
            "preemptions": sum(r.preemptions for r in requests),
            "total_s": wall,
            "tok_per_s": float(toks / wall) if wall > 0.0 else 0.0,
            "mean_latency_s": hub.hist_mean("latency_s"),
            "p50_latency_s": pct("latency_s", 0.5),
            "p95_latency_s": pct("latency_s", 0.95),
            "mean_ttft_s": hub.hist_mean("ttft_s"),
            "p50_ttft_s": pct("ttft_s", 0.5),
            "p95_ttft_s": pct("ttft_s", 0.95),
            "mean_queue_wait_s": hub.hist_mean("queue_wait_s"),
            "p50_queue_wait_s": pct("queue_wait_s", 0.5),
            "p95_queue_wait_s": pct("queue_wait_s", 0.95)}
