"""Serving launcher: batched generation with SparseInfer decode.

CPU demo (reduced config):
    PYTHONPATH=src python -m repro.launch.serve --arch prosparse-llama2-13b \
        --reduced --requests 8 --max-new 16 --strategy gather

Slot-refill continuous batching with a per-request SLA mix (DESIGN.md §5):
    ... --strategy masked --sla-mix latency:1,balanced:2,quality:1 \
        --controller --per-tier

Production: same flags plus --mesh 16x16 (weights TP over 'model').
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Optional

import jax
import numpy as np

from repro.configs.base import (ControllerConfig, MetricsConfig,
                                ModelConfig, PagedKVConfig)
from repro.configs.registry import arch_names, get_config, reduced_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.specs import model_module
from repro.launch.train import parse_mesh
from repro.runtime.metrics import capture
from repro.runtime.server import Request, Server, ServeConfig, \
    throughput_report


def parse_sla_mix(mix: str, n_requests: int) -> list:
    """``"latency:1,balanced:2"`` -> a tier name per request, interleaved
    round-robin in weight proportion (so every scheduler batch sees the
    mix, not a sorted prefix)."""
    pairs = []
    for part in mix.split(","):
        name, _, w = part.strip().partition(":")
        pairs.append((name, int(w) if w else 1))
    total = sum(w for _, w in pairs)
    if total <= 0 or any(w < 0 for _, w in pairs):
        raise ValueError(f"--sla-mix needs positive weights, got {mix!r}")
    out, acc = [], [0.0] * len(pairs)
    for _ in range(n_requests):
        for j, (_, w) in enumerate(pairs):
            acc[j] += w / total
        j = max(range(len(pairs)), key=lambda j: acc[j])
        acc[j] -= 1.0
        out.append(pairs[j][0])
    return out


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=arch_names())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--mesh", default="",
                    help="legacy GSPMD weights-TP mesh (sharding "
                         "constraints only); for the shard_map sparse "
                         "decode subsystem use --mesh-shape")
    # tensor-parallel sparse decode (DESIGN.md §8): the server runs the
    # whole sparse decode step under shard_map over the mesh's 'model'
    # axis (shard-local selection, psum telemetry epilogue, sharded KV)
    ap.add_argument("--mesh-shape", default="",
                    help="serve mesh for the sharded sparse decode "
                         "subsystem, DxM (data x model), e.g. 2x4 (batch "
                         "slots sharded 2-way over 'data', FFN hidden dim "
                         "4-way over 'model'), 1x4, or 4 (model-only); "
                         "tokens and controller telemetry are "
                         "bitwise-identical to the single-device path for "
                         "any placement of the same (data, model) "
                         "semantics")
    ap.add_argument("--controller-ckpt", default="",
                    help="directory for controller-state checkpoints: the "
                         "server restores the latest snapshot at startup "
                         "(alpha/EMA state survives restarts) and writes "
                         "one after each serve drain (DESIGN.md §8)")
    ap.add_argument("--strategy", default=None,
                    choices=[None, "dense", "masked", "gather", "pallas"])
    ap.add_argument("--alpha", type=float, default=None)
    # slot-refill continuous batching + per-request SLA tiers (DESIGN.md §5)
    ap.add_argument("--slot-refill", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="slot-refill continuous batching (default); "
                         "--no-slot-refill selects the legacy chunked "
                         "scheduler")
    ap.add_argument("--sla-mix", default="balanced:1",
                    help="comma list tier:weight (tiers: latency, balanced, "
                         "quality) — requests are assigned tiers "
                         "proportionally, e.g. latency:1,balanced:2,"
                         "quality:1")
    # online adaptive-alpha controller (DESIGN.md §4)
    ap.add_argument("--controller", action="store_true",
                    help="adapt per-layer alpha online toward "
                         "--target-density")
    ap.add_argument("--per-tier", action="store_true",
                    help="one controller (alpha vector, density target) per "
                         "SLA tier (DESIGN.md §5)")
    ap.add_argument("--target-density", type=float, default=0.25)
    ap.add_argument("--ctrl-gain", type=float, default=0.5)
    ap.add_argument("--audit-period", type=int, default=8)
    ap.add_argument("--adapt-capacity", action="store_true",
                    help="re-size gather capacity at refill boundaries "
                         "from the observed keep-rate (re-jit boundary); "
                         "superseded by --capacity-buckets when set")
    ap.add_argument("--capacity-buckets", default="",
                    help="comma list of capacity fractions forming the "
                         "pre-jitted decode-step ladder, e.g. "
                         "0.125,0.25,0.5 — the controller switches buckets "
                         "between decode steps from its union-demand hint "
                         "with no retrace (gather/pallas; DESIGN.md §2)")
    ap.add_argument("--warm-buckets", action="store_true",
                    help="compile every capacity bucket before serving so "
                         "the first switches never stall a request")
    # chunked prefill unified with decode (DESIGN.md §9)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="fixed prefill chunk size in tokens (MXU-aligned "
                         "64/128; must divide --max-len).  0 = monolithic "
                         "prefill.  Slot-refill streams admissions through "
                         "one pre-jitted chunk executable interleaved with "
                         "decode steps (no per-prompt-length retraces); the "
                         "legacy chunked scheduler pads prompt lengths to "
                         "the chunk ladder")
    ap.add_argument("--prefill-interleave", type=int, default=1,
                    help="max prefill chunks advanced per decode-loop "
                         "iteration — the TTFT-vs-ITL knob (higher = "
                         "faster admission, more decode-step jitter)")
    ap.add_argument("--sparse-prefill", action="store_true",
                    help="extend sign-bit sparse prediction to prefill "
                         "chunks (one chunk-union selection per chunk; "
                         "requires --prefill-chunk)")
    # paged KV pool + overload handling (DESIGN.md §10-11)
    ap.add_argument("--paged-kv", type=int, default=0, metavar="BLOCK",
                    help="enable the paged KV pool with this block size in "
                         "tokens (0 = dense per-slot caches); prefix reuse, "
                         "sessions, and preemption all need the pool")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="total pool blocks (0 = auto-size to exactly fit "
                         "--batch x --max-len; smaller values oversubscribe "
                         "the pool, exercising eviction and preemption)")
    ap.add_argument("--max-queue-depth", type=int, default=0,
                    help="admission control: requests beyond this queue "
                         "depth are shed immediately with outcome "
                         "shed/queue_depth (0 = unbounded)")
    ap.add_argument("--default-deadline", type=float, default=0.0,
                    help="seconds from admission before an un-deadlined "
                         "request is shed (0 = none); per-request "
                         "Request.deadline_s overrides")
    ap.add_argument("--preempt", action="store_true",
                    help="tier-aware preemption under pool pressure: park "
                         "the lowest-priority slot's blocks in the prefix "
                         "trie and requeue it (resume re-admits by "
                         "reference) instead of failing the serve; "
                         "requires --paged-kv")
    ap.add_argument("--pressure-gate", type=float, default=1.0,
                    help="defer admissions while pool pressure >= this "
                         "fraction (1.0 = disabled; useful range "
                         "0.8-0.95)")
    # int8 weight quantization (DESIGN.md §13): symmetric per-group absmax
    # over the sparse-MLP matrices, applied at load time; the predictor
    # keeps fp sign-packs so selection sets are identical fp-vs-int8
    ap.add_argument("--weight-dtype", default="", choices=("", "int8"),
                    help="quantize the sparse-MLP weights at load time "
                         "('int8' streams 1-byte tiles + per-group scales "
                         "through the fused kernels; '' = native fp)")
    ap.add_argument("--quant-group-size", type=int, default=128,
                    help="quantization group width (must divide d_model "
                         "and d_ff and be a multiple of the selection "
                         "group size)")
    # first-class observability (DESIGN.md §12): any sink flag enables the
    # metrics hub; --metrics alone enables the in-memory instruments only
    ap.add_argument("--metrics", action="store_true",
                    help="enable the metrics hub (counters/gauges/"
                         "histograms + retrace watchdog) without file "
                         "sinks; implied by any --metrics-* path flag")
    ap.add_argument("--metrics-jsonl", default="", metavar="PATH",
                    help="append structured serve events (admissions, "
                         "first tokens, completions, sheds, preemptions, "
                         "bucket switches, retraces) as JSON lines")
    ap.add_argument("--metrics-trace", default="", metavar="DIR",
                    help="run the serve under a jax.profiler capture into "
                         "DIR: the serve.* phase spans, the model step's "
                         "device ops and the transfers on one clock "
                         "(perfetto_trace.json.gz opens in ui.perfetto.dev)")
    ap.add_argument("--metrics-snapshot", default="", metavar="PATH",
                    help="write a Prometheus-style text exposition of all "
                         "instruments at each serve drain")
    return ap


def build_config(args: argparse.Namespace) -> ModelConfig:
    """The served model configuration the flags select."""
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if args.strategy:
        sp = dataclasses.replace(cfg.sparse, strategy=args.strategy,
                                 enabled=args.strategy != "dense")
        cfg = cfg.replace(sparse=sp)
    if args.alpha is not None:
        cfg = cfg.replace(sparse=dataclasses.replace(
            cfg.sparse, alpha_base=args.alpha, alpha_early=args.alpha))
    if args.capacity_buckets:
        buckets = tuple(float(v) for v in args.capacity_buckets.split(","))
        cfg = cfg.replace(sparse=dataclasses.replace(
            cfg.sparse, capacity_buckets=buckets))
    if args.weight_dtype:
        cfg = cfg.replace(sparse=dataclasses.replace(
            cfg.sparse, weight_dtype=args.weight_dtype,
            quant_group_size=args.quant_group_size))
    if args.sparse_prefill:
        if not args.prefill_chunk:
            raise SystemExit("--sparse-prefill needs --prefill-chunk "
                             "(chunk-union selection is per prefill chunk)")
        cfg = cfg.replace(sparse=dataclasses.replace(
            cfg.sparse, sparse_prefill=True,
            prefill_max_tokens=max(cfg.sparse.prefill_max_tokens,
                                   args.prefill_chunk)))
    return cfg


def init_params(mod, cfg: ModelConfig, seed: int = 0) -> dict:
    """Random weights from ``seed``, initialized under one jit so that a
    full-size model never holds float32 temporaries of every stacked
    layer on the device at once."""
    return jax.jit(mod.init_lm, static_argnums=1)(jax.random.PRNGKey(seed),
                                                  cfg)


def build_server(args: argparse.Namespace, cfg: ModelConfig, mod,
                 params: dict, rng: np.random.Generator,
                 mesh: Optional[jax.sharding.Mesh] = None) -> Server:
    """The Server the flags describe, over ``params`` (stub frontend
    inputs for the vlm / encdec families are drawn from ``rng``)."""
    extra = {}
    if cfg.family == "vlm":
        extra["images"] = jax.numpy.asarray(rng.standard_normal(
            (args.batch, cfg.n_image_tokens, cfg.d_model),
            dtype=np.float32))
    if cfg.family == "encdec":
        extra["frames"] = jax.numpy.asarray(rng.standard_normal(
            (args.batch, cfg.n_frames, cfg.d_model), dtype=np.float32))
    ccfg = ControllerConfig(enabled=args.controller,
                            target_density=args.target_density,
                            gain=args.ctrl_gain,
                            audit_period=args.audit_period,
                            adapt_capacity=args.adapt_capacity,
                            per_tier=args.per_tier)
    paged = (PagedKVConfig(block_size=args.paged_kv,
                           pool_blocks=args.pool_blocks)
             if args.paged_kv else None)
    mcfg = MetricsConfig(
        enabled=bool(args.metrics or args.metrics_jsonl
                     or args.metrics_trace or args.metrics_snapshot),
        jsonl_path=args.metrics_jsonl,
        snapshot_path=args.metrics_snapshot)
    scfg = ServeConfig(batch=args.batch, max_len=args.max_len,
                       max_new_tokens=args.max_new,
                       slot_refill=args.slot_refill, controller=ccfg,
                       warm_buckets=args.warm_buckets,
                       prefill_chunk=args.prefill_chunk,
                       prefill_interleave=args.prefill_interleave,
                       controller_ckpt=args.controller_ckpt, paged_kv=paged,
                       max_queue_depth=args.max_queue_depth,
                       default_deadline_s=args.default_deadline,
                       preempt=args.preempt,
                       pressure_gate=args.pressure_gate, metrics=mcfg)
    return Server(mod, cfg, scfg, params, extra_inputs=extra, mesh=mesh)


def make_requests(args: argparse.Namespace, cfg: ModelConfig,
                  rng: np.random.Generator) -> list:
    """``--requests`` random prompts of ``--prompt-len`` tokens, with SLA
    tiers assigned by ``--sla-mix``."""
    slas = parse_sla_mix(args.sla_mix, args.requests)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, size=args.prompt_len),
                    max_new=args.max_new, sla=slas[i])
            for i in range(args.requests)]


def main() -> None:
    args = build_parser().parse_args()
    use_compile_cache()
    cfg = build_config(args)
    mesh = parse_mesh(args.mesh)
    serve_mesh = None
    if args.mesh_shape:
        if args.mesh:
            raise SystemExit("--mesh and --mesh-shape are exclusive: the "
                             "shard_map subsystem owns the mesh it runs on")
        dims = tuple(int(v) for v in args.mesh_shape.split("x"))
        axes = ("model",) if len(dims) == 1 else ("data", "model")
        serve_mesh = make_mesh(dims, axes)
    mod = model_module(cfg)

    def run():
        rng = np.random.default_rng(0)
        srv = build_server(args, cfg, mod, init_params(mod, cfg), rng,
                           mesh=serve_mesh)
        reqs = make_requests(args, cfg, rng)
        slas = [r.sla for r in reqs]
        with capture(args.metrics_trace):
            t0 = time.perf_counter()
            done = srv.serve(reqs)
            dt = time.perf_counter() - t0
        rep = throughput_report(done)
        rep["wall_s"] = dt
        rep["scheduler"] = ("slot_refill" if args.slot_refill else "chunked")
        rep["sla_mix"] = {s: slas.count(s) for s in dict.fromkeys(slas)}
        # the chunked scheduler decodes every chunk on the uniform schedule
        # (Server warns); don't let the report read as a tiered measurement
        rep["sla_applied"] = bool(args.slot_refill)
        rep["sparse"] = {"enabled": cfg.sparse.enabled,
                         "strategy": cfg.sparse.strategy,
                         "alpha": cfg.sparse.alpha_base,
                         # srv.cfg, not cfg: adapt-capacity may have moved it
                         "capacity_frac": round(
                             srv.cfg.sparse.capacity_frac, 4)}
        if cfg.sparse.capacity_buckets:
            rep["sparse"]["capacity_buckets"] = list(
                cfg.sparse.capacity_ladder(cfg.d_ff))
            rep["sparse"]["active_bucket"] = getattr(srv, "_active_cap",
                                                     None)
        if args.prefill_chunk:
            rep["prefill"] = {
                "chunk": args.prefill_chunk,
                "interleave": args.prefill_interleave,
                "sparse": bool(args.sparse_prefill),
                # one trace per chunk SHAPE after warmup (zero retraces)
                "chunk_traces": {str(k): v
                                 for k, v in srv._prefill_traces.items()},
            }
        if args.paged_kv:
            rep["paged"] = srv.paged_stats()
        if srv.controller is not None:
            rep["controller"] = srv.controller.report()
        if srv.scfg.metrics.enabled:
            rep["metrics"] = srv.metrics_report()
            rep["metrics"]["sinks"] = {
                k: v for k, v in (("jsonl", args.metrics_jsonl),
                                  ("trace", args.metrics_trace),
                                  ("snapshot", args.metrics_snapshot)) if v}
            srv.metrics.close()
        print(json.dumps(rep, indent=1))

    if mesh is not None:
        with mesh:
            run()
    else:
        run()


if __name__ == "__main__":
    main()
