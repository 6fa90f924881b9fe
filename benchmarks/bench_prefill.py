"""Serve-path prefill bench: chunked vs monolithic (DESIGN.md §9).

    PYTHONPATH=src python -m benchmarks.bench_prefill [--quick] \
        [--out BENCH_prefill.json] [--against BENCH_prefill.json]

Runs the same staggered-prompt-length request queue through the
slot-refill scheduler twice — monolithic prefill (``prefill_chunk=0``)
and chunked prefill interleaved with decode — and reports:

* TTFT p50/p95        — admission to first token (the chunked path
                        admits through fixed-shape executables, so a new
                        prompt length never pays a trace)
* ITL p95             — per-request mean inter-token latency,
                        (latency - ttft) / (tokens - 1); the interleave
                        knob trades this against TTFT
* tok/s               — queue tokens over true wall clock
* chunk_traces        — executable count per (chunk shape, collect)
                        (the zero-retraces-after-warmup invariant)
* paged_kv_study      — multi-turn chat over the paged KV pool
                        (DESIGN.md §10): turn-2 prefill-chunk reduction
                        from prefix/session reuse (>= 90%), sessions
                        retained vs dense slot capacity, paged vs dense
                        tok/s.  ``--study-only`` runs just this and
                        gates the two invariants (the tier-1 CI smoke).
* overload_study      — fault-tolerant serving under ~2x pool
                        oversubscription (DESIGN.md §11): shed rate,
                        preemption count, admissions deferred, and
                        virtual-clock p95 latency, with the
                        survivors-bitwise acceptance bar gated hard
                        (non-zero exit when a pressured survivor's
                        tokens diverge from the unpressured run).

CPU wall-clock is a trend proxy, not TPU time.  ``--against`` diffs a
previous run (the nightly compares against the committed seed) through
``benchmarks.bench_diff``: structural fields (shape, backend,
``chunk_traces``) must match exactly, timing fields compare with a
relative tolerance (``--tolerance``, default 50% — shared-runner CPU
clocks are noisy), and the job exits non-zero only past the threshold.
The hard invariants are still asserted in tests/test_prefill_chunked.py.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import numpy as np

from repro.configs.base import MetricsConfig, ModelConfig, PagedKVConfig
from repro.models import lm
from repro.runtime.faults import FaultInjector
from repro.runtime.metrics import capture
from repro.runtime.metrics import nearest_rank_pct as _pct
from repro.runtime.server import Request, Server, ServeConfig, \
    throughput_report


def _requests(n: int, max_new: int, vocab: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    # staggered lengths: the monolithic path traces one prefill per
    # distinct length, the chunked path reuses one executable
    plens = [int(p) for p in rng.integers(8, 96, size=n)]
    return [Request(uid=i, prompt=rng.integers(0, vocab, size=plens[i]),
                    max_new=max_new) for i in range(n)]


def _serve(cfg, scfg, n_req, max_new, mcfg=None):
    """One warmed serve; ``mcfg`` enables the metrics hub on the server
    and rides its histogram/watchdog snapshot along in the result (the
    hub is closed afterwards so its process-wide retrace watchdog never
    counts a LATER server's compiles)."""
    if mcfg is not None:
        scfg = dataclasses.replace(scfg, metrics=mcfg)
    srv = Server(lm, cfg, scfg, lm.init_lm(jax.random.PRNGKey(0), cfg))
    # warmup with more requests than batch slots so the slot-REFILL path
    # traces too — the watchdog arms after this serve, and the measured
    # chunked serve must then be retrace-free (the monolithic side still
    # retraces per new prompt length: that's the storm being measured)
    srv.serve(_requests(scfg.batch + 2, max_new, cfg.vocab, seed=99))
    reqs = _requests(n_req, max_new, cfg.vocab)
    t0 = time.perf_counter()
    done = srv.serve(reqs)
    wall = time.perf_counter() - t0
    rep = throughput_report(done)
    itls = [(r.latency_s - r.ttft_s) / max(1, len(r.out) - 1)
            for r in done if r.ttft_s > 0.0 and len(r.out) > 1]
    out = {
        "wall_s": wall,
        "tok_per_s": rep["tokens"] / max(wall, 1e-9),
        "p50_ttft_s": rep["p50_ttft_s"],
        "p95_ttft_s": rep["p95_ttft_s"],
        "p50_itl_s": _pct(itls, 0.5),
        "p95_itl_s": _pct(itls, 0.95),
        "p95_queue_wait_s": rep["p95_queue_wait_s"],
        "chunk_traces": {str(k): v for k, v in srv._prefill_traces.items()},
    }
    if mcfg is not None:
        hub = srv.metrics
        out["metrics"] = {
            # warmup serve arms the watchdog, so this counts traces the
            # SECOND (measured) serve performed: 0 for the chunked path,
            # one per new prompt length for monolithic (the retrace storm
            # the chunked executable exists to kill)
            "retraces_post_warmup": hub.watchdog.retraces_post_warmup,
            "decode_step_s": {"p50": hub.percentile("decode_step_s", 0.5),
                              "p95": hub.percentile("decode_step_s", 0.95),
                              "mean": hub.hist_mean("decode_step_s")},
            "events": len(hub.events()),
        }
        hub.close()
    return out


def paged_kv_study(cfg, quick: bool, mcfg=None) -> dict:
    """Multi-turn chat over the paged KV pool vs dense re-prefill
    (DESIGN.md §10).

    ``n_sessions`` two-turn conversations share one long system prompt;
    turn 2 resends the full history plus a short follow-up.  The paged
    server admits turn 2 by reference (session chain + prefix trie), so
    nearly every turn-2 chunk is skipped; the dense server re-prefills
    everything.  Deterministic structural outputs (gated exactly by the
    nightly diff):

    * ``turn2_chunk_reduction`` — fraction of turn-2 prefill chunks the
      paged server skipped (the ISSUE acceptance bar: >= 0.90)
    * ``sessions_retained``     — live sessions held at dense-equivalent
      pool bytes (> ``slots``: the dense layout caps at batch
      conversations, the pool dedups the shared prefix once)
    """
    n_sessions = 6 if quick else 8
    batch, max_len, bs, pc, max_new = 4, 256, 16, 16, 8
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab, 144)     # shared system prompt
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)

    def mk_server(paged):
        scfg = ServeConfig(
            batch=batch, max_len=max_len, prefill_chunk=pc,
            prefill_interleave=2,
            paged_kv=PagedKVConfig(block_size=bs) if paged else None)
        if paged and mcfg is not None:   # the CI smoke's JSONL schema gate
            scfg = dataclasses.replace(scfg, metrics=mcfg)
        return Server(lm, cfg, scfg, params)

    turn1 = [Request(uid=i, prompt=np.concatenate(
                [shared, rng.integers(0, cfg.vocab, 16)]),
             max_new=max_new, session_id=f"s{i}")
             for i in range(n_sessions)]
    follow = [rng.integers(0, cfg.vocab, 8) for _ in range(n_sessions)]

    out = {}
    for mode, paged in (("paged", True), ("dense", False)):
        srv = mk_server(paged)
        t0 = time.perf_counter()
        done1 = srv.serve([Request(uid=r.uid, prompt=r.prompt,
                                   max_new=r.max_new,
                                   session_id=r.session_id if paged
                                   else None)
                           for r in turn1])
        hist = {r.uid: np.concatenate([r.prompt, r.out]) for r in done1}
        run0 = srv.prefill_chunks_run
        done2 = srv.serve([Request(uid=i, prompt=np.concatenate(
                              [hist[i], follow[i]]),
                           max_new=max_new,
                           session_id=f"s{i}" if paged else None)
                           for i in range(n_sessions)])
        wall = time.perf_counter() - t0
        toks = sum(len(r.out) for r in done1 + done2)
        ran = srv.prefill_chunks_run - run0
        out[mode] = {"wall_s": wall,
                     "tok_per_s": toks / max(wall, 1e-9),
                     "turn2_chunks_run": ran}
        if paged:
            stats = srv.paged_stats()
            skipped = srv.prefill_chunks_skipped
            out["turn2_chunks_skipped"] = skipped
            out["turn2_chunk_reduction"] = skipped / max(1, skipped + ran)
            out["sessions_retained"] = stats["sessions"]
            out["slots"] = batch
            out["pool_rows"] = stats["n_blocks"] * bs
            out["dense_rows"] = batch * max_len
            for k in ("reuse_hits", "reused_tokens", "dedup_blocks",
                      "cow_forks", "committed_blocks"):
                out[k] = stats.get(k, 0)
            if mcfg is not None:
                out["kv_pool_pressure_gauge"] = srv.metrics.gauge_value(
                    "kv_pool_pressure")
                srv.metrics.close()   # don't count the dense server's
                # compiles against this hub's armed watchdog
    return out


def overload_study(cfg, quick: bool) -> dict:
    """Fault-tolerant serving under ~2x pool oversubscription
    (DESIGN.md §11).

    A mixed-tier queue whose total KV working set is ~2x the paged pool
    runs with admission control, deadlines, and tier-aware preemption on,
    under the deterministic virtual clock (one tick per scheduler
    iteration), so every reported number is exact: outcome counters and
    the oversubscription ratio are gated exactly by the nightly diff,
    and the virtual latency percentiles are tick-multiples, not CPU
    noise.  The hard acceptance bar rides along as ``survivors_bitwise``:
    every request the pressured server completes must emit bitwise the
    tokens of an unpressured (big-pool, no-deadline) run.
    """
    n_req = 6 if quick else 8
    batch, max_len, bs, max_new = 4, 64, 8, 8
    mcfg = MetricsConfig(enabled=True)
    rng = np.random.default_rng(7)
    plens = [int(p) for p in rng.integers(12, 40, size=n_req)]
    prompts = [rng.integers(0, cfg.vocab, size=p) for p in plens]
    slas = ["latency", "balanced", "quality"]
    # every third request carries a tight deadline so the study always
    # exercises the shed path, not just preemption
    deadlines = [0.6 if i % 3 == 2 else 0.0 for i in range(n_req)]
    demand = sum(-(-(p + max_new) // bs) for p in plens)
    pool = -(-demand // 2)          # ~2x oversubscribed
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)

    def mk_reqs(with_deadlines):
        return [Request(uid=i, prompt=prompts[i], max_new=max_new,
                        sla=slas[i % len(slas)],
                        deadline_s=deadlines[i] if with_deadlines else 0.0)
                for i in range(n_req)]

    def mk_server(pool_blocks, metrics=None):
        scfg = ServeConfig(
            batch=batch, max_len=max_len,
            paged_kv=PagedKVConfig(block_size=bs, pool_blocks=pool_blocks),
            preempt=True, default_deadline_s=100.0,
            metrics=metrics or MetricsConfig())
        return Server(lm, cfg, scfg, params)

    ref_srv = mk_server(demand + 4 * batch)    # headroom: never pressured
    ref = {r.uid: np.asarray(r.out)
           for r in ref_srv.serve(mk_reqs(with_deadlines=False))}

    srv = mk_server(pool, metrics=mcfg)
    srv.attach_faults(FaultInjector(seed=0, virtual_clock=True,
                                    tick_s=0.05))
    done = srv.serve(mk_reqs(with_deadlines=True))
    rep = throughput_report(done)
    bitwise = all(np.array_equal(np.asarray(r.out), ref[r.uid])
                  for r in done if r.outcome == "completed")
    stats = srv.paged_stats()
    out = {"pool_blocks": pool, "demand_blocks": demand,
           "oversubscription": round(demand / pool, 4),
           "requests": n_req,
           "completed": rep["completed"], "shed": rep["shed"],
           "shed_rate": rep["shed_rate"],
           "preempted": rep["preempted"],
           "preemptions": rep["preemptions"],
           "admissions_deferred": stats["admissions_deferred"],
           "survivors_bitwise": bool(bitwise),
           "terminal_outcomes": all(r.outcome in ("completed", "shed")
                                    for r in done),
           "p95_latency_virtual_s": rep["p95_latency_s"],
           "p95_ttft_virtual_s": rep["p95_ttft_s"]}
    for k, v in rep.items():
        if k.startswith("shed_") and k != "shed_rate":
            out[k] = v
    # the hub ran the whole pressured serve on the virtual clock: its
    # outcome counters (shed reasons, preemptions per tier, pool eviction/
    # COW totals) are exact and diff structurally in the nightly gate
    out["metrics_counters"] = dict(srv.metrics.snapshot()["counters"])
    srv.metrics.close()
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_prefill.json")
    ap.add_argument("--against", default="",
                    help="previous BENCH_prefill.json to diff against: "
                         "structural fields exact, timing fields within "
                         "--tolerance, exit 1 past the threshold")
    ap.add_argument("--tolerance", type=float, default=0.5,
                    help="relative timing drift that fails the diff "
                         "(0.5 = 50%%)")
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--interleave", type=int, default=2)
    ap.add_argument("--study-only", action="store_true",
                    help="run only the paged-KV multi-turn study and gate "
                         "its invariants (>= 90%% turn-2 chunks skipped, "
                         "sessions retained > slots) — the CI smoke")
    ap.add_argument("--metrics-jsonl", default="", metavar="PATH",
                    help="write the chunked serve's structured metrics "
                         "event stream (JSON lines; schema-gated by "
                         "runtime.metrics.validate_jsonl in CI)")
    ap.add_argument("--metrics-trace", default="", metavar="DIR",
                    help="capture the chunked serve with jax.profiler into "
                         "DIR: serve.* spans and device ops on one clock, "
                         "with a Perfetto trace (nightly artifact)")
    ap.add_argument("--append-history", default="", metavar="PATH",
                    help="append a one-line run summary (key metrics + "
                         "git sha) to this JSONL trajectory file")
    args = ap.parse_args()

    d = 64 if args.quick else 128
    cfg = ModelConfig(name="bench-prefill", family="dense", vocab=512,
                      d_model=d, n_layers=4, n_heads=4, n_kv_heads=4,
                      d_ff=4 * d, max_seq=256, dtype="float32",
                      param_dtype="float32", attn_chunk=256, remat=False)
    smoke_mcfg = MetricsConfig(enabled=True,
                               jsonl_path=args.metrics_jsonl)
    if args.study_only:
        study = paged_kv_study(cfg, args.quick, mcfg=smoke_mcfg)
        print(f"paged_kv_study,reduction={study['turn2_chunk_reduction']:.3f},"
              f"skipped={study['turn2_chunks_skipped']},"
              f"sessions={study['sessions_retained']}/{study['slots']} slots,"
              f"paged_tok_per_s={study['paged']['tok_per_s']:.1f},"
              f"dense_tok_per_s={study['dense']['tok_per_s']:.1f}")
        ok = (study["turn2_chunk_reduction"] >= 0.90
              and study["sessions_retained"] > study["slots"])
        if args.metrics_jsonl:
            # CI smoke gate: every line the sink produced must be schema
            # valid (numeric ts + string kind)
            from repro.runtime.metrics import validate_jsonl
            n = validate_jsonl(args.metrics_jsonl)
            print(f"metrics_jsonl,valid_lines={n},{args.metrics_jsonl}")
        if args.append_history:
            from benchmarks.bench_diff import append_history, summarize
            append_history(args.append_history, "bench_prefill_study",
                           summarize(study, ("turn2_chunk_reduction",
                                             "turn2_chunks_skipped",
                                             "sessions_retained",
                                             "paged.tok_per_s",
                                             "dense.tok_per_s")))
        sys.exit(0 if ok else 1)
    n_req = 8 if args.quick else 16
    max_new = 8 if args.quick else 16
    mk = lambda pc: ServeConfig(batch=4, max_len=256, prefill_chunk=pc,
                                prefill_interleave=args.interleave)
    # both serves run with the hub enabled (the report rides its
    # histogram/watchdog snapshot); file sinks and the profiler capture
    # only on the chunked side
    monolithic = _serve(cfg, mk(0), n_req, max_new,
                        mcfg=MetricsConfig(enabled=True))
    with capture(args.metrics_trace):
        chunked = _serve(cfg, mk(args.chunk), n_req, max_new,
                         mcfg=smoke_mcfg)
    report = {
        "shape": {"d_model": d, "n_layers": 4, "batch": 4, "max_len": 256,
                  "requests": n_req, "max_new": max_new,
                  "chunk": args.chunk, "interleave": args.interleave},
        "backend": jax.default_backend(),
        "monolithic": monolithic,
        "chunked": chunked,
        "paged_kv_study": paged_kv_study(cfg, args.quick,
                                         mcfg=MetricsConfig(enabled=True)),
        "overload_study": overload_study(cfg, args.quick),
        "generated_unix": time.time(),
    }
    ov = report["overload_study"]
    print(f"overload_study,oversub={ov['oversubscription']:.2f},"
          f"shed_rate={ov['shed_rate']:.3f},"
          f"preemptions={ov['preemptions']},"
          f"deferred={ov['admissions_deferred']},"
          f"p95_latency_virtual_s={ov['p95_latency_virtual_s']:.2f},"
          f"survivors_bitwise={ov['survivors_bitwise']}")
    study = report["paged_kv_study"]
    print(f"paged_kv_study,reduction={study['turn2_chunk_reduction']:.3f},"
          f"sessions={study['sessions_retained']}/{study['slots']} slots,"
          f"paged_tok_per_s={study['paged']['tok_per_s']:.1f},"
          f"dense_tok_per_s={study['dense']['tok_per_s']:.1f}")
    for side in ("monolithic", "chunked"):
        r = report[side]
        print(f"bench_prefill,{side},tok_per_s={r['tok_per_s']:.1f},"
              f"p50_ttft_s={r['p50_ttft_s']:.4f},"
              f"p95_ttft_s={r['p95_ttft_s']:.4f},"
              f"p95_itl_s={r['p95_itl_s']:.5f},"
              f"traces={r['chunk_traces']}")
    status = 0
    if not (ov["survivors_bitwise"] and ov["terminal_outcomes"]):
        print("overload_study,FAIL,survivors must be bitwise and every "
              "outcome terminal")
        status = 1
    if args.against:
        from benchmarks.bench_diff import check_against
        status = max(status, check_against(args.against, report,
                                           args.tolerance,
                                           "bench_prefill_diff"))
    if args.append_history:
        from benchmarks.bench_diff import append_history, summarize
        append_history(args.append_history, "bench_prefill", summarize(
            report, ("backend",
                     "chunked.tok_per_s", "chunked.p95_ttft_s",
                     "chunked.p95_itl_s", "monolithic.tok_per_s",
                     "chunked.metrics.retraces_post_warmup",
                     "paged_kv_study.turn2_chunk_reduction",
                     "overload_study.shed_rate",
                     "overload_study.p95_latency_virtual_s")))
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {args.out}")
    sys.exit(status)


if __name__ == "__main__":
    main()
