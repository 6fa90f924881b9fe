"""First-class observability tests (DESIGN.md §12, runtime/metrics.py).

Unit level: counter/gauge/histogram semantics (exact-then-bucketed
percentiles, label keying), the shared nearest-rank percentile helper's
parity with the two implementations it replaced, JSONL/exposition golden
shapes, spans as profiler annotations, and the retrace watchdog firing on
a forced recompile.

Serve level: a metrics-enabled serve emits per-tier density, per-layer
alpha, pool pressure, and latency percentiles to every sink; is BITWISE
identical (tokens + controller telemetry) to the same queue served with
the hub disabled; times span histograms on the FaultInjector virtual
clock when one is armed; stays retrace-silent across a warmed
bucket-ladder sweep; and lays one tree of ``serve.*`` spans per serve
loop pass into a ``jax.profiler`` capture.
"""
import glob
import json
import math

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, StepTraceAnnotation, TraceAnnotation

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # tier-1 runs with no extra deps
    from _hypothesis_shim import given, settings, strategies as st

from repro.configs.base import (ControllerConfig, MetricsConfig,
                                ModelConfig, PagedKVConfig)
from repro.configs.registry import default_sparse
from repro.models import lm
from repro.runtime.faults import FaultInjector
from repro.runtime.metrics import (DEFAULT_BUCKETS, Histogram, MetricsHub,
                                   RetraceWatchdog, _Span, capture,
                                   nearest_rank_pct, validate_jsonl)
from repro.runtime.server import Request, Server, ServeConfig

jax.config.update("jax_platform_name", "cpu")

CFG = ModelConfig(name="tiny-metrics", family="dense", n_layers=2,
                  d_model=32, n_heads=2, n_kv_heads=2, d_ff=64, vocab=128,
                  max_seq=64, dtype="float32", param_dtype="float32",
                  attn_chunk=8, loss_chunk=64, remat=False)
SPARSE_CFG = CFG.replace(sparse=default_sparse(activation="relu"),
                         activation="relu")

_PARAMS: dict = {}


def params_for(cfg):
    if cfg.name not in _PARAMS:
        _PARAMS[cfg.name] = lm.init_lm(jax.random.PRNGKey(0), cfg)
    return _PARAMS[cfg.name]


def enabled_hub(**over) -> MetricsHub:
    kw = dict(enabled=True, watchdog=False)
    kw.update(over)
    return MetricsHub(MetricsConfig(**kw))


# ---------------------------------------------------------------------------
# nearest-rank percentile: parity with the two helpers it deduplicated
# ---------------------------------------------------------------------------

def _old_server_pct(vals, q):
    """runtime.server.throughput_report's inner pct before the dedupe."""
    if not vals:
        return 0.0
    vals = sorted(vals)
    rank = math.ceil(round(q * len(vals), 9))
    return vals[min(len(vals) - 1, max(0, rank - 1))]


def _old_bench_pct(vals, q):
    """benchmarks.bench_prefill._pct before the dedupe."""
    if not vals:
        return 0.0
    vals = sorted(vals)
    return vals[min(len(vals) - 1,
                    max(0, int(np.ceil(q * len(vals))) - 1))]


class TestNearestRankPct:
    def test_empty(self):
        assert nearest_rank_pct([], 0.5) == 0.0

    def test_parity_with_old_helpers(self):
        rng = np.random.default_rng(0)
        for n in (1, 2, 3, 7, 10, 16, 20, 100):
            vals = list(rng.standard_normal(n))
            for q in (0.01, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
                got = nearest_rank_pct(vals, q)
                assert got == _old_server_pct(vals, q)
                assert got == _old_bench_pct(vals, q)

    def test_float_fuzz_p95(self):
        # 0.95 * 20 == 18.999999999999996: a bare ceil would report the
        # max as p95 for every n <= 20
        vals = list(range(1, 21))
        assert nearest_rank_pct(vals, 0.95) == 19
        assert nearest_rank_pct(vals, 0.5) == 10

    def test_unsorted_input(self):
        assert nearest_rank_pct([3.0, 1.0, 2.0], 0.5) == 2.0


# ---------------------------------------------------------------------------
# histogram: exact below the cap, bucketed past it
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_exact_percentiles(self):
        h = Histogram(max_exact=100)
        for v in range(1, 11):
            h.observe(float(v))
        assert h.exact
        assert h.percentile(0.5) == 5.0
        assert h.percentile(0.95) == 10.0
        assert h.count == 10 and h.total == 55.0
        assert h.vmin == 1.0 and h.vmax == 10.0

    def test_fold_past_cap(self):
        h = Histogram(max_exact=4, buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 0.5, 1.5, 3.0, 3.5):   # 5th observe folds
            h.observe(v)
        assert not h.exact
        # bucketed percentile reports the covering bucket's upper bound:
        # cumulative counts are 2 (<=1.0), 3 (<=2.0), 5 (<=4.0) so the
        # rank-3 median lands in the 2.0 bucket
        assert h.percentile(0.5) == 2.0
        assert h.percentile(0.25) == 1.0
        assert h.percentile(0.99) == 4.0
        assert h.count == 5

    def test_inf_bucket_reports_max(self):
        h = Histogram(max_exact=1, buckets=(1.0,))
        h.observe(5.0)
        h.observe(7.0)
        assert not h.exact
        assert h.percentile(0.99) == 7.0    # +inf bucket -> observed max

    def test_zero_cap_exact_forever(self):
        h = Histogram(max_exact=0)
        for v in range(5000):
            h.observe(float(v))
        assert h.exact
        assert h.percentile(0.5) == 2499.0

    def test_bad_buckets_raise(self):
        with pytest.raises(ValueError):
            Histogram(buckets=(2.0, 1.0))
        with pytest.raises(ValueError):
            Histogram(buckets=(1.0, 1.0))

    def test_terminal_inf_appended(self):
        h = Histogram(buckets=(1.0, 2.0))
        assert h.buckets[-1] == math.inf
        assert DEFAULT_BUCKETS[-1] == math.inf

    def test_empty_snapshot(self):
        snap = Histogram().snapshot()
        assert snap == {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                        "p50": 0.0, "p90": 0.0, "p95": 0.0, "p99": 0.0,
                        "exact": True}


class TestHistogramProperty:
    """Property sweep (hypothesis, or the seeded shim on tier-1): for ANY
    stream long enough to cross ``hist_max_exact`` and fold into buckets,
    the bucketed-mode percentile is a conservative upper bound on the
    exact nearest-rank value — and a tight one: it reports exactly the
    upper bound of the bucket containing the exact value (i.e. the
    overshoot is less than one bucket width), while values past the last
    finite bucket land in the +inf bucket, which reports the observed
    max."""

    BUCKETS = tuple(0.05 * 2 ** i for i in range(9))     # 0.05 .. 12.8

    @given(st.integers(0, 10 ** 6), st.integers(5, 60),
           st.integers(1, 4), st.floats(0.05, 4.0),
           st.sampled_from([0.01, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0]))
    @settings(max_examples=40, deadline=None)
    def test_bucketed_bounds_exact_nearest_rank(self, seed, n, max_exact,
                                                scale, q):
        rng = np.random.default_rng(seed)
        vals = [float(v) for v in rng.exponential(scale, size=n)]
        h = Histogram(max_exact=max_exact, buckets=self.BUCKETS)
        for v in vals:
            h.observe(v)
        assert not h.exact and h.count == n       # the stream really folded
        exact = nearest_rank_pct(vals, q)
        got = h.percentile(q)
        assert got >= exact, (got, exact)
        if exact <= self.BUCKETS[-1]:
            # ...and equals the covering bucket's ub: within one bucket
            covering = min(ub for ub in self.BUCKETS if exact <= ub)
            assert got == covering, (got, exact, covering)
        else:
            # +inf bucket: reports the observed max, still >= exact
            assert got == max(vals), (got, max(vals))


# ---------------------------------------------------------------------------
# hub instruments + disabled no-op contract
# ---------------------------------------------------------------------------

class TestHubInstruments:
    def test_counters_and_labels(self):
        hub = enabled_hub()
        assert hub.inc("sheds", reason="deadline") == 1
        assert hub.inc("sheds", reason="deadline") == 2
        assert hub.inc("sheds", reason="pool") == 1
        assert hub.counter_value("sheds", reason="deadline") == 2
        assert hub.counter_value("sheds", reason="missing") == 0

    def test_set_counter_mirrors_external_total(self):
        hub = enabled_hub()
        hub.set_counter("kv_pool_evictions", 7)
        hub.set_counter("kv_pool_evictions", 9)
        assert hub.counter_value("kv_pool_evictions") == 9

    def test_gauges(self):
        hub = enabled_hub()
        hub.set_gauge("alpha", 1.5, layer=0, tier="latency")
        assert hub.gauge_value("alpha", layer=0, tier="latency") == 1.5
        # label order must not matter
        assert hub.gauge_value("alpha", tier="latency", layer=0) == 1.5
        assert hub.gauge_value("alpha", layer=1, tier="latency") is None

    def test_observe_and_summaries(self):
        hub = enabled_hub()
        for v in (1.0, 2.0, 3.0):
            hub.observe("latency_s", v, tier="fast")
        assert hub.percentile("latency_s", 0.5, tier="fast") == 2.0
        assert hub.hist_mean("latency_s", tier="fast") == 2.0
        assert hub.hist_count("latency_s", tier="fast") == 3
        assert hub.hist_count("latency_s") == 0

    def test_span_records_duration(self):
        ticks = iter([10.0, 10.5])
        hub = enabled_hub()
        hub.bind_clock(lambda: next(ticks))
        with hub.span("serve.phase", hist="phase_s", uid=3):
            pass
        assert hub.hist_mean("phase_s", uid=3) == pytest.approx(0.5)

    def test_disabled_hub_is_noop(self):
        hub = MetricsHub(MetricsConfig())        # enabled=False default
        assert not hub.enabled
        assert hub.inc("c") == 0.0
        hub.set_gauge("g", 1.0)
        hub.observe("h", 1.0)
        hub.event("e")
        with hub.span("s", hist="p_s"):
            pass
        assert isinstance(hub.span("s", hist="p_s"), TraceAnnotation)
        assert hub.events() == []
        snap = hub.snapshot()
        assert snap["counters"] == {} and snap["gauges"] == {} \
            and snap["histograms"] == {}

    def test_span_without_hist_or_trace_is_null(self):
        # no histogram to fold into (or a disabled hub): the span is the
        # bare profiler annotation, no clock read on the hub's side
        hub = enabled_hub()
        assert type(hub.span("s")) is TraceAnnotation
        assert type(hub.span("s", uid=1)) is TraceAnnotation
        assert isinstance(hub.span("s", step_num=2), StepTraceAnnotation)
        assert isinstance(hub.span("s", hist="s_s"), _Span)
        off = MetricsHub(MetricsConfig())
        assert type(off.span("s", hist="s_s")) is TraceAnnotation

    def test_config_validation(self):
        with pytest.raises(ValueError):
            MetricsHub(MetricsConfig(cadence=0))
        with pytest.raises(ValueError):
            MetricsHub(MetricsConfig(hist_max_exact=-1))
        with pytest.raises(ValueError):
            MetricsHub(MetricsConfig(events_keep=0))


# ---------------------------------------------------------------------------
# sinks: JSONL, exposition
# ---------------------------------------------------------------------------

class TestSinks:
    def test_jsonl_roundtrip_and_schema(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        hub = enabled_hub(jsonl_path=path)
        hub.event("admit", uid=1, tier="latency")
        hub.event("complete", uid=1, tokens=8, latency_s=0.25)
        hub.flush()
        assert validate_jsonl(path) == 2
        recs = [json.loads(line) for line in open(path)]
        assert recs[0]["kind"] == "admit" and recs[0]["uid"] == 1
        assert isinstance(recs[0]["ts"], float)
        hub.close()

    def test_validate_jsonl_rejects_bad_lines(self, tmp_path):
        cases = ("not json\n",
                 "[1, 2]\n",
                 '{"kind": "x"}\n',                      # no ts
                 '{"ts": true, "kind": "x"}\n',          # bool ts
                 '{"ts": 1.0, "kind": ""}\n',            # empty kind
                 '{"ts": 1.0}\n')                        # no kind
        for i, bad in enumerate(cases):
            p = str(tmp_path / f"bad{i}.jsonl")
            with open(p, "w") as f:
                f.write(bad)
            with pytest.raises(ValueError):
                validate_jsonl(p)
        empty = str(tmp_path / "empty.jsonl")
        open(empty, "w").close()
        with pytest.raises(ValueError):
            validate_jsonl(empty)

    def test_exposition_golden_shape(self):
        hub = enabled_hub()
        hub.inc("requests_completed")
        hub.set_gauge("tier_realized_density", 0.25, tier="latency")
        for v in (1.0, 2.0, 3.0, 4.0):
            hub.observe("latency_s", v)
        text = hub.exposition()
        lines = text.splitlines()
        assert "# TYPE sparseinfer_requests_completed counter" in lines
        assert "sparseinfer_requests_completed 1" in lines
        assert ("sparseinfer_tier_realized_density"
                '{tier="latency"} 0.25') in lines
        assert "# TYPE sparseinfer_latency_s summary" in lines
        assert 'sparseinfer_latency_s{quantile="0.5"} 2' in lines
        assert "sparseinfer_latency_s_sum 10" in lines
        assert "sparseinfer_latency_s_count 4" in lines
        assert "sparseinfer_retraces_post_warmup 0" in lines

    def test_snapshot_shape(self):
        hub = enabled_hub()
        hub.inc("c", tier="fast")
        hub.set_gauge("g", 2.0)
        hub.observe("h_s", 1.0)
        snap = hub.snapshot()
        assert snap["counters"] == {'c{tier="fast"}': 1}
        assert snap["gauges"] == {"g": 2.0}
        assert snap["histograms"]["h_s"]["count"] == 1
        assert snap["retraces_post_warmup"] == 0
        json.dumps(snap)     # must be JSON-clean


# ---------------------------------------------------------------------------
# retrace watchdog
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_fires_on_forced_recompile(self):
        hub = MetricsHub(MetricsConfig(enabled=True, watchdog=True))
        try:
            fn = jax.jit(lambda x: x * 2)
            fn(np.ones((4,), np.float32)).block_until_ready()
            before = hub.watchdog.compiles
            assert before > 0
            hub.watchdog.arm()
            with pytest.warns(UserWarning, match="post-warmup retrace"):
                # new shape => forced retrace while armed
                fn(np.ones((5,), np.float32)).block_until_ready()
            assert hub.watchdog.retraces_post_warmup > 0
            assert hub.counter_value("retrace_post_warmup") > 0
            assert any(e["kind"] == "retrace" for e in hub.events())
        finally:
            hub.close()

    def test_silent_when_disarmed_and_after_close(self):
        hub = MetricsHub(MetricsConfig(enabled=True, watchdog=True))
        fn = jax.jit(lambda x: x + 1)
        fn(np.ones((3,), np.float32)).block_until_ready()
        assert hub.watchdog.retraces_post_warmup == 0
        hub.close()                               # uninstalls the listener
        n = hub.watchdog.compiles
        fn(np.ones((6,), np.float32)).block_until_ready()
        assert hub.watchdog.compiles == n

    def test_report_shape(self):
        hub = MetricsHub(MetricsConfig(enabled=True, watchdog=True))
        try:
            rep = hub.watchdog.report()
            assert rep["installed"] and not rep["armed"]
            assert rep["retraces_post_warmup"] == 0
        finally:
            hub.close()


# ---------------------------------------------------------------------------
# serve-level: emission completeness, bitwise parity, virtual clock,
# warmed-ladder silence (the ISSUE 9 acceptance criteria)
# ---------------------------------------------------------------------------

def _mk_requests(n=4, max_new=6, tiered=True):
    return [Request(uid=i, prompt=list(range(1 + i, 6 + i)), max_new=max_new,
                    sla=("latency" if i % 2 else "balanced") if tiered
                    else "balanced")
            for i in range(n)]


def _mk_server(mcfg=None, paged=False, buckets=None, **over):
    cfg = SPARSE_CFG
    if buckets:
        import dataclasses
        cfg = cfg.replace(sparse=dataclasses.replace(
            cfg.sparse, capacity_buckets=buckets))
    kw = dict(batch=2, max_len=48,
              controller=ControllerConfig(enabled=True, per_tier=True),
              metrics=mcfg or MetricsConfig())
    if paged:
        kw["paged_kv"] = PagedKVConfig(block_size=8)
    kw.update(over)
    return Server(lm, cfg, ServeConfig(**kw), params_for(cfg))


class TestServeMetrics:
    def test_serve_emits_every_family(self, tmp_path):
        jl = str(tmp_path / "m.jsonl")
        sn = str(tmp_path / "s.prom")
        srv = _mk_server(MetricsConfig(enabled=True, jsonl_path=jl,
                                       snapshot_path=sn, cadence=2),
                         paged=True)
        try:
            srv.serve(_mk_requests())
            hub = srv.metrics
            snap = hub.snapshot()
            g = snap["gauges"]
            # per-tier density + per-layer alpha (controller)
            assert 'tier_realized_density{tier="latency"}' in g
            assert 'tier_realized_density{tier="balanced"}' in g
            assert 'alpha{layer="0",tier="latency"}' in g
            assert 'layer_density{layer="1",tier="balanced"}' in g
            # pool occupancy/pressure (paged KV)
            assert "kv_pool_pressure" in g
            assert g["kv_pool_n_blocks"] > 0
            # latency percentiles live in the histograms
            assert hub.percentile("latency_s", 0.95, tier="balanced") > 0.0
            assert hub.hist_count("decode_step_s") > 0
            assert snap["counters"]["requests_completed"] == 4
            # zero post-warmup retraces during the monitored serve
            assert snap["retraces_post_warmup"] == 0
            # every sink materialized and well-formed
            assert validate_jsonl(jl) > 0
            kinds = {e["kind"] for e in hub.events()}
            assert {"serve_start", "admit", "first_token", "complete",
                    "serve_end"} <= kinds
            # the spans' histograms: monolithic prefill per request,
            # decode steps and controller updates
            hists = snap["histograms"]
            assert sum(h["count"] for k, h in hists.items()
                       if k.startswith("prefill_s{")) == 4
            assert hub.hist_count("controller_update_s") \
                == hub.hist_count("decode_step_s")
            expo = open(sn).read()
            assert "sparseinfer_tier_realized_density" in expo
            assert ('sparseinfer_latency_s'
                    '{tier="balanced",quantile="0.95"}') in expo
        finally:
            srv.metrics.close()

    def test_disabled_hub_bitwise_parity(self):
        srv_on = _mk_server(MetricsConfig(enabled=True))
        srv_off = _mk_server()
        try:
            # disabled serve first: srv_on's watchdog arms at the end of
            # its serve and the listener is process-wide, so any compile
            # srv_off triggers afterwards would count against it
            done_off = srv_off.serve(_mk_requests())
            done_on = srv_on.serve(_mk_requests())
            toks_on = {r.uid: np.asarray(r.out).tolist() for r in done_on}
            toks_off = {r.uid: np.asarray(r.out).tolist() for r in done_off}
            assert toks_on == toks_off
            s_on, s_off = srv_on.controller.state, srv_off.controller.state
            for name in ("alphas", "density_ema", "fn_ema",
                         "predicted_ema", "union_ema", "overflow_ema"):
                assert np.array_equal(getattr(s_on, name),
                                      getattr(s_off, name)), name
            assert type(srv_off.metrics.span("x", hist="x_s")) \
                is TraceAnnotation
        finally:
            srv_on.metrics.close()

    def test_virtual_clock_spans(self, tmp_path):
        jl = str(tmp_path / "v.jsonl")
        srv = _mk_server(MetricsConfig(enabled=True, jsonl_path=jl))
        try:
            tick = 0.05
            srv.attach_faults(FaultInjector(seed=0, virtual_clock=True,
                                            tick_s=tick))
            srv.serve(_mk_requests())
            hub = srv.metrics
            # every stamp comes off the injector clock: origin 1.0,
            # advanced one tick per scheduler iteration
            ts = [e["ts"] for e in hub.events()]
            assert ts and all(t >= 1.0 for t in ts)
            assert ts == sorted(ts)
            for t in ts:
                frac = (t - 1.0) / tick
                assert abs(frac - round(frac)) < 1e-6, t
            # the virtual clock does not advance INSIDE a phase, so every
            # span histogram observes zero-duration phases
            hists = hub.snapshot()["histograms"]
            spans = {k: h for k, h in hists.items()
                     if k.split("{")[0] in ("prefill_s", "decode_step_s",
                                            "controller_update_s")}
            assert {k.split("{")[0] for k in spans} == {
                "prefill_s", "decode_step_s", "controller_update_s"}
            for k, h in spans.items():
                assert h["count"] > 0 and h["sum"] == 0.0, k
                assert h["max"] == 0.0, k
            # latency percentiles are exact tick multiples, not CPU noise
            p95 = hub.percentile("latency_s", 0.95, tier="balanced")
            frac = p95 / tick
            assert abs(frac - round(frac)) < 1e-6
        finally:
            srv.metrics.close()

    def test_warmed_bucket_ladder_stays_silent(self):
        srv = _mk_server(MetricsConfig(enabled=True),
                         buckets=(0.25, 0.5, 1.0), warm_buckets=True)
        try:
            srv.serve(_mk_requests())       # drain 1: warm + arm
            assert srv.metrics.watchdog.armed
            srv.serve(_mk_requests(n=6))    # drain 2: sweep again, refill
            assert srv.metrics.watchdog.retraces_post_warmup == 0
            assert srv.metrics.counter_value("retrace_post_warmup") == 0
        finally:
            srv.metrics.close()

    def test_metrics_report_and_throughput_report_hub(self):
        srv = _mk_server(MetricsConfig(enabled=True))
        try:
            done = srv.serve(_mk_requests())
            rep = srv.metrics_report()
            assert rep["enabled"] and rep["watchdog"]["armed"]
            assert rep["events"] > 0
            from repro.runtime.server import throughput_report
            trep = throughput_report(done)
            # the report's percentiles come from an exact-mode hub now;
            # nearest-rank over 4 latencies: p50 = 2nd smallest
            lats = sorted(r.latency_s for r in done)
            assert trep["p50_latency_s"] == lats[1]
            assert trep["p95_latency_s"] == lats[-1]
        finally:
            srv.metrics.close()


# ---------------------------------------------------------------------------
# spans in a jax.profiler capture, with the hub disabled
# ---------------------------------------------------------------------------

LOOP_SPANS = {"serve.iteration", "serve.schedule", "serve.admit",
              "serve.prefill_chunk", "serve.put_slots", "serve.decode",
              "serve.decode_dispatch", "serve.sync", "serve.commit"}


def _host_spans(log_dir) -> list:
    """[(name, start_ns, end_ns, stats)] of every serve.* span captured."""
    path, = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: v for k, v in ev.stats}))
    return out


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


class TestProfilerSpans:
    @pytest.mark.parametrize("controller", [False, True])
    def test_capture_holds_one_span_tree_per_pass(self, tmp_path,
                                                  controller):
        srv = _mk_server(controller=ControllerConfig(enabled=controller,
                                                     per_tier=True),
                         prefill_chunk=4)
        assert not srv.metrics.enabled
        srv.serve(_mk_requests())                # compiles outside the capture
        reqs = _mk_requests()
        with capture(str(tmp_path)):
            srv.serve(reqs)
        spans = _host_spans(tmp_path)
        names = {s[0] for s in spans}
        want = LOOP_SPANS | ({"serve.controller"} if controller else set())
        assert want <= names, want - names
        iters = [s for s in spans if s[0] == "serve.iteration"]
        assert [s[3]["step_num"] for s in iters] == list(range(len(iters)))
        # every other span lies inside the pass that opened it
        for s in spans:
            if s[0] != "serve.iteration":
                assert sum(_inside(s, it) for it in iters) == 1, s
        # one wait for the step's tokens per decode; the controller's
        # update holds the wait for its telemetry
        decodes = [s for s in spans if s[0] == "serve.decode"]
        ctrl_updates = [s for s in spans if s[0] == "serve.controller"
                        and any(_inside(y, s) for y in spans
                                if y[0] == "serve.sync")]
        for d in decodes:
            syncs = [y for y in spans if y[0] == "serve.sync"
                     and _inside(y, d)
                     and not any(_inside(y, c) for c in ctrl_updates)]
            assert len(syncs) == 1, d
        assert len(ctrl_updates) == (len(decodes) if controller else 0)
        for it in iters:
            if any(_inside(d, it) for d in decodes):
                assert any(y[0] == "serve.sync" and _inside(y, it)
                           for y in spans)
        # admissions and chunks carry the request's uid and slot
        uids = {r.uid for r in reqs}
        for name in ("serve.admit", "serve.prefill_chunk"):
            tagged = [s for s in spans if s[0] == name]
            assert {s[3]["uid"] for s in tagged} == uids, name
            assert all(s[3]["slot"] in (0, 1) for s in tagged)

    @pytest.mark.parametrize("paged", [False, True])
    def test_spans_leave_tokens_and_traces_unchanged(self, paged):
        # no profiler session: a disabled hub's spans are bare annotations,
        # and the served tokens are bitwise the enabled hub's
        srv_off = _mk_server(paged=paged, prefill_chunk=8)
        srv_on = _mk_server(MetricsConfig(enabled=True), paged=paged,
                            prefill_chunk=8)
        wd = RetraceWatchdog()
        wd.install()
        try:
            srv_off.serve(_mk_requests())
            wd.arm()
            done_off = srv_off.serve(_mk_requests())
            assert wd.retraces_post_warmup == 0
            wd.disarm()
            # the same two serves: the controller adapts across them
            srv_on.serve(_mk_requests())
            done_on = srv_on.serve(_mk_requests())
        finally:
            wd.uninstall()
            srv_on.metrics.close()
        assert srv_on.metrics.watchdog.retraces_post_warmup == 0
        toks_on = {r.uid: np.asarray(r.out).tolist() for r in done_on}
        toks_off = {r.uid: np.asarray(r.out).tolist() for r in done_off}
        assert toks_on == toks_off
