"""First-class observability for the serve stack (DESIGN.md §12).

The serve path accumulates rich internal telemetry — per-layer
predicted/realized density, FN proxies, alpha trajectories, pool pressure,
shed/preemption reasons — but until this module none of it left the process
except through end-of-run reports.  ``MetricsHub`` is the low-overhead
registry everything emits into:

* **Counters / gauges / histograms.**  Plain-Python instruments keyed by
  ``(name, sorted labels)``.  Histograms are fixed-bucket streaming: exact
  nearest-rank percentiles while the observation count stays at or below
  ``MetricsConfig.hist_max_exact``, folding into the bucket ladder past it
  (the percentile then reports the covering bucket's upper bound — a
  conservative estimate whose error is bounded by the bucket width).

* **Spans on the profiler clock.**  ``span()`` is the one span API: it
  opens a ``jax.profiler.TraceAnnotation`` (a ``StepTraceAnnotation``
  when given ``step_num``) whether the hub is enabled or not, so every
  serve phase lands in a ``jax.profiler`` capture on the same clock as
  the device's ops and transfers.  With no profiler session active a
  span costs about a microsecond.  When the hub is enabled a span given
  ``hist`` also folds its duration into that histogram, stamped from
  *the scheduler's clock* — wall clock, or the ``FaultInjector`` virtual
  clock when one is armed (``bind_clock``).

* **Structured sinks.**  A JSONL event stream (``event()``; every line is
  ``{"ts": float, "kind": str, ...}`` — :func:`validate_jsonl` is the
  schema gate CI runs) and a Prometheus-style text exposition snapshot
  (``exposition`` / ``write_snapshot``) carrying per-step latency
  percentiles, per-tier realized/predicted density and FN rate,
  per-(layer, shard) alpha and capacity-bucket occupancy, KV-pool
  pressure/eviction/COW counters, and shed/preemption reasons.

* **Retrace watchdog.**  ``RetraceWatchdog`` hooks the jax monitoring
  compile events (``/jax/core/compile/jaxpr_trace_duration`` — one firing
  per trace, independent of the persistent compilation cache) and turns
  the codebase's "zero retraces after warmup" invariant from a test-only
  property into a monitored counter: once ``arm()``-ed (the server arms it
  at the end of its first serve drain), any further trace warns and
  increments ``retraces_post_warmup``.

**Overhead contract.**  Emission is plain Python over already-materialized
host values: no extra device syncs, no new jit inputs, zero retraces
(pinned by tests/test_metrics.py).  A disabled hub is a no-op — every
public method returns immediately (``span`` hands back a bare profiler
annotation), so the serve loop is bitwise-identical with the hub on or
off.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import json
import math
import time
import warnings
from typing import Any, Callable, Optional

import jax

from repro.configs.base import MetricsConfig

# Default histogram bucket upper bounds (seconds): a coarse log ladder from
# 100us to a minute, terminated by +inf.  Wide on purpose — serve latencies
# span prefill chunks (ms) to queue waits (s); custom ladders go through
# MetricsConfig.hist_buckets.
DEFAULT_BUCKETS: tuple = (
    1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, float("inf"))


def nearest_rank_pct(vals, q: float) -> float:
    """Nearest-rank percentile, shared by ``runtime.server
    .throughput_report`` and ``benchmarks.bench_prefill`` (it used to be
    duplicated in both).  rank = ceil(q*n) with float fuzz rounded away
    first — a bare ``int(q*n)`` (or a ceil of ``0.95*20 ==
    18.999999999999996``) would report the max as p95 for every n <= 20.
    Accepts any sequence; sorts internally."""
    if not vals:
        return 0.0
    vals = sorted(vals)
    rank = math.ceil(round(q * len(vals), 9))
    return vals[min(len(vals) - 1, max(0, rank - 1))]


def _label_key(labels: dict) -> tuple:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _flat_name(name: str, lkey: tuple) -> str:
    """``name{k=v,...}`` — the snapshot/exposition key of one instrument."""
    if not lkey:
        return name
    return name + "{" + ",".join(f'{k}="{v}"' for k, v in lkey) + "}"


class Histogram:
    """Fixed-bucket streaming histogram.  Exact nearest-rank percentiles
    while ``count <= max_exact`` (``max_exact=0`` = exact forever — the
    mode ``throughput_report`` uses); past the cap the raw values fold
    away and percentiles come from the bucket counts (covering bucket's
    upper bound; the +inf bucket reports the observed max)."""

    __slots__ = ("buckets", "counts", "count", "total", "vmin", "vmax",
                 "max_exact", "_exact")

    def __init__(self, max_exact: int = 2048, buckets: tuple = ()):
        b = tuple(float(x) for x in (buckets or DEFAULT_BUCKETS))
        if sorted(b) != list(b) or len(set(b)) != len(b):
            raise ValueError(f"histogram buckets must be strictly "
                             f"increasing, got {b}")
        if not b or math.isfinite(b[-1]):
            b = b + (float("inf"),)
        self.buckets = b
        self.counts = [0] * len(b)
        self.count = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf
        self.max_exact = int(max_exact)
        self._exact: Optional[list] = []

    @property
    def exact(self) -> bool:
        return self._exact is not None

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        self.vmin = min(self.vmin, v)
        self.vmax = max(self.vmax, v)
        self.counts[bisect.bisect_left(self.buckets, v)] += 1
        if self._exact is not None:
            self._exact.append(v)
            if self.max_exact and self.count > self.max_exact:
                self._exact = None          # fold: bucketed from here on

    def percentile(self, q: float) -> float:
        if self.count == 0:
            return 0.0
        if self._exact is not None:
            return nearest_rank_pct(self._exact, q)
        rank = max(1, math.ceil(round(q * self.count, 9)))
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= rank:
                ub = self.buckets[i]
                return ub if math.isfinite(ub) else self.vmax
        return self.vmax                     # unreachable (counts sum==count)

    def snapshot(self) -> dict:
        if self.count == 0:
            return {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                    "p50": 0.0, "p90": 0.0, "p95": 0.0, "p99": 0.0,
                    "exact": True}
        return {"count": self.count, "sum": self.total,
                "min": self.vmin, "max": self.vmax,
                "p50": self.percentile(0.5), "p90": self.percentile(0.9),
                "p95": self.percentile(0.95), "p99": self.percentile(0.99),
                "exact": self.exact}


class _Span:
    """A profiler annotation that also times its phase on the hub's clock
    and folds the duration into the histogram ``hist``."""

    __slots__ = ("hub", "ann", "labels", "hist", "t0")

    def __init__(self, hub: "MetricsHub", ann, hist: str, labels: dict):
        self.hub = hub
        self.ann = ann
        self.labels = labels
        self.hist = hist
        self.t0 = 0.0

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = self.hub.now()
        return self

    def __exit__(self, *exc):
        dur = max(0.0, self.hub.now() - self.t0)
        self.hub.observe(self.hist, dur, **self.labels)
        return self.ann.__exit__(*exc)


class MetricsHub:
    """Registry of counters/gauges/histograms + JSONL sink, and the span
    API.

    Construct with a ``configs.base.MetricsConfig`` (``enabled=False`` —
    the default — makes every instrument a no-op; spans stay profiler
    annotations) and drive through the instrument methods; ``bind_clock``
    points the hub at the scheduler's clock so span histograms and events
    share its notion of time (virtual under a ``FaultInjector``).
    Exports: :meth:`snapshot` (JSON-friendly dict), :meth:`exposition`
    (Prometheus text), :meth:`events` (JSONL ring), :meth:`flush` (write
    configured sink files).  Spans go to the ``jax.profiler`` trace
    (:func:`capture`)."""

    def __init__(self, cfg: Optional[MetricsConfig] = None,
                 clock: Optional[Callable[[], float]] = None):
        cfg = cfg if cfg is not None else MetricsConfig()
        if cfg.cadence < 1:
            raise ValueError(f"metrics cadence must be >= 1, "
                             f"got {cfg.cadence}")
        if cfg.hist_max_exact < 0 or cfg.events_keep < 1:
            raise ValueError(
                f"hist_max_exact must be >= 0 and events_keep >= 1; got "
                f"{cfg.hist_max_exact}/{cfg.events_keep}")
        self.cfg = cfg
        self.enabled = bool(cfg.enabled)
        self._clock: Callable[[], float] = clock or time.perf_counter
        self._counters: dict = {}
        self._gauges: dict = {}
        self._hists: dict = {}
        self._events: collections.deque = collections.deque(
            maxlen=cfg.events_keep)
        self._jsonl = None                     # lazy append handle
        self.watchdog = RetraceWatchdog(self)
        if self.enabled and cfg.watchdog:
            self.watchdog.install()

    # ------------------------------------------------------------- clock --
    def bind_clock(self, clock: Callable[[], float]) -> None:
        """Point the hub at the scheduler's clock (``Server._now``): span
        histograms and events then share the scheduler's notion of time —
        the ``FaultInjector`` virtual clock when one is armed."""
        self._clock = clock

    def now(self) -> float:
        return float(self._clock())

    # -------------------------------------------------------- instruments --
    def inc(self, name: str, value: float = 1, **labels) -> float:
        """Increment a counter; returns the new value (0.0 disabled)."""
        if not self.enabled:
            return 0.0
        k = (name, _label_key(labels))
        v = self._counters.get(k, 0) + value
        self._counters[k] = v
        return v

    def set_gauge(self, name: str, value: float, **labels) -> None:
        if not self.enabled:
            return
        self._gauges[(name, _label_key(labels))] = float(value)

    def set_counter(self, name: str, value: float, **labels) -> None:
        """Overwrite a counter with an externally-maintained monotonic
        total (e.g. ``KVPool.stats`` — the pool already counts, the hub
        just mirrors).  Semantically still a counter for exposition."""
        if not self.enabled:
            return
        self._counters[(name, _label_key(labels))] = value

    def observe(self, name: str, value: float, **labels) -> None:
        if not self.enabled:
            return
        k = (name, _label_key(labels))
        h = self._hists.get(k)
        if h is None:
            h = self._hists[k] = Histogram(self.cfg.hist_max_exact,
                                           self.cfg.hist_buckets)
        h.observe(value)

    def counter_value(self, name: str, **labels) -> float:
        return self._counters.get((name, _label_key(labels)), 0)

    def gauge_value(self, name: str, **labels) -> Optional[float]:
        return self._gauges.get((name, _label_key(labels)))

    def percentile(self, name: str, q: float, **labels) -> float:
        h = self._hists.get((name, _label_key(labels)))
        return h.percentile(q) if h is not None else 0.0

    def hist_mean(self, name: str, **labels) -> float:
        h = self._hists.get((name, _label_key(labels)))
        if h is None or h.count == 0:
            return 0.0
        return h.total / h.count

    def hist_count(self, name: str, **labels) -> int:
        h = self._hists.get((name, _label_key(labels)))
        return h.count if h is not None else 0

    # -------------------------------------------------------------- spans --
    def span(self, name: str, hist: Optional[str] = None, **labels):
        """Phase context on the profiler's clock: ``with hub.span(
        "serve.decode", hist="decode_step_s"): ...``.  Always a
        ``jax.profiler.TraceAnnotation(name, **labels)`` — a
        ``StepTraceAnnotation`` when ``labels`` holds ``step_num`` — so a
        profiler capture shows it whether the hub is enabled or not.  With
        the hub enabled and ``hist`` given, the phase's duration on the
        hub's clock is also observed into ``hist`` (with ``labels``)."""
        ann = (jax.profiler.StepTraceAnnotation if "step_num" in labels
               else jax.profiler.TraceAnnotation)(name, **labels)
        if hist is None or not self.enabled:
            return ann
        return _Span(self, ann, hist, labels)

    # -------------------------------------------------------- JSONL events --
    def event(self, kind: str, **payload) -> None:
        """One structured event: ``{"ts": <clock>, "kind": kind,
        **payload}`` appended to the in-memory ring and (when
        ``jsonl_path`` is configured) written as one JSON line."""
        if not self.enabled:
            return
        rec = {"ts": self.now(), "kind": str(kind)}
        for k, v in payload.items():
            rec[k] = _jsonable(v)
        self._events.append(rec)
        if self.cfg.jsonl_path:
            if self._jsonl is None:
                self._jsonl = open(self.cfg.jsonl_path, "a")
            self._jsonl.write(json.dumps(rec) + "\n")

    def events(self) -> list:
        return list(self._events)

    # ------------------------------------------------------------- exports --
    def snapshot(self) -> dict:
        """JSON-friendly state of every instrument (flat ``name{labels}``
        keys; histograms as their summary dicts)."""
        return {
            "counters": {_flat_name(n, lk): v
                         for (n, lk), v in sorted(self._counters.items())},
            "gauges": {_flat_name(n, lk): v
                       for (n, lk), v in sorted(self._gauges.items())},
            "histograms": {_flat_name(n, lk): h.snapshot()
                           for (n, lk), h in sorted(self._hists.items())},
            "retraces_post_warmup": self.watchdog.retraces_post_warmup,
        }

    def exposition(self, prefix: str = "sparseinfer_") -> str:
        """Prometheus-style text exposition (summary-style histograms:
        ``{quantile="..."}`` gauges plus ``_sum``/``_count``)."""
        lines: list = []
        seen: set = set()

        def family(name: str, mtype: str) -> str:
            fam = prefix + _sanitize(name)
            if fam not in seen:
                seen.add(fam)
                lines.append(f"# TYPE {fam} {mtype}")
            return fam

        for (n, lk), v in sorted(self._counters.items()):
            fam = family(n, "counter")
            lines.append(f"{_flat_name(fam, lk)} {_fmt(v)}")
        for (n, lk), v in sorted(self._gauges.items()):
            fam = family(n, "gauge")
            lines.append(f"{_flat_name(fam, lk)} {_fmt(v)}")
        for (n, lk), h in sorted(self._hists.items()):
            fam = family(n, "summary")
            for q in (0.5, 0.9, 0.95, 0.99):
                qlk = lk + (("quantile", f"{q:g}"),)
                lines.append(f"{_flat_name(fam, qlk)} "
                             f"{_fmt(h.percentile(q))}")
            lines.append(f"{_flat_name(fam + '_sum', lk)} {_fmt(h.total)}")
            lines.append(f"{_flat_name(fam + '_count', lk)} {h.count}")
        fam = family("retraces_post_warmup", "counter")
        lines.append(f"{fam} {self.watchdog.retraces_post_warmup}")
        return "\n".join(lines) + "\n"

    def write_snapshot(self, path: Optional[str] = None) -> Optional[str]:
        path = path or self.cfg.snapshot_path
        if not path:
            return None
        with open(path, "w") as f:
            f.write(self.exposition())
        return path

    def flush(self) -> None:
        """Flush the JSONL handle and write the configured exposition sink
        file (serve-drain boundary)."""
        if not self.enabled:
            return
        if self._jsonl is not None:
            self._jsonl.flush()
        self.write_snapshot()

    def close(self) -> None:
        self.flush()
        if self._jsonl is not None:
            self._jsonl.close()
            self._jsonl = None
        self.watchdog.uninstall()


def _sanitize(name: str) -> str:
    return "".join(c if (c.isalnum() or c in "_:") else "_" for c in name)


def _fmt(v: float) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, bool, type(None))):
        return v
    if isinstance(v, (int, float)):
        return v
    try:
        import numpy as _np
        if isinstance(v, _np.integer):
            return int(v)
        if isinstance(v, _np.floating):
            return float(v)
        if isinstance(v, _np.ndarray):
            return v.tolist()
    except Exception:                                   # pragma: no cover
        pass
    return str(v)


def validate_jsonl(path: str, max_lines: int = 0) -> int:
    """Schema gate for the JSONL sink (the CI smoke): every line must
    parse as a JSON object with a numeric ``ts`` and a non-empty string
    ``kind``.  Returns the number of valid lines; raises ``ValueError``
    on the first violation."""
    n = 0
    with open(path) as f:
        for i, line in enumerate(f):
            if max_lines and n >= max_lines:
                break
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i + 1}: not JSON ({e})") from None
            if not isinstance(rec, dict):
                raise ValueError(f"{path}:{i + 1}: not an object")
            if not isinstance(rec.get("ts"), (int, float)) \
                    or isinstance(rec.get("ts"), bool):
                raise ValueError(f"{path}:{i + 1}: missing numeric 'ts'")
            if not (isinstance(rec.get("kind"), str) and rec["kind"]):
                raise ValueError(f"{path}:{i + 1}: missing 'kind'")
            n += 1
    if n == 0:
        raise ValueError(f"{path}: no JSONL records")
    return n


def capture(log_dir: str):
    """A ``jax.profiler`` capture into ``log_dir`` (a null context when it
    is empty): the serve spans, the model step's device ops and the
    transfers on one clock, with a Perfetto trace (``perfetto_trace.json.gz``)
    written beside the ``.xplane.pb``."""
    if not log_dir:
        return contextlib.nullcontext()
    return jax.profiler.trace(log_dir, create_perfetto_trace=True)


# ---------------------------------------------------------------------------
# Retrace watchdog: jax compile-event hook
# ---------------------------------------------------------------------------
# One module-level listener dispatches to every active watchdog —
# jax.monitoring has register-only semantics (no unregister), so per-hub
# listeners would leak across servers/tests.  The jaxpr-trace event fires
# exactly once per trace regardless of the persistent compilation cache
# (backend_compile is skipped on a disk-cache hit, a trace is not), which
# is precisely the "retrace" the serve-path invariant forbids.
_COMPILE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_ACTIVE_WATCHDOGS: list = []
_LISTENER_INSTALLED = [False]


def _dispatch_compile_event(event: str, duration: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    for w in list(_ACTIVE_WATCHDOGS):
        w._on_compile()


def _install_listener() -> bool:
    if _LISTENER_INSTALLED[0]:
        return True
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(
            _dispatch_compile_event)
    except Exception as e:                              # pragma: no cover
        warnings.warn(f"retrace watchdog unavailable: jax.monitoring "
                      f"listener registration failed ({e})", stacklevel=2)
        return False
    _LISTENER_INSTALLED[0] = True
    return True


class RetraceWatchdog:
    """Post-warmup recompile alarm (DESIGN.md §12).

    ``install()`` hooks the process-wide jax compile-event stream;
    ``compiles`` then counts every trace this process performs.  The serve
    path's contract is *zero retraces after warmup* — once :meth:`arm` is
    called (the server does it at the end of its first serve drain, when
    every executable the configuration needs has been traced), any further
    compile fires a warning, bumps ``retraces_post_warmup`` and the hub's
    ``retrace_post_warmup`` counter, and records a JSONL event — the
    invariant is a monitored, alertable signal instead of a test-only
    property."""

    def __init__(self, hub: Optional[MetricsHub] = None):
        self.hub = hub
        self.armed = False
        self.compiles = 0                 # every trace since install()
        self.retraces_post_warmup = 0     # traces observed while armed

    def install(self) -> None:
        if _install_listener() and self not in _ACTIVE_WATCHDOGS:
            _ACTIVE_WATCHDOGS.append(self)

    def uninstall(self) -> None:
        if self in _ACTIVE_WATCHDOGS:
            _ACTIVE_WATCHDOGS.remove(self)

    def arm(self) -> None:
        self.armed = True

    def disarm(self) -> None:
        self.armed = False

    def _on_compile(self) -> None:
        self.compiles += 1
        if not self.armed:
            return
        self.retraces_post_warmup += 1
        hub = self.hub
        if hub is not None and hub.enabled:
            hub.inc("retrace_post_warmup")
            hub.event("retrace", n=self.retraces_post_warmup)
        warnings.warn(
            "post-warmup retrace detected: a jitted function traced after "
            "the serve warmup boundary — the zero-retrace serving "
            "invariant is violated (check bucket-ladder warmup, chunk "
            "shapes, and prompt-length padding; DESIGN.md §12)",
            stacklevel=2)

    def report(self) -> dict:
        return {"installed": self in _ACTIVE_WATCHDOGS,
                "armed": self.armed,
                "compiles": self.compiles,
                "retraces_post_warmup": self.retraces_post_warmup}
