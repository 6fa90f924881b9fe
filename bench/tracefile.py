"""Reduction of a JAX profiler trace (``.xplane.pb``) to device intervals.

``ProfileData`` gives planes, lines and events with start and duration in
nanoseconds, all on one timeline.  A TPU's plane is ``/device:TPU:<n>``; its
``XLA Modules`` line holds one event per executed program (a jitted
function), its ``XLA Ops`` line one per operation.  The benchmark's host
thread writes one annotation, ``bench_traced_window``, over each traced
sample; it sets the sample's window and ties its clock to the host's.  A
run traces several samples spread over its window; the functions below
that take ``samples`` sum over them.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re

WINDOW_NAME = "bench_traced_window"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Trace:
    window: tuple          # (start_ns, end_ns) of the traced window
    modules: dict          # device id -> [(name, start_ns, end_ns)]
    ops: dict              # device id -> [(name, start_ns, end_ns)]
    host: list             # [(name, start_ns, end_ns)] host events
    anchor: float = 0.0    # host perf_counter() at the window's start

    @property
    def seconds(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def to_ns(self, t: float) -> float:
        """A host ``perf_counter()`` time on this trace's clock."""
        return self.window[0] + (t - self.anchor) * 1e9

    def clipped(self, events: list) -> list:
        a, b = self.window
        return [(n, max(s, a), min(e, b)) for n, s, e in events
                if e > a and s < b]


def find(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str, anchor: float = 0.0) -> Trace:
    """Read a trace file (``.xplane.pb``, or gzipped ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    modules, ops, host, window = {}, {}, [], None
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events]
            if dev is not None:
                if line.name == MODULE_LINE:
                    modules[int(dev.group(1))] = evs
                elif line.name == OP_LINE:
                    ops[int(dev.group(1))] = evs
            elif plane.name.startswith("/host:"):
                for ev in evs:
                    if ev[0] == WINDOW_NAME:
                        window = (ev[1], ev[2])
                host.extend(evs)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_NAME!r} annotation")
    if not modules:
        raise ValueError(f"{path}: no device plane with an "
                         f"{MODULE_LINE!r} line")
    return Trace(window, modules, ops, host, anchor)


def union(intervals: list) -> list:
    """Merge (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(tr: Trace, device: int, line: str = "ops") -> int:
    evs = (tr.ops if line == "ops" else tr.modules).get(device, [])
    return sum(e - s for s, e in union(
        [(s, e) for _, s, e in tr.clipped(evs)]))


def window_s(samples: list) -> float:
    return sum(tr.seconds for tr in samples)


def busy_s(samples: list, device: int, line: str = "ops") -> float:
    return sum(busy_ns(tr, device, line) for tr in samples) * 1e-9


def matching(tr: Trace, device: int, pattern: str,
             whole: bool = False) -> list:
    """Module executions on ``device`` whose name matches ``pattern``,
    clipped to the window; ``whole``: only those that lie inside it."""
    rx = re.compile(pattern)
    a, b = tr.window
    evs = tr.modules.get(device, [])
    if whole:
        # the device's last event is cut where the profiler stopped
        last = max(e for _, _, e in evs + tr.ops.get(device, []))
        return [ev for ev in evs
                if rx.search(ev[0]) and ev[1] >= a and ev[2] <= b
                and ev[2] < last]
    return [ev for ev in tr.clipped(evs) if rx.search(ev[0])]


CONTAINERS = re.compile(r"^%(while|conditional|call)[.\s]")


def top_ops(samples: list, device: int, n: int = 10) -> list:
    """[[op name, seconds]] of the n operations that took most time; loop
    and call ops, whose events span the ops inside them, are left out."""
    tot = {}
    for tr in samples:
        for name, s, e in tr.clipped(tr.ops.get(device, [])):
            if CONTAINERS.match(name):
                continue
            tot[name] = tot.get(name, 0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def _gaps(tr: Trace, device: int) -> list:
    busy = union([(s, e) for _, s, e in tr.clipped(tr.modules.get(device,
                                                                   []))])
    a, b = tr.window
    gaps, t = [], a
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if b > t:
        gaps.append((t, b))
    out = []
    for s, e in gaps:
        mid = (s + e) // 2
        under = [(he - hs, name) for name, hs, he in tr.host
                 if hs <= mid < he and name != WINDOW_NAME]
        out.append([min(under)[1] if under else "host idle", (e - s) * 1e-9])
    return out


def idle_gaps(samples: list, device: int, n: int = 10) -> list:
    """[[host activity, seconds]] of the n longest stretches in which no
    program ran on ``device``, each named by the innermost host event
    under its midpoint ("host idle" where there is none)."""
    gaps = [g for tr in samples for g in _gaps(tr, device)]
    return sorted(gaps, key=lambda g: -g[1])[:n]


def module_names(samples: list, device: int = 0) -> list:
    return sorted({re.sub(r"\(\d+\)$", "", n) for tr in samples
                   for n, _, _ in tr.modules.get(device, [])})
