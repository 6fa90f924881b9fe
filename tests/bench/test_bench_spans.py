"""The serve-loop readers ``host_gap_share`` and ``loop_host_ms``: exact
values on hand-built traces (nested spans, idle under ``serve.sync``, idle
outside any span, a pass cut by the window's edge), nothing on a trace
without ``serve.*`` spans, and a band on a trace recorded on a TPU v5e
during a ``qwen3-8b.chat`` run with the spans in place."""
import argparse
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import spec, tracefile  # noqa: E402

MS = 1_000_000                                  # ns
DATA = os.path.join(ROOT, "bench", "testdata")


def _read(metric, samples):
    ctx = argparse.Namespace(samples=samples, device=0)
    return spec.reader(metric, ROOT).read(ctx)


def _trace(window, ops, host):
    """Times in ms; one device (0) whose ``XLA Ops`` are ``ops``."""
    ev = lambda xs: [(n, s * MS, e * MS) for n, s, e in xs]
    return tracefile.Trace((window[0] * MS, window[1] * MS),
                           {0: ev([("jit__decode", 0, 1)])},
                           {0: ev([("op", s, e) for s, e in ops])},
                           ev(host) + ev([("bench_traced_window",) + window]))


# one pass: schedule (with an admission inside), a decode whose tokens
# the host waits for, commit
ONE_PASS = [("serve.iteration", 0, 100), ("serve.schedule", 0, 10),
            ("serve.admit", 2, 8), ("serve.decode", 20, 90),
            ("serve.put_slots", 20, 25), ("serve.decode_dispatch", 25, 30),
            ("serve.sync", 60, 90), ("serve.commit", 90, 100)]


def test_nested_spans_and_idle_under_sync():
    # device busy 10-50: idle 0-10 (schedule/admit: host work), 50-60
    # (decode, between dispatch and sync: host work), 60-90 (sync: the
    # host waits), 90-100 (commit: host work)
    tr = _trace((0, 100), [(10, 30), (25, 50)], ONE_PASS)
    assert _read("host_gap_share", [tr]) == pytest.approx(30.0)
    assert _read("device_idle_share", [tr]) == pytest.approx(60.0)
    # the pass less its wait
    assert _read("loop_host_ms", [tr]) == pytest.approx(70.0)


def test_idle_outside_any_span_is_no_host_gap():
    # the loop ends at 100; the window runs on idle to 150
    tr = _trace((0, 150), [(10, 60)], ONE_PASS)
    # idle: 0-10 schedule, 60-90 sync, 90-100 commit, 100-150 no span
    assert _read("host_gap_share", [tr]) == pytest.approx(100 * 20 / 150)
    assert _read("loop_host_ms", [tr]) == pytest.approx(70.0)


def test_pass_cut_by_the_window_edge():
    # a second pass starts at 100 and runs past the window's end (130):
    # its spans count for the gap up to the edge, the pass itself is
    # left out of loop_host_ms
    second = [("serve.iteration", 100, 200), ("serve.schedule", 100, 112),
              ("serve.decode", 112, 190), ("serve.sync", 114, 190)]
    tr = _trace((0, 130), [(10, 60), (112, 114)], ONE_PASS + second)
    # idle: 0-10, 90-100, 100-112 (host work), 60-90 and 114-130 (sync)
    assert _read("host_gap_share", [tr]) == pytest.approx(100 * 32 / 130)
    assert _read("loop_host_ms", [tr]) == pytest.approx(70.0)


def test_samples_sum_their_windows():
    a = _trace((0, 100), [(10, 50)], ONE_PASS)
    b = _trace((0, 100), [(0, 100)], ONE_PASS)           # never idle
    assert _read("host_gap_share", [a, b]) == pytest.approx(100 * 30 / 200)
    assert _read("loop_host_ms", [a, b]) == pytest.approx(70.0)


def test_other_host_events_are_ignored():
    # the Python tracer's frames and the tracer thread's sleep cover the
    # idle stretches, but no serve.* span is open there
    noise = [("$time sleep", 0, 100), ("$api.py:2641 device_put", 52, 58)]
    tr = _trace((0, 100), [(10, 50)], ONE_PASS + noise)
    assert _read("host_gap_share", [tr]) == pytest.approx(30.0)


def test_nothing_to_read_without_spans():
    tr = _trace((0, 100), [(10, 50)], [("$time sleep", 0, 100)])
    assert _read("host_gap_share", [tr]) is None
    assert _read("loop_host_ms", [tr]) is None
    # the recorded trace of a program that has no serve.* spans
    old = tracefile.load(os.path.join(DATA, "chat_decode.xplane.pb.gz"))
    assert _read("host_gap_share", [old]) is None
    assert _read("loop_host_ms", [old]) is None


# The first traced sample (0.75 s) of a qwen3-8b.chat run on a TPU v5e
# (seed 3141592653, --keep-trace): a wave's start, so the initial fill
# and prefill-only passes weigh more than in a whole run's four samples.
SPANS = os.path.join(DATA, "chat_decode_spans.xplane.pb.gz")


@pytest.fixture(scope="module")
def spans_trace():
    return tracefile.load(SPANS)


def test_recorded_trace_holds_the_span_tree(spans_trace):
    names = {n for n, _, _ in spans_trace.host if n.startswith("serve.")}
    assert {"serve.iteration", "serve.schedule", "serve.admit",
            "serve.prefill_chunk", "serve.put_slots", "serve.decode",
            "serve.decode_dispatch", "serve.sync", "serve.commit"} <= names
    a, b = spans_trace.window
    whole = [ev for ev in spans_trace.host
             if ev[0] == "serve.iteration" and ev[1] >= a and ev[2] <= b]
    assert len(whole) == 17


def test_recorded_trace_readings(spans_trace):
    idle = _read("device_idle_share", [spans_trace])
    gap = _read("host_gap_share", [spans_trace])
    host = _read("loop_host_ms", [spans_trace])
    assert idle == pytest.approx(14.919800531, rel=1e-9)
    assert gap == pytest.approx(8.046219621, rel=1e-9)
    assert host == pytest.approx(7.914392412, rel=1e-9)
    # the band of the chip runs: host work is most of the idle, never
    # more; a pass spends a few ms of host time besides its waits
    assert 5.0 <= gap <= idle
    assert 3.0 <= host <= 10.0
