"""The trace reduction, on a small trace recorded on a TPU v5e during a
``qwen3-8b.chat`` run (0.12 s: five decode-step executions, no prefill
chunk), against numbers read off that trace once and written here."""
import argparse
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import flops, spec, tracefile  # noqa: E402

DATA = os.path.join(ROOT, "bench", "testdata", "chat_decode.xplane.pb.gz")
START, END = 49302769, 169831817            # bench_traced_window, ns
DECODES = [(50019725, 73529363), (75971400, 99480321),
           (101953201, 125462418), (127853433, 151357620)]
BUSY_OPS_NS = 110168181
MODEL = {"n_layers": 12, "d_model": 4096, "n_heads": 32, "n_kv_heads": 8,
         "head_dim": 128, "d_ff": 12288, "vocab_rows": 152064,
         "gated_mlp": True}


@pytest.fixture(scope="module")
def tr():
    return tracefile.load(DATA)


def _ctx(tr, records=()):
    # anchor 0: host time t sits at START + t seconds on the trace's clock
    return argparse.Namespace(
        samples=[tr], device=0, records=list(records),
        token_flops=lambda pos: flops.decode_token_flops(MODEL, pos, 320 * 8),
        peaks={"bf16_flops": 197e12})


def test_window_and_programs(tr):
    assert tr.window == (START, END)
    assert tracefile.module_names([tr]) == ["jit__decode"]
    assert tr.to_ns(0.5) == START + 0.5e9
    whole = tracefile.matching(tr, 0, r"^jit__decode", whole=True)
    assert [(s, e) for _, s, e in whole] == DECODES
    # the fifth execution is cut where the profiler stopped
    assert len(tracefile.matching(tr, 0, r"^jit__decode")) == 5


def test_busy_and_idle(tr):
    assert tracefile.busy_ns(tr, 0) == BUSY_OPS_NS
    assert tracefile.busy_s([tr, tr], 0) == pytest.approx(2e-9 * BUSY_OPS_NS)
    assert tracefile.window_s([tr]) == pytest.approx((END - START) * 1e-9)
    idle = spec.reader("device_idle_share", ROOT).read(_ctx(tr))
    assert idle == pytest.approx(100 * (1 - BUSY_OPS_NS / (END - START)),
                                 rel=1e-12)
    assert idle == pytest.approx(8.5961576665, rel=1e-9)


def test_decode_step_ms(tr):
    ms = spec.reader("decode_step_ms", ROOT).read(_ctx(tr))
    assert ms == pytest.approx(sum(e - s for s, e in DECODES) / 4 * 1e-6,
                               rel=1e-12)
    assert ms == pytest.approx(23.50799075, rel=1e-9)


def test_prefill_share_reads_nothing_without_chunks(tr):
    assert spec.reader("prefill_share", ROOT).read(_ctx(tr)) is None


def test_decode_mfu_counts_live_requests(tr):
    # one request live over the whole trace (first token at 0 s, last at 1 s
    # on the trace's clock), 100 prompt tokens, 1001 served: at each of the
    # four whole executions it needs one token at its live context
    rec = {"plen": 100, "n_out": 1001, "t_first": -1e-3, "t_end": 1.0}
    ops = 0
    for s, _ in DECODES:
        frac = (s - (START - 1e6)) / (START + 1e9 - (START - 1e6))
        ops += flops.decode_token_flops(MODEL, 100 + int(frac * 1000) + 1,
                                        320 * 8)
    dev_s = sum(e - s for s, e in DECODES) * 1e-9
    mfu = spec.reader("decode_mfu", ROOT).read(_ctx(tr, [rec]))
    assert mfu == pytest.approx(100 * ops / (dev_s * 197e12), rel=1e-12)
    assert spec.reader("decode_mfu", ROOT).read(_ctx(tr)) is None


def test_breakdown(tr):
    ops = tracefile.top_ops([tr], 0)
    assert len(ops) == 10
    assert not any(n.startswith("%while") for n, _ in ops)
    assert ops[0][0].startswith("%dynamic-slice_bitcast_fusion.14 ")
    assert ops[0][1] == pytest.approx(0.017335869, rel=1e-9)
    gaps = tracefile.idle_gaps([tr], 0)
    assert gaps[0][0] == "$array.py:631 _value"
    assert gaps[0][1] == pytest.approx(0.00247288, rel=1e-9)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
