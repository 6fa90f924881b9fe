"""Operation counts, device peaks and the end-to-end arithmetic, each
checked by hand."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import devices, flops, spec  # noqa: E402
from bench import run  # noqa: E402

DENSE = spec.kit({})

SMALL = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 4, "d_ff": 32, "vocab_rows": 10, "gated_mlp": True}


def test_decode_flops_sparse_by_hand():
    # per layer: q 2*8*8 + k 2*8*4 + v 2*8*4 + o 2*8*8 = 384;
    # scores + weighted sum 4*2*4*ctx(5) = 160; MLP 3 matrices over 16 rows
    # 2*8*16*3 = 768; two layers, then the head 2*8*10 = 160
    assert flops.decode_token_flops(SMALL, 5, 16) == 2 * (384 + 160 + 768) \
        + 160


def test_decode_flops_dense_plain_ffn_by_hand():
    m = dict(SMALL, gated_mlp=False)
    # MLP 2 matrices over all 32 rows: 2*8*32*2 = 1024
    assert flops.decode_token_flops(m, 1, 32) == 2 * (384 + 32 + 1024) + 160


def test_dense_kit_counts_by_hand():
    # the two hand counts above, through the kit the configurations name
    assert DENSE.decode_token_flops(SMALL, 5, 16) == 2 * (384 + 160 + 768) \
        + 160
    plain = dict(SMALL, gated_mlp=False)
    assert DENSE.decode_token_flops(plain, 1, 32) == 2 * (384 + 32 + 1024) \
        + 160


@pytest.mark.parametrize("d_ff,enabled,slots,rows", [
    # 12288 / 8 = 1536 groups, 20% = 307.2 -> 307, up to a multiple of
    # 128 / 8 = 16: 320 groups of 8 rows
    (12288, True, 16, 320 * 8),
    # 3072 groups -> 614.4 -> 614 -> 624 (granite-34b)
    (24576, True, 1, 624 * 8),
    # over sparse_max_batch (16) the program runs the dense MLP
    (12288, True, 17, 12288),
    (12288, True, 32, 12288),
    # sparsity off
    (12288, False, 16, 12288),
])
def test_dense_kit_decode_mlp_rows_by_hand(d_ff, enabled, slots, rows):
    from repro.core.sparse_mlp import SparseInferConfig
    sp = SparseInferConfig(enabled=enabled)
    assert DENSE.decode_mlp_rows({"d_ff": d_ff}, sp, slots) == rows


def test_peaks_by_device_kind():
    p = devices.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        devices.peaks("TPU v9 imaginary")


def test_nearest_rank_percentile():
    vals = list(range(1, 21))          # 20 values: p95 is the 19th
    assert run.pct(vals, 0.95) == 19
    assert run.pct(vals, 0.5) == 10
    assert run.pct([], 0.95) == 0.0


def _rec(plen, n_out, t_start, t_first, t_end):
    return {"plen": plen, "n_out": n_out, "t_start": t_start,
            "t_first": t_first, "t_end": t_end, "queue_wait_s": 0.0}


def test_tails_are_over_all_requests_not_wave_medians():
    # wave 1: 19 requests with 10 ms TTFT; wave 2: one with 1 s.  A median
    # of per-wave tails would read (0.01 + 1.0) / 2; the tail over all 20
    # requests reads the 19th smallest, 10 ms, and one more slow request
    # moves it to 1 s.
    fast = [_rec(8, 11, 0.0, 0.01, 0.11) for _ in range(19)]
    slow = [_rec(8, 11, 1.0, 2.0, 2.1)]
    e = run.end_to_end(fast + slow, window_s=2.0)
    assert e["ttft_p95_ms"] == pytest.approx(10.0)
    assert e["tpot_p95_ms"] == pytest.approx(10.0)
    assert e["output_tok_s"] == pytest.approx(20 * 11 / 2.0)
    e2 = run.end_to_end(fast[:-1] + slow * 2, window_s=2.0)
    assert e2["ttft_p95_ms"] == pytest.approx(1000.0)


def test_tpot_leaves_out_the_first_token():
    r = _rec(4, 5, 0.0, 1.0, 1.4)      # 4 gaps of 0.1 s after the first
    assert run.end_to_end([r], 1.0)["tpot_p95_ms"] == pytest.approx(100.0)
