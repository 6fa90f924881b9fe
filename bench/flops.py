"""Operations a served decode step needs, counted from shapes.

A token at live context ``ctx`` (its own position included) needs, in each
layer, the q/k/v/o projections, the scores and the weighted sum over its
context, and the MLP over the rows its step computes (``mlp_rows``: the
selected row groups times the group size on the sparse path, every hidden
row on the dense one), then the head over the vocabulary rows.  A
multiply-add counts as two operations; norms, rope, softmax and the
predictor's integer sign work are left out (well under 1% of the total at
these widths).
"""
from __future__ import annotations


def decode_token_flops(m: dict, ctx: int, mlp_rows: int) -> int:
    d, H, K, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    proj = 2 * d * hd * (2 * H + 2 * K)
    attn = 4 * H * hd * ctx
    mlp = 2 * d * mlp_rows * (3 if m["gated_mlp"] else 2)
    return m["n_layers"] * (proj + attn + mlp) + 2 * d * m["vocab_rows"]
