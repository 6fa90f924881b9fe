"""One general traffic generator, driven by a mix's data file.

A mix file (``bench/traffic/<name>.json``) fixes the serve flags (slots,
cache length, prefill chunk and interleave), the prompt and output length
distributions, and the wave size: the number of requests handed to one
``Server.serve`` call.  Lengths are the quantiles (i + 1/2)/n of a clipped
log-normal; which prompt length meets which output length, and their order
in the wave, are drawn per wave index, so every seed serves the same
requests in the same order and the seed draws the token ids.  On the chip
an order drawn per seed moved TTFT's p95 by up to 47% and tokens/s by 7%
between seeds, while one seed repeated within 1-2%: the order is part of
the work, like the sizes.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def lengths(dist: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles of a log-normal clipped to
    [min, max]: {"median", "sigma", "min", "max"}."""
    q = (np.arange(n) + 0.5) / n
    z = np.asarray([_NORMAL.inv_cdf(float(v)) for v in q])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def wave(mix: dict, seed: int, index: int, vocab: int) -> list:
    """Wave ``index`` of a run: a list of (prompt token ids, max_new).
    The sizes, their pairing and their order depend on the wave's index
    only, so every seed serves the same work; the seed draws the tokens."""
    n = mix["wave_requests"]
    shape = _rng(0, 4, index)
    plen = shape.permutation(lengths(mix["prompt"], n))
    outl = shape.permutation(lengths(mix["output"], n))
    rng = _rng(seed, 1, index)
    return [(rng.integers(0, vocab, size=int(p), dtype=np.int32), int(o))
            for p, o in zip(plen, outl)]


def warmup(mix: dict, vocab: int) -> list:
    """One request per slot, one prefill chunk each: it runs every program
    the window runs at the window's shapes (chunk, splice, decode with
    per-slot alphas while slots fill, and without once all are live, which
    needs more tokens than there are slots)."""
    rng = _rng(0, 2)
    return [(rng.integers(0, vocab, size=mix["prefill_chunk"],
                          dtype=np.int32), mix["slots"] + 4)
            for _ in range(mix["slots"])]


def sample(n_done: int, k: int, longest: int, seed: int) -> list:
    """Indices of the requests the check compares: ``longest`` and k - 1
    others drawn from the seed."""
    rest = [i for i in range(n_done) if i != longest]
    pick = _rng(seed, 3).choice(len(rest), size=min(k - 1, len(rest)),
                                replace=False)
    return sorted([longest] + [rest[i] for i in pick])
