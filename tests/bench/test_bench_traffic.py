"""The traffic generator: deterministic per seed, inside the stated
ranges, and the same requests in the same order on every seed."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import spec, traffic  # noqa: E402

BM = spec.benchmark(ROOT)
MIXES = sorted({w["traffic"] for w in BM["workloads"]})
BIG = 2 ** 31 + 12345


def _mix(name):
    return spec.load_json(os.path.join(ROOT, "bench", "traffic",
                                       name + ".json"))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_wave(name):
    a = traffic.wave(_mix(name), BIG, 3, 1000)
    b = traffic.wave(_mix(name), BIG, 3, 1000)
    assert len(a) == len(b) == _mix(name)["wave_requests"]
    for (pa, na), (pb, nb) in zip(a, b):
        assert na == nb and np.array_equal(pa, pb)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range_and_same_set_on_every_seed(name):
    mix = _mix(name)
    sets = []
    for seed in (0, 7, BIG, 2 ** 40 + 1):
        w = traffic.wave(mix, seed, 0, 50)
        p = np.asarray([len(x) for x, _ in w])
        o = np.asarray([n for _, n in w])
        assert p.min() >= mix["prompt"]["min"]
        assert p.max() <= mix["prompt"]["max"]
        assert o.min() >= mix["output"]["min"]
        assert o.max() <= mix["output"]["max"]
        assert (p + o).max() <= mix["max_len"]
        assert all(x.min() >= 0 and x.max() < 50 for x, _ in w)
        sets.append(list(zip(p, o)))
    assert all(s == sets[0] for s in sets)
    # the order is drawn, not sorted by length
    assert sets[0] != sorted(sets[0]) and sets[0] != sorted(sets[0])[::-1]


def test_seeds_change_tokens_and_waves_change_sizes():
    mix = _mix(MIXES[0])
    a = traffic.wave(mix, 1, 0, 1000)
    b = traffic.wave(mix, 2, 0, 1000)
    assert any(not np.array_equal(x, y) for (x, _), (y, _) in zip(a, b))
    c = traffic.wave(mix, 1, 1, 1000)
    assert [(len(x), n) for x, n in a] != [(len(x), n) for x, n in c]


def test_lengths_are_mid_quantiles_of_the_lognormal():
    dist = {"median": 300, "sigma": 0.5, "min": 1, "max": 10 ** 6}
    x = traffic.lengths(dist, 101)
    assert x[50] == 300
    assert x[0] < 300 < x[-1]
    assert list(x) == sorted(x)


def test_sample_holds_the_longest_and_follows_the_seed():
    s1 = traffic.sample(50, 8, 17, BIG)
    assert 17 in s1 and len(s1) == 8 and len(set(s1)) == 8
    assert s1 == traffic.sample(50, 8, 17, BIG)
    assert traffic.sample(3, 8, 1, 5) == [0, 1, 2]


def test_warmup_fills_every_slot_past_the_slot_count():
    mix = _mix(MIXES[0])
    w = traffic.warmup(mix, 100)
    assert len(w) == mix["slots"]
    assert all(len(p) == mix["prefill_chunk"] and n > mix["slots"]
               for p, n in w)
