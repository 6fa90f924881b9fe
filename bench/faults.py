"""Faults planted in the timed path, to show that ``correct`` sees them.

Each fault replaces one function of the program before the server is
built, so every program the window runs carries it.  The tests plant them
with pytest's ``monkeypatch.setattr``; ``run.py --fault`` plants one for a
reading on the chip (never in a benchmark run).

- ``token_altered``: each sampled token is moved by one where it is made.
- ``state_unchanged``: a decode step hands back the cache it was given.
- ``half_batch_left_out``: the second half of the slots get the first
  half's logits.
- ``selection_half_slots``: the sparse MLP's row-group union is taken over
  the first half of the slots only.
- ``selection_stale``: each layer's row-group union is the one the same
  layer computed at the step before.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("token_altered", "state_unchanged", "half_batch_left_out",
         "selection_half_slots", "selection_stale")


def plant(name: str, n_layers: int, vocab: int, setattr=setattr) -> None:
    from repro.core import selection
    from repro.models import lm
    from repro.runtime import server
    if name == "token_altered":
        orig = server.greedy_sample
        setattr(server, "greedy_sample",
                lambda logits: (orig(logits) + 1) % vocab)
    elif name == "state_unchanged":
        orig = lm.decode_step

        def step(params, cfg, token, caches, cache_len, **kw):
            logits, _ = orig(params, cfg, token, caches, cache_len, **kw)
            return logits, caches
        setattr(lm, "decode_step", step)
    elif name == "half_batch_left_out":
        orig = lm.decode_step

        def step(params, cfg, token, caches, cache_len, **kw):
            logits, caches = orig(params, cfg, token, caches, cache_len,
                                  **kw)
            b = logits.shape[0]
            half = jnp.roll(logits[:b // 2], 1, axis=0)[:b - b // 2]
            return logits.at[b // 2:].set(half), caches
        setattr(lm, "decode_step", step)
    elif name == "selection_half_slots":
        orig = selection.union_margin

        def union(margin):
            if margin.ndim == 1:
                return margin
            return orig(margin[:max(1, margin.shape[0] // 2)])
        setattr(selection, "union_margin", union)
    elif name == "selection_stale":
        setattr(selection, "union_margin",
                _stale_union(selection.union_margin, n_layers))
    else:
        raise ValueError(f"unknown fault {name!r}; have {NAMES}")


def _stale_union(orig, n_layers: int):
    """The union a layer computed ``n_layers`` calls ago: decode steps
    call it once per layer, in layer order, so that is the same layer's
    union at the step before (the first step keeps its own)."""
    held = collections.deque()

    def swap(cur):
        held.append(np.asarray(cur))
        if len(held) > n_layers:
            return held.popleft()
        return held[-1]

    def union(margin):
        cur = orig(margin)
        if margin.ndim == 1:
            return cur
        from jax.experimental import io_callback
        return io_callback(swap, jax.ShapeDtypeStruct(cur.shape, cur.dtype),
                           cur, ordered=False)
    return union
