"""The one traffic generator: everything a cell feeds is drawn here from
``--seed`` and the parameters in the cell's workload file.

Seeds may exceed 32 bits, so every stream is keyed through
``numpy.random.SeedSequence`` rather than by truncating the seed.
"""
from __future__ import annotations

import numpy as np


def seed32(seed: int, *salt: int) -> int:
    """A 32-bit seed derived from an arbitrary non-negative ``seed``."""
    return int(np.random.SeedSequence([int(seed), *salt]).generate_state(1)[0])


def tokens(seed: int, tick: int, shape: tuple, vocab: int,
           process: dict) -> np.ndarray:
    """int32 token rows for one training tick (``shape = (..., seq)``).

    ``process["kind"] == "successor"``: an order-1 stream in which each
    token is its predecessor plus one with probability ``follow_p`` and a
    uniform draw otherwise (learnable, and no dense transition table at a
    published vocabulary).  The stream of tick ``t`` is keyed by
    ``(seed, t)``, so every tick's rows differ from every other tick's.
    """
    if process.get("kind") != "successor":
        raise ValueError(f"unknown token process {process!r}")
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1, tick]))
    draw = rng.integers(vocab, size=shape, dtype=np.int64)
    follow = rng.random(shape) < float(process["follow_p"])
    follow[..., 0] = False
    # each token is the last fresh draw at or before it, plus the number
    # of successor steps since that draw
    pos = np.broadcast_to(np.arange(shape[-1]), shape)
    last = np.maximum.accumulate(np.where(follow, 0, pos), axis=-1)
    fresh = np.take_along_axis(draw, last, axis=-1)
    return ((fresh + pos - last) % vocab).astype(np.int32)
