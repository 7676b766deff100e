"""Helpers a deployment's ``make_cohort`` shares: the seed, and where the
planted effects go."""
from __future__ import annotations

import numpy as np


def seed_sequence(seed: int) -> np.random.SeedSequence:
    """Any whole number, negative or past 64 bits, to a seed sequence."""
    return np.random.SeedSequence([abs(int(seed)), int(seed < 0)])


def plant(rng: np.random.Generator, *, n_traits: int, distinct: int, segment: int,
          density: float) -> tuple[np.ndarray, np.ndarray]:
    """``round(density * distinct * n_traits)`` planted pairs, at most one per
    trait, dealt round-robin over the pool's ``segment``-sized stretches."""
    k = min(n_traits, int(round(density * distinct * n_traits)))
    traits = rng.permutation(n_traits)[:k]
    n_seg = distinct // segment
    rows = (np.arange(k) % n_seg) * segment + rng.integers(0, segment, size=k)
    return rows.astype(np.int32), traits.astype(np.int32)
