"""What every traffic generator shares: seeded bytes and the op record."""

from __future__ import annotations

import numpy as np


def fresh_bytes(n: int, *key: int) -> bytes:
    """``n`` pseudo-random bytes that depend on ``key`` alone (any
    non-negative whole numbers: seed, client, index ...)."""
    raw = np.random.PCG64(np.random.SeedSequence(list(key)))
    return raw.random_raw((n + 7) // 8).tobytes()[:n]


def rng(*key: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(np.random.SeedSequence(list(key))))
