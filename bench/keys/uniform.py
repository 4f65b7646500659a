"""Integers drawn uniformly from [0, 2^bits): hashed record ordinals."""

import numpy as np


def sample(rng: np.random.Generator, size: int, *, bits: int) -> np.ndarray:
    return rng.integers(0, 1 << int(bits), size).astype(np.float64)
