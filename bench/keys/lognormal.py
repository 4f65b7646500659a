"""SOSD's synthetic ``lognormal`` keys: floor(scale * X), X ~ lognormal(mu, sigma)."""

import numpy as np


def sample(rng: np.random.Generator, size: int, *, mu: float, sigma: float,
           scale: float) -> np.ndarray:
    return np.floor(float(scale) * rng.lognormal(float(mu), float(sigma),
                                                 size))
