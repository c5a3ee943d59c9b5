"""b = A u with u ~ N(0, 1) drawn from the seed (PETSc ex2's
``-random_exact_sol``).  Most of such a b lies in high-frequency modes,
so CG needs few iterations, and about the same number for every draw."""

from __future__ import annotations

import numpy as np


def make(cfg: dict, operator, rng: np.random.Generator, count: int) -> np.ndarray:
    """(count, n) float32 right-hand sides; A u is formed in float64 by the
    reference operator and rounded once."""
    matvec = operator.reference_matvec(cfg)
    n = operator.n(cfg)
    out = np.empty((count, n), np.float32)
    for i in range(count):
        out[i] = matvec(rng.standard_normal(n))
    return out
