"""b = A (s 1): HPCG's right-hand side, whose exact solution is all ones
(``GenerateProblem_ref``: b_i = 27 - nnz_i for the 27-point operator),
scaled by a power of two s = 2**e drawn from the seed for each entry, e in
-4..4.  A power-of-two scale is exact in binary floating point, and CG from
x0 = 0 is homogeneous in b, so every entry is solved in the same
iterations, with the same relative residuals, as HPCG's own b = A 1.
The draw only makes the seed choose the inputs."""

from __future__ import annotations

import numpy as np

EXPONENTS = (-4, 4)


def make(cfg: dict, operator, rng: np.random.Generator, count: int) -> np.ndarray:
    """(count, n) float32 right-hand sides; A 1 is formed in float64 by the
    reference operator and rounded once (its entries are small integers)."""
    base = operator.reference_matvec(cfg)(np.ones(operator.n(cfg)))
    scale = np.ldexp(1.0, rng.integers(EXPONENTS[0], EXPONENTS[1] + 1,
                                       size=count))
    return (scale[:, None] * base[None, :]).astype(np.float32)
