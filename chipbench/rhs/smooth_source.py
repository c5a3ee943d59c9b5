"""The constant source of PETSc ex45 (-Laplace u = 1, zero Dirichlet
boundary) plus a smooth perturbation drawn from the seed: the sine modes
of wave numbers 1..4 on every axis, each with a normal coefficient damped
by the square of its wave number, scaled so the perturbation's norm is a
fixed quarter of the constant's.  Every draw has the same size and the
same spectrum, so CG takes about the same number of iterations on each,
many more than on a random exact solution."""

from __future__ import annotations

import numpy as np

MODES = 4            # wave numbers 1..MODES on each axis
SHARE = 0.25         # ||perturbation|| / ||constant||


def make(cfg: dict, operator, rng: np.random.Generator, count: int) -> np.ndarray:
    g = operator.dims(cfg)
    # sines on the interior points of each axis, one row per wave number
    sines = [np.sin(np.pi * np.outer(np.arange(1, MODES + 1),
                                     np.arange(1, d + 1) / (d + 1)))
             for d in g]
    k = np.arange(1, MODES + 1)
    damp = sum(np.meshgrid(*([k ** 2] * len(g)), indexing="ij"))
    out = np.empty((count, operator.n(cfg)), np.float32)
    for i in range(count):
        pert = rng.standard_normal((MODES,) * len(g)) / damp
        for s in sines:             # contract one wave-number axis at a time
            pert = np.tensordot(pert, s, axes=([0], [0]))
        pert *= SHARE * np.sqrt(pert.size) / np.linalg.norm(pert)
        out[i] = (1.0 + pert).ravel()
    return out
