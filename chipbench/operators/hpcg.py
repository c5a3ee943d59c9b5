"""HPCG's operator: the 27-point stencil on an nx x ny x nz grid, stored,
with the coarse levels of its multigrid (HPCG 3.1, ``GenerateProblem_ref``
and ``GenerateCoarseProblem``).

The configuration's ``operator`` block::

    {"kind": "hpcg", "grid": [nx, ny, nz], "levels": 4, "storage": "csr"}

Row iz*nx*ny + iy*nx + ix holds 26 on the diagonal and -1 for each
neighbour inside the grid (no periodic wrap).  Level l + 1 is the same
operator on the grid of half the extents; coarse (i, j, k) injects from and
into fine (2i, 2j, 2k).  Both come from ``chipbench/mg_reference.py``.
HPCG requires every extent to halve ``levels - 1`` times; a grid that
halves fewer times (the CPU tests cut cells to 12^3) gets the levels it
halves to.  The reference parts here (``scipy_csr``,
``reference_matvec``, ``control_matvec``) import nothing of the program;
only ``program_operator`` hands the operator to it.
"""

from __future__ import annotations

import math

import numpy as np
import scipy.sparse as sp

from chipbench.mg_reference import f2c, stencil27


def dims(cfg: dict) -> tuple:
    return tuple(int(d) for d in cfg["operator"]["grid"])


def n(cfg: dict) -> int:
    return math.prod(dims(cfg))


def nnz(cfg: dict) -> int:
    """Each axis of length d has 3d - 2 (row, column) neighbour pairs."""
    return math.prod(3 * d - 2 for d in dims(cfg))


def stored(cfg: dict) -> bool:
    if cfg["operator"]["storage"] != "csr":
        raise ValueError("HPCG's operator is stored (its rules forbid "
                         "using the known values in place of the matrix)")
    return True


def level_dims(cfg: dict) -> list:
    """The grid of each multigrid level, finest first."""
    g = dims(cfg)
    out = [g]
    while (len(out) < int(cfg["operator"]["levels"])
           and all(d % 2 == 0 and d >= 2 for d in out[-1])):
        out.append(tuple(d // 2 for d in out[-1]))
    return out


def scipy_csr(cfg: dict) -> sp.csr_matrix:
    """The float64 operator (the finest level) as a scipy CSR matrix."""
    return stencil27(*dims(cfg))


def reference_matvec(cfg: dict):
    """x -> A x in float64 on the host, for (n,) or (k, n) vectors."""
    a = scipy_csr(cfg)
    return lambda x: (a @ np.asarray(x, np.float64).T).T


def diagonal(cfg: dict) -> float:
    return 26.0


def control_matvec(cfg: dict, dtype):
    """x -> A x on the device in ``dtype``, for the control: the stored
    operator as padded rows gathered from x.  Nothing here comes from the
    program."""
    import jax.numpy as jnp

    a = scipy_csr(cfg)
    lens = np.diff(a.indptr)
    rows = np.repeat(np.arange(a.shape[0]), lens)
    slot = np.arange(a.nnz) - a.indptr[rows]
    cols = np.zeros((a.shape[0], int(lens.max())), np.int32)
    vals = np.zeros(cols.shape, np.float64)
    cols[rows, slot] = a.indices
    vals[rows, slot] = a.data
    cols_d = jnp.asarray(cols)
    vals_d = jnp.asarray(vals, dtype)

    def mv(x):
        return jnp.sum(vals_d * x[cols_d], axis=1, dtype=dtype)
    return mv


def program_operator(cfg: dict):
    """The operator as the program takes it: a ``repro.core.multigrid.
    MGHierarchy`` of the levels' CSRs and the f2c maps."""
    stored(cfg)
    from repro.core.formats import csr_from_scipy
    from repro.core.multigrid import MGHierarchy

    grids = level_dims(cfg)
    return MGHierarchy(
        tuple(csr_from_scipy(stencil27(*g)) for g in grids),
        tuple(f2c(*g).astype(np.int32) for g in grids[:-1]))
