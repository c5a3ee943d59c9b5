"""Plain float64 reference of HPCG's multigrid-preconditioned CG.

The equations of HPCG 3.1's reference code, in numpy/scipy, with no
kernels, batching or padding, to check ``repro.core.multigrid`` against:

* the operator (``GenerateProblem_ref``): 26 on the diagonal and -1 for
  every neighbour of an nx x ny x nz grid, built here as 27 I minus the
  Kronecker product of three tridiagonal all-ones matrices;
* the coarse levels (``GenerateCoarseProblem``): the same operator on the
  grid of half the extents, coarse (i, j, k) injecting from and into fine
  (2i, 2j, 2k);
* SymGS (``ComputeSYMGS_ref``): a forward Gauss-Seidel sweep and a
  backward one, each a triangular solve, x += T^-1 (r - A x), with T the
  lower (forward) or upper (backward) triangle of A in the sweep's order;
* the V-cycle (``ComputeMG_ref``) and PCG (``CG_ref``) stopping at
  ||r|| <= rtol ||b|| from x0 = 0.

Two orders of the sweeps: ``"lexicographic"`` is HPCG's own row order;
``"colour"`` visits the rows by colour of the greedy first-fit colouring
in row order, each colour in row order, which is the order the program's
multicolour sweeps compute in.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.linalg import spsolve_triangular

__all__ = ["operator", "hierarchy", "colours", "Level", "levels", "vcycle",
           "pcg"]

ORDERS = ("lexicographic", "colour")


def operator(nx: int, ny: int, nz: int) -> sp.csr_matrix:
    """HPCG's 27-point operator on an nx x ny x nz grid (row iz*nx*ny +
    iy*nx + ix)."""
    def ones3(d):
        return sp.diags([1.0, 1.0, 1.0], [-1, 0, 1], shape=(d, d))

    near = sp.kron(sp.kron(ones3(nz), ones3(ny)), ones3(nx))
    a = (27.0 * sp.identity(nx * ny * nz) - near).tocsr()
    a.sort_indices()
    return a


def hierarchy(nx: int, ny: int, nz: int, n_levels: int = 4):
    """([A_0, ..., A_L], [f2c_0, ..., f2c_{L-1}]) of HPCG's multigrid."""
    mats, f2c = [operator(nx, ny, nz)], []
    for _ in range(n_levels - 1):
        cx, cy, cz = nx // 2, ny // 2, nz // 2
        f2c.append(np.array([2 * k * ny * nx + 2 * j * nx + 2 * i
                             for k in range(cz) for j in range(cy)
                             for i in range(cx)]))
        nx, ny, nz = cx, cy, cz
        mats.append(operator(nx, ny, nz))
    return mats, f2c


def colours(a: sp.csr_matrix) -> np.ndarray:
    """Greedy first-fit colouring in row order."""
    out = np.zeros(a.shape[0], np.int64)
    for i in range(a.shape[0]):
        nbrs = a.indices[a.indptr[i]:a.indptr[i + 1]]
        taken = set(out[nbrs[nbrs < i]].tolist())
        c = 0
        while c in taken:
            c += 1
        out[i] = c
    return out


class Level:
    """One level's operator in a sweep order, with its two triangles."""

    def __init__(self, a: sp.csr_matrix, order: str):
        if order not in ORDERS:
            raise ValueError(f"order must be one of {ORDERS}, got {order!r}")
        n = a.shape[0]
        self.a = a
        self.perm = (np.arange(n) if order == "lexicographic"
                     else np.argsort(colours(a), kind="stable"))
        b = a[self.perm][:, self.perm].tocsr()
        self.b = b
        self.lower = sp.tril(b, format="csr")
        self.upper = sp.triu(b, format="csr")

    def symgs(self, r: np.ndarray, x: np.ndarray) -> np.ndarray:
        p = self.perm
        rp, xp = r[p], x[p].copy()
        xp += spsolve_triangular(self.lower, rp - self.b @ xp, lower=True)
        xp += spsolve_triangular(self.upper, rp - self.b @ xp, lower=False)
        out = np.empty_like(x)
        out[p] = xp
        return out


def levels(nx: int, ny: int, nz: int, n_levels: int = 4,
           order: str = "colour"):
    """([Level, ...], f2c) of HPCG's problem in the given sweep order."""
    mats, f2c = hierarchy(nx, ny, nz, n_levels)
    return [Level(a, order) for a in mats], f2c


def vcycle(lvls: list, f2c: list, r: np.ndarray, lv: int = 0) -> np.ndarray:
    """HPCG's ComputeMG_ref: z = M^-1 r."""
    level = lvls[lv]
    x = level.symgs(r, np.zeros_like(r))
    if lv + 1 == len(lvls):
        return x
    rc = (r - level.a @ x)[f2c[lv]]
    x[f2c[lv]] += vcycle(lvls, f2c, rc, lv + 1)
    return level.symgs(r, x)


def pcg(lvls: list, f2c: list, b: np.ndarray, rtol: float = 1e-5,
        max_iters: int = 500):
    """CG preconditioned by one V-cycle per iteration, from x0 = 0, until
    ||r|| <= rtol ||b||.  Returns (x, iterations, [||r_k|| / ||b||])."""
    a = lvls[0].a
    b = np.asarray(b, np.float64)
    x = np.zeros_like(b)
    r = b.copy()
    bn = np.linalg.norm(b)
    hist = [np.linalg.norm(r) / bn]
    z = vcycle(lvls, f2c, r)
    p = z.copy()
    rz = r @ z
    k = 0
    while hist[-1] > rtol and k < max_iters:
        ap = a @ p
        alpha = rz / (p @ ap)
        x += alpha * p
        r -= alpha * ap
        k += 1
        hist.append(np.linalg.norm(r) / bn)
        z = vcycle(lvls, f2c, r)
        rz_new = r @ z
        p = z + (rz_new / rz) * p
        rz = rz_new
    return x, k, hist
