#!/usr/bin/env python
"""HPCG's multigrid-preconditioned CG in float64, for the benchmark.

    python3 chipbench/mg_reference.py --grid 64 64 64 [--levels 4] [--rtol 1e-5]

prints one JSON line: the iterations CG takes to ||r|| <= rtol ||b|| from
x0 = 0 on b = A 1, in each of the two sweep orders, and the relative
residual after 10, 20, 30 and 50 iterations.  Nothing here imports the
program.  It follows HPCG 3.1's reference code (``GenerateProblem_ref``,
``GenerateCoarseProblem``, ``ComputeSYMGS_ref``, ``ComputeMG_ref``,
``CG_ref``):

* A: 26 on the diagonal, -1 for each grid neighbour, row iz*nx*ny + iy*nx
  + ix; coarse levels regenerate A on half the extents, and f2c takes
  coarse (i, j, k) to fine (2i, 2j, 2k) (restriction and prolongation are
  injection);
* V-cycle: x = 0, SymGS, r_c = (r - A x)[f2c], recurse, x[f2c] += x_c,
  SymGS; the coarsest level x = 0, SymGS;
* SymGS: a forward Gauss-Seidel sweep, then a backward one.

Departures from HPCG, each what the benchmark's configuration runs:

* the sweeps may visit the rows in colour order (``"colour"``, the
  default of ``iterations``): the greedy first-fit colouring in row order,
  colour by colour, as the program computes; HPCG's own order is
  ``"lexicographic"``.  HPCG lets an optimised run reorder and charges it
  the extra iterations;
* CG stops at a relative residual, where HPCG runs sets of 50 iterations;
* the grid is the configuration's (HPCG's ``hpcg.dat`` says 104^3).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np
import scipy.sparse as sp
from scipy.sparse.linalg import spsolve_triangular


def stencil27(nx: int, ny: int, nz: int) -> sp.csr_matrix:
    """The 27-point operator: row iz*nx*ny + iy*nx + ix holds 26 on the
    diagonal and -1 for each neighbour inside the grid, as
    GenerateProblem_ref fills it."""
    iz, iy, ix = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                             indexing="ij")
    me = iz * ny * nx + iy * nx + ix
    rows, cols = [], []
    for sz in (-1, 0, 1):
        for sy in (-1, 0, 1):
            for sx in (-1, 0, 1):
                z, y, x = iz + sz, iy + sy, ix + sx
                ok = ((z >= 0) & (z < nz) & (y >= 0) & (y < ny)
                      & (x >= 0) & (x < nx))
                rows.append(me[ok])
                cols.append((z * ny * nx + y * nx + x)[ok])
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    a = sp.csr_matrix((np.where(rows == cols, 26.0, -1.0), (rows, cols)),
                      shape=(me.size, me.size))
    a.sort_indices()
    return a


def f2c(nx: int, ny: int, nz: int) -> np.ndarray:
    """GenerateCoarseProblem's map: the fine row (2i, 2j, 2k) of each row
    (i, j, k) of the grid of half the extents, coarse rows in order."""
    k, j, i = np.meshgrid(np.arange(nz // 2), np.arange(ny // 2),
                          np.arange(nx // 2), indexing="ij")
    return (2 * k * ny * nx + 2 * j * nx + 2 * i).ravel()


def first_fit_colours(a: sp.csr_matrix) -> np.ndarray:
    """Each row, in order, takes the least colour its earlier neighbours
    leave free."""
    colour = np.full(a.shape[0], -1)
    for i in range(a.shape[0]):
        cols = a.indices[a.indptr[i]:a.indptr[i + 1]]
        seen = {int(colour[j]) for j in cols if j < i}
        colour[i] = next(c for c in range(len(seen) + 1) if c not in seen)
    return colour


class Grid:
    """One multigrid level: its grid, operator and sweep order."""

    def __init__(self, nx, ny, nz, order):
        self.shape = (nx, ny, nz)
        self.a = stencil27(nx, ny, nz)
        n = self.a.shape[0]
        self.visit = (np.arange(n) if order == "lexicographic"
                      else np.lexsort((np.arange(n),
                                       first_fit_colours(self.a))))
        ap = self.a[self.visit, :][:, self.visit].tocsr()
        self.ap = ap
        self.fwd = sp.tril(ap).tocsr()
        self.bwd = sp.triu(ap).tocsr()

    def smooth(self, r, x):
        v = self.visit
        xv = x[v]
        xv = xv + spsolve_triangular(self.fwd, r[v] - self.ap @ xv,
                                     lower=True)
        xv = xv + spsolve_triangular(self.bwd, r[v] - self.ap @ xv,
                                     lower=False)
        out = np.empty_like(xv)
        out[v] = xv
        return out


def grids(nx, ny, nz, levels, order):
    out = [Grid(nx, ny, nz, order)]
    for _ in range(levels - 1):
        nx, ny, nz = nx // 2, ny // 2, nz // 2
        out.append(Grid(nx, ny, nz, order))
    return out


def mg(gs, r, level=0):
    g = gs[level]
    x = g.smooth(r, np.zeros_like(r))
    if level == len(gs) - 1:
        return x
    fine = f2c(*g.shape)
    x[fine] += mg(gs, (r - g.a @ x)[fine], level + 1)
    return g.smooth(r, x)


def cg(gs, rtol, max_iters, run_on=False):
    """(iterations to ||r|| <= rtol ||b|| on b = A 1, or None,
    [||r_k|| / ||b|| for k = 0..]).  Stops there, or with ``run_on`` after
    ``max_iters`` iterations."""
    a = gs[0].a
    b = a @ np.ones(a.shape[0])
    x = np.zeros_like(b)
    r = b.copy()
    bn = np.linalg.norm(b)
    z = mg(gs, r)
    p, rz = z.copy(), r @ z
    hist, reached = [1.0], None
    for k in range(1, max_iters + 1):
        ap = a @ p
        alpha = rz / (p @ ap)
        x += alpha * p
        r -= alpha * ap
        hist.append(np.linalg.norm(r) / bn)
        if reached is None and hist[-1] <= rtol:
            reached = k
            if not run_on:
                break
        z = mg(gs, r)
        rz, rz_old = r @ z, rz
        p = z + (rz / rz_old) * p
    return reached, hist


def iterations(grid, levels=4, rtol=1e-5, order="colour",
               max_iters=500) -> int | None:
    """Iterations to rtol on b = A 1 in the given sweep order."""
    return cg(grids(*grid, levels, order), rtol, max_iters)[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--grid", type=int, nargs=3, required=True)
    ap.add_argument("--levels", type=int, default=4)
    ap.add_argument("--rtol", type=float, default=1e-5)
    ap.add_argument("--history", type=int, default=50)
    args = ap.parse_args(argv)
    out = {"grid": args.grid, "levels": args.levels, "rtol": args.rtol}
    for order in ("colour", "lexicographic"):
        gs = grids(*args.grid, args.levels, order)
        it, hist = cg(gs, args.rtol, args.history, run_on=True)
        if it is None:
            it = cg(gs, args.rtol, 500)[0]
        out[order] = {"iterations": it,
                      "rel_residual": {str(k): hist[k] for k in (10, 20, 30, 50)
                                       if k < len(hist)}}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
