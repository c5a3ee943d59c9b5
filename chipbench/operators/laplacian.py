"""Constant-coefficient Laplacians on a grid with zero Dirichlet boundary:
the 5-point operator in 2-D (PETSc KSP ex2) and the 7-point in 3-D
(ex45), diagonal 2 per axis and -1 per neighbour.

The configuration's ``operator`` block::

    {"kind": "laplacian", "grid": [ny, nx] or [nz, ny, nx],
     "storage": "csr" | "matrix_free"}

Grid index: the last extent varies fastest.  The reference parts here
(``scipy_csr``, ``reference_matvec``, ``control_matvec``) import nothing of
the program; only ``program_operator`` hands the operator to it.
"""

from __future__ import annotations

import math

import numpy as np
import scipy.sparse as sp


def dims(cfg: dict) -> tuple:
    return tuple(int(d) for d in cfg["operator"]["grid"])


def n(cfg: dict) -> int:
    return math.prod(dims(cfg))


def nnz(cfg: dict) -> int:
    """Nonzeros of the assembled operator: the diagonal, and two per pair
    of neighbours along each axis."""
    g = dims(cfg)
    total = n(cfg)
    return total + sum(2 * (d - 1) * (total // d) for d in g)


def stored(cfg: dict) -> bool:
    storage = cfg["operator"]["storage"]
    if storage not in ("csr", "matrix_free"):
        raise ValueError(f"unknown storage {storage!r}")
    return storage == "csr"


def scipy_csr(cfg: dict) -> sp.csr_matrix:
    """The float64 operator as a scipy CSR matrix (sum of Kronecker
    products of the 1-D (2, -1, -1) operator)."""
    g = dims(cfg)
    out = None
    for ax, d in enumerate(g):
        t = sp.diags([2.0, -1.0, -1.0], [0, -1, 1], shape=(d, d))
        term = None
        for j, e in enumerate(g):
            f = t if j == ax else sp.identity(e)
            term = f if term is None else sp.kron(term, f)
        out = term if out is None else out + term
    out = out.tocsr()
    out.sort_indices()
    return out


def _stencil_f64(g: tuple, x: np.ndarray) -> np.ndarray:
    lead = x.shape[:-1]
    u = x.reshape(lead + g)
    y = 2.0 * len(g) * u
    for ax in range(len(lead), u.ndim):
        lo = [slice(None)] * u.ndim
        hi = [slice(None)] * u.ndim
        lo[ax], hi[ax] = slice(0, -1), slice(1, None)
        y[tuple(lo)] -= u[tuple(hi)]
        y[tuple(hi)] -= u[tuple(lo)]
    return y.reshape(x.shape)


def reference_matvec(cfg: dict):
    """x -> A x in float64 on the host, for (n,) or (k, n) vectors."""
    g = dims(cfg)
    if stored(cfg):
        a = scipy_csr(cfg)
        return lambda x: (a @ np.asarray(x, np.float64).T).T
    return lambda x: _stencil_f64(g, np.asarray(x, np.float64))


def diagonal(cfg: dict) -> float:
    return 2.0 * len(dims(cfg))


def control_matvec(cfg: dict, dtype):
    """x -> A x on the device in ``dtype``, for the control: the stored
    operator as padded rows gathered from x, the matrix-free one as
    shifted adds.  Nothing here comes from the program."""
    import jax.numpy as jnp

    g = dims(cfg)
    if stored(cfg):
        a = scipy_csr(cfg)
        width = int(np.diff(a.indptr).max())
        rows = np.repeat(np.arange(a.shape[0]), np.diff(a.indptr))
        slot = np.arange(a.nnz) - a.indptr[rows]
        cols = np.zeros((a.shape[0], width), np.int32)
        vals = np.zeros((a.shape[0], width), np.float64)
        cols[rows, slot] = a.indices
        vals[rows, slot] = a.data
        cols_d = jnp.asarray(cols)
        vals_d = jnp.asarray(vals, dtype)

        def mv(x):
            return jnp.sum(vals_d * x[cols_d], axis=1, dtype=dtype)
        return mv

    diag = jnp.asarray(2.0 * len(g), dtype)

    def mv(x):
        u = x.reshape(g)
        y = diag * u
        for ax in range(len(g)):
            pad = [(0, 0)] * len(g)
            pad[ax] = (1, 1)
            up = jnp.pad(u, pad)
            lo = [slice(None)] * len(g)
            hi = [slice(None)] * len(g)
            lo[ax], hi[ax] = slice(0, -2), slice(2, None)
            y = y - up[tuple(lo)] - up[tuple(hi)]
        return y.reshape(-1)
    return mv


def program_operator(cfg: dict):
    """The operator as the program takes it: its host CSR type for stored
    storage, its matrix-free ``Stencil`` otherwise."""
    if stored(cfg):
        from repro.core.formats import csr_from_scipy

        return csr_from_scipy(scipy_csr(cfg))
    from repro.core.stencil import lap2d_stencil, lap3d_stencil

    g = dims(cfg)
    if len(g) == 2:
        return lap2d_stencil(g[1], g[0])
    if len(g) == 3 and g[0] == g[1] == g[2]:
        return lap3d_stencil(g[0])
    raise ValueError(f"no matrix-free operator for grid {g}")
