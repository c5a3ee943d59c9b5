"""Matrix-free stencil operators: the coefficient stream without the storage.

The paper's motivating workloads reach millions of rows; storing even a
compact format costs O(nnz) device memory, but the PDE operators in the
benchmark suite (``data.matrices.laplacian_2d``/``laplacian_3d``) are
constant-coefficient stencils whose nonzeros are *generated*, not stored.
A :class:`Stencil` names such an operator; :func:`stencil_matvec` applies
it as shifted adds on the grid view of the solver vector -- no gathers, no
cols/vals arrays, O(n) memory total -- and produces results **bitwise
identical per format contract** to itself (fused and reference substrates
share the one matvec closure).

The engine accepts a ``Stencil`` wherever it accepts a CSR operator
(``AzulEngine(lap2d_stencil(1024))``) and lowers it through the same
registry/``SolverDef`` plumbing, so batched RHS, tolerance methods,
guards, and the plan cache come for free; ``plan.info["format"]`` reports
``"stencil"``.  Coefficients match the assembled generators exactly:

* ``lap2d``: 5-point Poisson on (nx, ny), index = y*nx + x, diag 4
* ``lap3d``: 7-point Poisson on (n, n, n), first axis slowest, diag 6

The jnp shifted-add composition is the portable definition; a Pallas
kernel that fuses the shifts with the CG dot emission is a TPU follow-up
(ROADMAP item 5).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.scopes import scope

__all__ = [
    "Stencil",
    "lap2d_stencil",
    "lap3d_stencil",
    "stencil_matvec",
    "stencil_matvec_host",
    "stencil_diag",
]


class Stencil(NamedTuple):
    """A matrix-free constant-coefficient operator.

    ``kind``: "lap2d" | "lap3d"; ``dims``: grid extents, slowest axis
    first (matching the assembled generators' kron order).
    """

    kind: str
    dims: tuple

    @property
    def n(self) -> int:
        return math.prod(self.dims)

    @property
    def shape(self) -> tuple:
        return (self.n, self.n)

    @property
    def nnz_equiv(self) -> int:
        """Nonzeros the assembled operator would store (for traffic
        models): diag + 2 per axis per interior neighbor pair."""
        total = self.n
        for ax, m in enumerate(self.dims):
            other = self.n // m
            total += 2 * (m - 1) * other
        return total


def lap2d_stencil(nx: int, ny: int | None = None) -> Stencil:
    """Matrix-free twin of ``data.matrices.laplacian_2d(nx, ny)``."""
    ny = ny or nx
    if nx < 1 or ny < 1:
        raise ValueError(f"grid extents must be >= 1, got ({nx}, {ny})")
    # index = y*nx + x: y is the slow axis
    return Stencil("lap2d", (int(ny), int(nx)))


def lap3d_stencil(n: int) -> Stencil:
    """Matrix-free twin of ``data.matrices.laplacian_3d(n)``."""
    if n < 1:
        raise ValueError(f"grid extent must be >= 1, got {n}")
    return Stencil("lap3d", (int(n), int(n), int(n)))


def stencil_diag(st: Stencil) -> float:
    """The (constant) diagonal entry -- 2 per grid axis."""
    return 2.0 * len(st.dims)


def _axis_1d(u: jnp.ndarray, axis: int) -> jnp.ndarray:
    """One tridiagonal (2, -1, -1) pass along ``axis`` with zero boundary:
    2*u - shift_down(u) - shift_up(u)."""
    z = jnp.zeros_like(jax.lax.slice_in_dim(u, 0, 1, axis=axis))
    dn = jnp.concatenate(
        [jax.lax.slice_in_dim(u, 1, None, axis=axis), z], axis=axis)
    up = jnp.concatenate(
        [z, jax.lax.slice_in_dim(u, 0, u.shape[axis] - 1, axis=axis)],
        axis=axis)
    return 2.0 * u - dn - up


@scope("matvec")
def stencil_matvec(st: Stencil, x: jnp.ndarray, n_pad: int | None = None) -> jnp.ndarray:
    """y = A x for the stencil operator on padded solver vectors.

    ``x`` is (n_pad,) or batched (k, n_pad) with n_pad >= st.n; entries
    past st.n are ignored on input and returned as zeros, matching the
    stored-format matvecs' padded-row contract.  The coefficient stream is
    generated in the kernel: one shifted-add pass per grid axis on the
    grid view, no stored nonzeros.
    """
    n = st.n
    if n_pad is None:
        n_pad = x.shape[-1]
    batched = x.ndim == 2
    lead = (x.shape[0],) if batched else ()
    u = x[..., :n].reshape(lead + st.dims)
    y = jnp.zeros_like(u)
    nd = len(st.dims)
    for ax in range(nd):
        y = y + _axis_1d(u, axis=ax + (1 if batched else 0))
    y = y.reshape(lead + (n,))
    if n_pad == n:
        return y
    out = jnp.zeros(lead + (n_pad,), y.dtype)
    return out.at[..., :n].set(y)


def stencil_matvec_host(st: Stencil, x) -> np.ndarray:
    """float64 numpy oracle of :func:`stencil_matvec` on unpadded (n,) or
    (k, n) vectors -- the host reference device solves are checked
    against (a million-row stencil has no assembled CSR to hand scipy)."""
    x = np.asarray(x, np.float64)
    lead = x.shape[:-1]
    u = x.reshape(lead + st.dims)
    y = stencil_diag(st) * u
    for ax in range(len(lead), u.ndim):
        lo = [slice(None)] * u.ndim
        hi = [slice(None)] * u.ndim
        lo[ax], hi[ax] = slice(0, -1), slice(1, None)
        y[tuple(lo)] -= u[tuple(hi)]
        y[tuple(hi)] -= u[tuple(lo)]
    return y.reshape(x.shape)
