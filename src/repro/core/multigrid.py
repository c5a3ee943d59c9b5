"""HPCG's multigrid preconditioner on the stored path.

The caller builds an :class:`MGHierarchy` -- the operator and its coarse
levels, each a stored CSR, and the fine-to-coarse maps -- as HPCG's own
set-up does (``repro.data.matrices.hpcg_problem``), and hands it to
``AzulEngine`` in place of a CSR.  ``precond="mg"`` then applies one
V-cycle (HPCG's ``ComputeMG_ref``) per preconditioner application:

    level l < last:  x = 0; SymGS(r, x); r_c = (r - A x)[f2c];
                     x_c = V-cycle(l + 1, r_c); x[f2c] += x_c; SymGS(r, x)
    last level:      x = 0; SymGS(r, x)

Restriction and prolongation are injection through f2c.  SymGS is one
forward Gauss-Seidel sweep, then one backward sweep.  At engine build each
level is coloured greedily (first fit, in row order, from its sparsity: 8
colours on a 27-point grid), so the rows R of one colour are mutually
independent and a sweep is one dependent step per colour:

    x_R = x_R + (r_R - A_R x) / d_R

over colours 0..C-1 forward and C-1..0 backward.  The smoother is
symmetric, so the V-cycle is a symmetric preconditioner and CG stays
valid.  Inside the V-cycle a level's vectors are held in colour order
(each colour's rows one contiguous block), so a colour step is a gather of
x over that colour's ELL slab and a slice update of x_R.  The solver's
vectors stay in natural order: they are permuted on entry to the V-cycle
and back on exit.

The V-cycle unrolls at trace time.  The colour steps run under the
``smooth`` scope; the residual at the coarse rows, the restriction and the
prolongation under ``transfer``; the two permutations stay under the
caller's ``precond`` (``repro.obs.scopes``).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..obs import REGISTRY as _OBS
from ..obs import clock as _clock
from ..obs import span as _span
from ..obs.scopes import scope
from .formats import CSR

__all__ = ["MGHierarchy", "MGLevel", "MGOperands", "greedy_colours",
           "build", "vcycle", "make_psolve", "count_vcycles"]

_M_SETUP_S = _OBS.histogram(
    "repro_mg_setup_seconds",
    "multigrid set-up wall time at engine build: colouring and packing "
    "every level")
_M_LEVEL_ROWS = _OBS.gauge(
    "repro_mg_level_rows", "rows of each multigrid level (0 = finest)",
    ("level",))
_M_COLOURS = _OBS.gauge(
    "repro_mg_colours", "colours of each multigrid level's greedy colouring",
    ("level",))
_M_VCYCLES = _OBS.counter(
    "repro_mg_vcycles_total",
    "V-cycles applied by solves: one for the initial residual and one per "
    "iteration, per right-hand side")


class MGHierarchy(NamedTuple):
    """A stored operator with its multigrid levels.

    ``levels``: one square CSR per level, finest first; ``levels[0]`` is
    the operator.  ``f2c``: one int array per coarse level; ``f2c[l][i]``
    is the row of level ``l`` that row ``i`` of level ``l + 1`` injects
    from and into.  The CSR attributes read through to ``levels[0]``.
    """

    levels: tuple
    f2c: tuple

    @property
    def shape(self) -> tuple:
        return self.levels[0].shape

    @property
    def indptr(self) -> np.ndarray:
        return self.levels[0].indptr

    @property
    def indices(self) -> np.ndarray:
        return self.levels[0].indices

    @property
    def data(self) -> np.ndarray:
        return self.levels[0].data

    @property
    def nnz(self) -> int:
        return self.levels[0].nnz


class MGLevel(NamedTuple):
    """One level on the device, in its colour order.

    Colour c's rows are positions ``offsets[c]:offsets[c + 1]``; its ELL
    slab is ``cols[c]`` / ``vals[c]`` ((width, rows), columns as positions
    in the colour order, padding 0 with value 0) and ``dinv[c]`` its
    inverse diagonal.  ``rest`` (None on the coarsest level) holds the
    positions of the next level's rows, in that level's colour order, and
    ``rcols`` / ``rvals`` the slab of those rows."""

    offsets: tuple
    cols: tuple
    vals: tuple
    dinv: tuple
    rest: jnp.ndarray | None
    rcols: jnp.ndarray | None
    rvals: jnp.ndarray | None


class MGOperands(NamedTuple):
    """The device operands of a V-cycle: the levels, finest first, and the
    finest level's colour order (``perm``: natural row of each position;
    ``iperm``: position of each natural row)."""

    levels: tuple
    perm: jnp.ndarray
    iperm: jnp.ndarray


# -- set-up (host) ------------------------------------------------------------


def greedy_colours(a: CSR) -> np.ndarray:
    """First-fit colouring in row order: each row takes the smallest colour
    that none of its lower-numbered neighbours holds.  Raises where the
    result leaves two coupled rows in one colour (a pattern that is not
    symmetric)."""
    n = a.shape[0]
    indptr = np.asarray(a.indptr).tolist()
    indices = np.asarray(a.indices).tolist()
    colour = [0] * n
    for i in range(n):
        used = 0
        for j in indices[indptr[i]:indptr[i + 1]]:
            if j < i:
                used |= 1 << colour[j]
        colour[i] = (~used & (used + 1)).bit_length() - 1
    out = np.asarray(colour, np.int32)
    rows = np.repeat(np.arange(n), np.diff(np.asarray(a.indptr)))
    cols = np.asarray(a.indices)
    if np.any((out[rows] == out[cols]) & (rows != cols)):
        raise ValueError("greedy colouring needs a symmetric sparsity "
                         "pattern: two coupled rows share a colour")
    return out


def _slab(a: CSR, rows: np.ndarray, pos: np.ndarray, dtype):
    """ELL slab ((width, len(rows)) cols and vals) of ``rows`` of ``a``,
    columns mapped to positions by ``pos``; width is the longest row."""
    indptr = np.asarray(a.indptr)
    lens = indptr[rows + 1] - indptr[rows]
    width = max(int(lens.max(initial=0)), 1)
    k = np.arange(width)[:, None]
    ok = k < lens[None, :]
    idx = np.where(ok, indptr[rows][None, :] + k, 0)
    cols = np.where(ok, pos[np.asarray(a.indices)[idx]], 0).astype(np.int32)
    vals = np.where(ok, np.asarray(a.data)[idx], 0.0).astype(dtype)
    return jnp.asarray(cols), jnp.asarray(vals)


def _diagonal(a: CSR) -> np.ndarray:
    indptr = np.asarray(a.indptr)
    rows = np.repeat(np.arange(a.shape[0]), np.diff(indptr))
    sel = np.asarray(a.indices) == rows
    d = np.zeros(a.shape[0])
    d[rows[sel]] = np.asarray(a.data)[sel]
    if np.any(d == 0):
        raise ValueError("Gauss-Seidel needs a nonzero diagonal on every "
                         "row of every level")
    return d


def _check(h: MGHierarchy) -> None:
    if not h.levels or len(h.f2c) != len(h.levels) - 1:
        raise ValueError(
            f"an MGHierarchy needs one f2c map per coarse level: "
            f"{len(h.levels)} levels, {len(h.f2c)} maps")
    for lv, a in enumerate(h.levels):
        if a.shape[0] != a.shape[1]:
            raise ValueError(f"level {lv} is not square: {a.shape}")
    for lv, f in enumerate(h.f2c):
        f = np.asarray(f)
        nf, nc = h.levels[lv].shape[0], h.levels[lv + 1].shape[0]
        if (f.shape != (nc,) or np.any(f < 0) or np.any(f >= nf)
                or np.unique(f).size != nc):
            raise ValueError(
                f"f2c[{lv}] must map the {nc} rows of level {lv + 1} to "
                f"distinct rows of level {lv} (0..{nf - 1})")


def build(h: MGHierarchy, dtype) -> MGOperands:
    """Colour and pack every level of ``h`` for the device, under the
    ``mg.setup`` span; feeds the ``repro_mg_*`` set-up metrics."""
    t0 = _clock.now()
    with _span("mg.setup", kind="mg_setup", levels=len(h.levels)):
        _check(h)
        colours = [greedy_colours(a) for a in h.levels]
        orders = [np.argsort(c, kind="stable") for c in colours]
        poss = []
        for o in orders:
            p = np.empty_like(o)
            p[o] = np.arange(o.size)
            poss.append(p)
        levels = []
        for lv, a in enumerate(h.levels):
            order, pos = orders[lv], poss[lv]
            offsets = tuple(int(v) for v in np.concatenate(
                [[0], np.cumsum(np.bincount(colours[lv]))]))
            dinv = (1.0 / _diagonal(a))[order].astype(dtype)
            cols, vals, dinvs = [], [], []
            for c in range(len(offsets) - 1):
                o, e = offsets[c], offsets[c + 1]
                sc, sv = _slab(a, order[o:e], pos, dtype)
                cols.append(sc)
                vals.append(sv)
                dinvs.append(jnp.asarray(dinv[o:e]))
            rest = rcols = rvals = None
            if lv + 1 < len(h.levels):
                fine = np.asarray(h.f2c[lv])[orders[lv + 1]]
                rest = jnp.asarray(pos[fine].astype(np.int32))
                rcols, rvals = _slab(a, fine, pos, dtype)
            levels.append(MGLevel(offsets, tuple(cols), tuple(vals),
                                  tuple(dinvs), rest, rcols, rvals))
            _M_LEVEL_ROWS.set(float(a.shape[0]), level=str(lv))
            _M_COLOURS.set(float(len(offsets) - 1), level=str(lv))
        ops = MGOperands(tuple(levels),
                         jnp.asarray(orders[0].astype(np.int32)),
                         jnp.asarray(poss[0].astype(np.int32)))
    _M_SETUP_S.observe(_clock.now() - t0)
    return ops


# -- the V-cycle (device) -----------------------------------------------------


def _sweep(lv: MGLevel, r, x, colours):
    for c in colours:
        o, e = lv.offsets[c], lv.offsets[c + 1]
        ax = jnp.sum(lv.vals[c] * x[lv.cols[c]], axis=0)
        x = lax.dynamic_update_slice(x, x[o:e] + (r[o:e] - ax) * lv.dinv[c],
                                     (o,))
    return x


def symgs(lv: MGLevel, r, x):
    """One symmetric Gauss-Seidel: the colours forward, then backward."""
    n_colours = len(lv.cols)
    with scope("smooth"):
        x = _sweep(lv, r, x, range(n_colours))
        return _sweep(lv, r, x, range(n_colours - 1, -1, -1))


def _vcycle(levels: tuple, lv: int, r):
    level = levels[lv]
    x = symgs(level, r, jnp.zeros_like(r))
    if lv + 1 == len(levels):
        return x
    with scope("transfer"):
        rc = r[level.rest] - jnp.sum(level.rvals * x[level.rcols], axis=0)
    xc = _vcycle(levels, lv + 1, rc)
    with scope("transfer"):
        x = x.at[level.rest].add(xc, unique_indices=True)
    return symgs(level, r, x)


def vcycle(ops: MGOperands, r):
    """z = M^-1 r for one (n,) vector in natural order."""
    return _vcycle(ops.levels, 0, r[ops.perm])[ops.iperm]


def make_psolve(ops: MGOperands, n: int, n_pad: int):
    """The solver's ``psolve`` over padded ``(n_pad,)`` or ``(k, n_pad)``
    vectors: one V-cycle per vector, zero in the padding."""

    def one(r):
        z = vcycle(ops, r[:n])
        return z if n_pad == n else jnp.pad(z, (0, n_pad - n))

    def psolve(r):
        return jax.vmap(one)(r) if r.ndim == 2 else one(r)

    return psolve


def count_vcycles(iters) -> None:
    """Count a solve's V-cycles from its per-RHS iteration counts."""
    _M_VCYCLES.inc(float(np.sum(np.asarray(iters) + 1)))
