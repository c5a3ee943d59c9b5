"""Solver substrates: fused-kernel vs. reference implementations of the
PCG iteration's hot ops.

A *substrate* bundles the callables one PCG iteration consumes:

  ``matvec(v)``                 -- y = A v
  ``psolve(r)``                 -- z = M^-1 r
  ``dot(u, v)``                 -- (global) dot product
  ``fold_matvec_dot(z, p, b)``  -- (p', A p', dot(p', A p')): the CG
                                   denominator emitted from the matrix
                                   stream itself, with the p-update
                                   p' = z + beta*p folded into the SpMV
                                   gather -- the separate 3n p-update
                                   stream disappears (kernels.spmv_dot
                                   p-fold variants; beta = 0 recovers the
                                   plain fused SpMV + dot)
  ``update(alpha, x, r, p, ap)``-- (x', r', z, rr, rz) fused one-pass CG
                                   vector update (kernels.vecops.cg_update;
                                   for IC(0) the preconditioner application
                                   itself fuses in via the whole-solve
                                   SpTRSV kernel)

and, for the pipelined (Chronopoulos-Gear) recurrence:

  ``pipe_dots(r, u, w)``        -- stacked [gamma=(r,u), delta=(w,u),
                                   rr=(r,r)]: the pipelined iteration's
                                   ONE reduction.  Shard flavors emit a
                                   single stacked psum of all three
                                   partials; rr rides along for free, so
                                   the trace is the true ``|r|`` (not the
                                   (r, M^-1 r) surrogate).
  ``pipe_update(beta, alpha, x, r, u, w, z, q, s, p, m, n)``
                                -- the one-pass 8-vector update (all four
                                   auxiliary recurrences + the four axpys,
                                   no reduction inside).
  ``matvec_start(v)`` / ``matvec_finish(halo)``
                                -- the split communication-hiding SpMV
                                   (engine shard substrates only, halo
                                   layout): ``start`` issues the ppermute
                                   pull schedule and returns the in-flight
                                   halo; ``finish`` streams the interior
                                   rows (no dependence on the pulls) and
                                   adds the frontier rows once the halo
                                   lands.  The pipelined solver issues
                                   ``start`` on the NEXT matvec operand at
                                   the tail of each step (double-buffered
                                   halo), so the whole update/reduction/
                                   psolve tail overlaps the exchange.

``solvers.pcg``/``solvers.pcg_tol`` are written against this interface;
which implementation backs it is a deployment decision:

* ``reference_substrate`` composes the caller's matvec/psolve/dot with
  plain jnp -- bit-identical to the historical unfused iteration.  This is
  the oracle the fused paths are property-verified against.
* ``fused_local_substrate`` runs the Pallas fused kernels on a
  device-resident padded-ELL operator (TPU compiled; interpret mode for CPU
  validation via ``kernels.ops.backend_mode``).  On backends where the
  kernels are inactive it falls back to the *fused jnp composition* --
  the same arithmetic in the same order, so fused results are
  backend-independent.
* ``fused_ic0_local_substrate`` extends the local flavor to the paper's
  heavyweight preconditioner: the CG vector update runs ``cg_update`` and
  the IC(0) application runs ``kernels.sptrsv_solve_dot`` -- BOTH
  triangular solves execute as single kernel launches with the solution
  vector VMEM-resident across every wavefront (no per-level HBM round
  trip), and the second solve emits dot(r', z) = rz in-stream, so the
  preconditioned residual never takes a second pass.
* ``fused_shard_substrate`` is the ``shard_map`` flavor the engine builds
  per tile: local fused update + ONE stacked psum for [rr, rz] (the
  reduction-fusion trick of pipelined CG applied to standard PCG), and the
  NoC matvec with a psum'd denominator.  ``fused_shard_ic0_substrate`` is
  the same collective fusion with the per-tile block-IC(0) triangular
  solves as the local psolve.

Every op runs under its ``repro.obs.scopes`` layer: ``precond`` for each
``psolve``, ``update`` for the vector updates (and the p-update where it
is not folded into an ELL gather), ``reduce`` for the dots and their
``psum``s; the matvecs carry ``matvec``/``gather`` from where they are
built.

The traffic models behind the fusions (see README "Performance") are
exposed as :func:`modeled_vector_traffic` / :func:`modeled_ic0_traffic` so
benchmarks can record them.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from ..obs.scopes import scope
from . import spops

__all__ = [
    "SolverSubstrate",
    "pipe_update",
    "reference_substrate",
    "fused_local_substrate",
    "fused_ic0_local_substrate",
    "fused_shard_substrate",
    "fused_shard_ic0_substrate",
    "format_stream_ops",
    "modeled_vector_traffic",
    "modeled_ic0_traffic",
]


@scope("reduce")
def _dot(u, v):
    """Solver dot convention: () for (n,), (k, 1) for (k, n) batches."""
    return jnp.sum(u * v, axis=-1, keepdims=u.ndim > 1)


class SolverSubstrate(NamedTuple):
    """The per-iteration op bundle PCG runs against (see module docstring).

    The trailing pipelined-CG fields default to None so third-party
    substrates built positionally keep working; ``solvers.pcg_pipelined``
    falls back to jnp compositions when they are unset."""

    kind: str
    matvec: Callable
    psolve: Callable
    dot: Callable
    fold_matvec_dot: Callable
    update: Callable
    pipe_dots: Callable | None = None
    pipe_update: Callable | None = None
    matvec_start: Callable | None = None
    matvec_finish: Callable | None = None


@scope("update")
def pipe_update(beta, alpha, x, r, u, w, z, q, s, p, m, n):
    """The Chronopoulos-Gear one-pass 8-vector update.

    Inputs are the carried vectors plus the two per-step products
    m = M^-1 w and n = A m; returns the new (x, r, u, w, z, q, s, p).
    Reduction-free by construction -- every dot the recurrence needs is in
    ``pipe_dots``, so one iteration has exactly ONE collective.  This jnp
    composition is the shared fallback; a single-launch Pallas version is
    a TPU follow-up (the vectors already stream once each here, so XLA
    fuses it into one elementwise pass).
    """
    z = n + beta * z
    q = m + beta * q
    s = w + beta * s
    p = u + beta * p
    x = x + alpha * p
    r = r - alpha * s
    u = u - alpha * q
    w = w - alpha * z
    return x, r, u, w, z, q, s, p


def _pipe_dots_local(dot):
    """Local stacked [gamma, delta, rr] (no collective)."""

    @scope("reduce")
    def pipe_dots(r, u, w):
        return jnp.stack([dot(r, u), dot(w, u), dot(r, r)])

    return pipe_dots


def _pipe_dots_shard(psum):
    """Shard flavor: all three partials ride ONE stacked psum."""

    @scope("reduce")
    def pipe_dots(r, u, w):
        return psum(jnp.stack([_dot(r, u), _dot(w, u), _dot(r, r)]))

    return pipe_dots


def reference_substrate(matvec, psolve, dot=None) -> SolverSubstrate:
    """Unfused jnp composition -- the historical PCG op sequence, used as
    the verification oracle and for preconditioners without a fused path."""
    dot = dot or _dot

    @scope("precond")
    def ps(r):
        return psolve(r)

    def fold_matvec_dot(z, p, beta):
        with scope("update"):
            p = z + beta * p
        ap = matvec(p)
        return p, ap, dot(p, ap)

    @scope("update")
    def update(alpha, x, r, p, ap):
        x = x + alpha * p
        r = r - alpha * ap
        z = ps(r)
        rz = dot(r, z)
        rr = dot(r, r)
        return x, r, z, rr, rz

    return SolverSubstrate("reference", matvec, ps, dot,
                           fold_matvec_dot, update,
                           pipe_dots=_pipe_dots_local(dot),
                           pipe_update=pipe_update)


def _ell_stream_ops(cols, vals):
    """The shared ELL-operator pair (matvec, fold_matvec_dot) for local
    fused substrates: Pallas kernels when active, the fused jnp
    composition otherwise.  Vectors arrive in solver layout ((n,) or
    (k, n)); kernel calls transpose to the (n, k) kernel layout.  The
    jnp p-fold is scoped ``gather``, like the kernel path's."""

    @scope("matvec")
    def matvec(v):
        if v.ndim == 2:
            if ops.kernels_active():
                return ops.ell_spmm(cols, vals, v.T).T
            return spops.spmm_ell_padded(cols, vals, v)
        return ops.ell_spmv(cols, vals, v)

    @scope("matvec")
    def fold_matvec_dot(z, p, beta):
        if z.ndim == 2:
            if ops.kernels_active():
                pn, y, pap = ops.ell_spmm_pfold_dot(
                    cols, vals, z.T, p.T, jnp.reshape(beta, (-1,))
                )
                return pn.T, y.T, pap[:, None]
            with scope("gather"):
                pn = z + beta * p
            y = spops.spmm_ell_padded(cols, vals, pn)
            return pn, y, _dot(pn, y)
        if ops.kernels_active():
            return ops.ell_spmv_pfold_dot(cols, vals, z, p, beta)
        with scope("gather"):
            pn = z + beta * p
        y = spops.spmv_ell_padded(cols, vals, pn)
        return pn, y, _dot(pn, y)

    return matvec, fold_matvec_dot


def _fold_from_matvec(matvec):
    """Fused jnp composition of the p-fold around an arbitrary matvec:
    p' = z + beta*p at the top of the stream, then one matrix pass and the
    in-stream denominator.  This is the format-generic fold -- gather-time
    kernel folds for the compact formats are a TPU follow-up (ROADMAP)."""

    def fold_matvec_dot(z, p, beta):
        with scope("update"):
            pn = z + beta * p
        y = matvec(pn)
        return pn, y, _dot(pn, y)

    return fold_matvec_dot


def format_stream_ops(fmt_obj, fmt: str, n_pad: int):
    """The (matvec, fold_matvec_dot) pair for a non-ELL storage format.

    ``fmt_obj`` is the built format container (SELL / HYB / BCSR from
    ``core.formats``, or a matrix-free ``core.stencil.Stencil``); vectors
    are padded solver-layout ((n_pad,) or (k, n_pad)).  Each format's fold
    is the jnp composition around its own matvec, so fused and reference
    substrates built from the same pair are bitwise identical per format.
    BCSR routes through the Pallas MXU kernel (``ops.bcsr_spmm``) when
    kernels are active.
    """
    if fmt == "stencil":
        from .stencil import stencil_matvec

        def matvec(v):
            return stencil_matvec(fmt_obj, v, n_pad)

    elif fmt == "sell":

        def matvec(v):
            if v.ndim == 2:
                return spops.spmm_sell_flat(fmt_obj, v)
            return spops.spmv_sell_flat(fmt_obj, v)

    elif fmt == "hyb":

        def matvec(v):
            if v.ndim == 2:
                return spops.spmm_hyb_padded(fmt_obj, v)
            return spops.spmv_hyb_padded(fmt_obj, v)

    elif fmt == "bcsr":
        nbc = (fmt_obj.n_cols + fmt_obj.bn - 1) // fmt_obj.bn

        @scope("matvec")
        def matvec(v):
            if ops.kernels_active():
                # kernel layout: x is (nbc*bn, k); embed the padded solver
                # vector into the block row space and extract back to n_pad
                vk = v.T if v.ndim == 2 else v[:, None]
                x_blk = jnp.zeros((nbc * fmt_obj.bn, vk.shape[1]), vk.dtype)
                x_blk = x_blk.at[: fmt_obj.n_cols].set(vk[: fmt_obj.n_cols])
                y = ops.bcsr_spmm(fmt_obj.block_cols, fmt_obj.blocks, x_blk,
                                  nbc=nbc)
                nbr_rows = y.shape[0]
                if nbr_rows >= n_pad:
                    y = y[:n_pad]
                else:
                    y = jnp.zeros((n_pad, vk.shape[1]), y.dtype).at[:nbr_rows].set(y)
                return y.T if v.ndim == 2 else y[:, 0]
            if v.ndim == 2:
                return spops.spmm_bcsr_padded(fmt_obj, v, n_pad)
            return spops.spmv_bcsr_padded(fmt_obj, v, n_pad)

    else:
        raise ValueError(f"unknown stream format {fmt!r}")

    return matvec, _fold_from_matvec(matvec)


def fused_local_substrate(cols, vals, dinv=None, stream_ops=None) -> SolverSubstrate:
    """Fused kernels over a local (single-device) padded-ELL operator.

    ``cols``/``vals``: (rows_p, w) square padded ELL; ``dinv``: (rows_p,)
    Jacobi inverse diagonal, or None for an identity preconditioner.
    Vectors are (rows_p,) or batched (k, rows_p) in solver layout; the
    batched kernel calls transpose to the (n, k) kernel layout only when
    the Pallas path is active.  ``stream_ops`` overrides the matrix-stream
    pair with a non-ELL format's (see :func:`format_stream_ops`); the
    vector-side fusions (``cg_update``) are format-independent.
    """
    matvec, fold_matvec_dot = (stream_ops if stream_ops is not None
                               else _ell_stream_ops(cols, vals))

    @scope("precond")
    def psolve(r):
        return r * dinv if dinv is not None else r

    def update(alpha, x, r, p, ap):
        return ops.cg_update(alpha, x, r, p, ap, dinv)

    return SolverSubstrate("fused", matvec, psolve, _dot,
                           fold_matvec_dot, update,
                           pipe_dots=_pipe_dots_local(_dot),
                           pipe_update=pipe_update)


def fused_ic0_local_substrate(cols, vals, factors, n: int,
                              n_pad: int, stream_ops=None) -> SolverSubstrate:
    """Local fused substrate for ``precond="block_ic0"``.

    ``cols``/``vals``: the engine's (n_pad, w) padded ELL of A; ``factors``:
    :class:`repro.core.precond.IC0Factors`; ``n``: true row count.  The
    preconditioner application z = (L L^T)^-1 r' runs as two
    ``sptrsv_solve_dot`` launches -- each keeps its solution VMEM-resident
    across all wavefronts instead of round-tripping full vectors per level,
    and the second (reversed-U) solve emits rz = dot(r', z) in-stream:
    dot(r', z) == dot(flip(r'), z_rev), so the dot weight vector is just
    the flipped residual.  Batched (k, n_pad) inputs vmap the triangular
    part (the factors are shared; each RHS is an independent solve).
    """
    from .precond import make_fused_ic0_apply

    matvec, fold_matvec_dot = (stream_ops if stream_ops is not None
                               else _ell_stream_ops(cols, vals))
    # (n_pad,) residual -> (z (n_pad,), rz scalar), fully fused
    _apply_dot = make_fused_ic0_apply(factors, n, n_pad, vals.dtype)

    @scope("precond")
    def psolve(r):
        if r.ndim == 2:
            return jax.vmap(lambda v: _apply_dot(v)[0])(r)
        return _apply_dot(r)[0]

    def update(alpha, x, r, p, ap):
        # one-pass x/r update + rr (identity z discarded), then the fused
        # two-solve preconditioner application with rz in-stream
        xo, ro, _, rr, _ = ops.cg_update(alpha, x, r, p, ap, None)
        with scope("precond"):
            if ro.ndim == 2:
                z, rz = jax.vmap(_apply_dot)(ro)
                return xo, ro, z, rr, rz[:, None]
            z, rz = _apply_dot(ro)
            return xo, ro, z, rr, rz

    return SolverSubstrate("fused_ic0", matvec, psolve, _dot,
                           fold_matvec_dot, update,
                           pipe_dots=_pipe_dots_local(_dot),
                           pipe_update=pipe_update)


def _shard_stream_ops(matvec, psum):
    """The shared per-tile pair (dot, fold_matvec_dot) for the shard_map
    substrates.  The folded p-update executes here, INSIDE the per-tile
    shard closure, immediately around the communication the matvec closure
    performs (the compiled halo exchange when the engine lowered a halo
    layout, the dense collectives otherwise) -- distributed iterations run
    the same top-of-step folded recurrence as the local fused path, and
    only the updated p's halo crosses the NoC.  The fold itself is the jnp
    composition on the (u,) shard: a gather-time kernel fold would need
    the halo-extended p carried across iterations (a TPU follow-up, see
    ROADMAP); the fused win here is collective fusion (flavors below)."""

    @scope("reduce")
    def dot(u, v):
        return psum(_dot(u, v))

    def fold_matvec_dot(z, p, beta):
        with scope("update"):
            p = z + beta * p             # folded update, inside the closure
        ap = matvec(p)                   # halo exchange (or dense gather)
        return p, ap, dot(p, ap)

    return dot, fold_matvec_dot


def fused_shard_substrate(matvec, dinv, psum) -> SolverSubstrate:
    """Per-tile substrate for the engine's ``shard_map`` programs.

    ``matvec`` is the NoC-composed distributed SpMV closure (collectives
    inside); ``dinv`` the local (u,) shard of the Jacobi inverse diagonal
    (or None); ``psum`` the engine's all-axes psum.  The fused win here is
    collective fusion: the one-pass update emits local [rr, rz] partials
    that ride a SINGLE stacked psum instead of two back-to-back
    latency-bound reductions (plus the local Pallas kernel on TPU).  The
    p-update folds at the top of the step inside this same closure (see
    ``_shard_stream_ops``), wrapped around whatever communication the
    matvec closure compiled -- halo pull schedule or dense collectives.
    """

    dot, fold_matvec_dot = _shard_stream_ops(matvec, psum)

    @scope("precond")
    def psolve(r):
        return r * dinv if dinv is not None else r

    def update(alpha, x, r, p, ap):
        x, r, z, rr, rz = ops.cg_update(alpha, x, r, p, ap, dinv)
        with scope("reduce"):
            s = psum(jnp.stack([rr, rz]))  # ONE collective for both dots
        return x, r, z, s[0], s[1]

    return SolverSubstrate("fused_shard", matvec, psolve, dot,
                           fold_matvec_dot, update,
                           pipe_dots=_pipe_dots_shard(psum),
                           pipe_update=pipe_update)


def fused_shard_ic0_substrate(matvec, psolve_local, psum) -> SolverSubstrate:
    """``shard_map`` flavor for ``precond="block_ic0"``: the per-tile
    block-IC(0) triangular solves (``psolve_local``, collective-free --
    each tile factors its own diagonal block) compose with the one-pass
    ``cg_update``, and [rr, rz] ride a single stacked psum exactly as in
    :func:`fused_shard_substrate`.  The reference path for the same
    preconditioner issues three separate reductions per iteration."""

    dot, fold_matvec_dot = _shard_stream_ops(matvec, psum)

    @scope("precond")
    def psolve(r):
        return psolve_local(r)

    def update(alpha, x, r, p, ap):
        xo, ro, _, rr, _ = ops.cg_update(alpha, x, r, p, ap, None)
        z = psolve(ro)
        rz = _dot(ro, z)
        with scope("reduce"):
            s = psum(jnp.stack([rr, rz]))  # ONE collective for both dots
        return xo, ro, z, s[0], s[1]

    return SolverSubstrate("fused_shard_ic0", matvec, psolve, dot,
                           fold_matvec_dot, update,
                           pipe_dots=_pipe_dots_shard(psum),
                           pipe_update=pipe_update)


def modeled_vector_traffic(ell_width: float) -> dict:
    """Vector words moved HBM<->VMEM per Jacobi-PCG iteration, per RHS, in
    units of n (the README "Performance" model; matrix values/cols stream
    identically in both paths and are excluded).

    Unfused (one XLA op per solver line, x gathered per nonzero from HBM):
      SpMV gather w + ap write 1; dot(p,ap) 2; x-axpy 3; r-axpy 3;
      z = dinv*r 3; dot(r,z) 2; dot(r,r) 1; p-update 3   -> 18 + w.
    Fused (x VMEM-resident in the SpMV kernel, dots emitted in-stream):
      spmv_dot 2 (p in, ap out); cg_update 8 (x,r,p,ap,dinv in; x,r,z
      out); p-update 3 (beta known only after the update)  -> 13.
    Fused + p-fold (p = z + beta*p computed at gather time inside the
    SpMV kernel): the standalone p-update disappears; the fold pass
    streams z in, p in, p' out, ap out = 4; cg_update 8    -> 12.
    """
    unfused = 18.0 + float(ell_width)
    fused = 13.0
    fused_fold = 12.0
    return {
        "ell_width": float(ell_width),
        "unfused_words_per_n": unfused,
        "fused_words_per_n": fused,
        "fused_fold_words_per_n": fused_fold,
        "reduction": round(unfused / fused_fold, 3),
    }


def modeled_ic0_traffic(ell_width: float, n_levels_l: int,
                        n_levels_u: int) -> dict:
    """Vector words per IC(0)-PCG iteration, per RHS, in units of n.

    The preconditioner application is two level-scheduled SpTRSVs.
    Reference (one XLA op per wavefront): every level gathers the full
    solution vector and scatters it back -- 2n per level -- plus b in /
    x out / the two ordering flips per solve.  On top of the Jacobi
    model's non-psolve terms (18 + w - 3, dropping the 3-word diagonal
    scale) that is:

      unfused = (15 + w) + 2*(2 + 2) + 2 * (L_l + L_u)

    Fused (``sptrsv_solve_dot``): each solve keeps x VMEM-resident across
    ALL wavefronts -- b in, x out, plus the dot weight vector for the
    second solve and the two flips: ~7 words total, level-count
    independent; with the p-fold SpMV (12 - 3 non-psolve words):

      fused = 9 + 7 = 16
    """
    levels = float(n_levels_l + n_levels_u)
    unfused = (15.0 + float(ell_width)) + 8.0 + 2.0 * levels
    fused = 16.0
    return {
        "ell_width": float(ell_width),
        "n_levels_l": int(n_levels_l),
        "n_levels_u": int(n_levels_u),
        "unfused_words_per_n": unfused,
        "fused_words_per_n": fused,
        "reduction": round(unfused / fused, 3),
    }
