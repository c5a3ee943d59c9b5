"""Iterative solvers (CG / PCG / Jacobi) as pure JAX programs.

The solvers are written against an abstract linear-operator interface so the
same code runs single-device (operators from ``spops``) and distributed
(operators the ``AzulEngine`` builds inside ``shard_map``):

  ``matvec(x)`` -- y = A x           (the only place A is touched)
  ``psolve(r)`` -- z = M^-1 r        (preconditioner application)
  ``dot(u, v)`` -- global dot product (the engine injects a psum-ing dot)

All vector math is elementwise, so it is layout-oblivious: vectors may be
full arrays or per-tile shards, as long as ``matvec``/``dot`` agree on the
layout.  Iteration count is static (``lax.scan``) so the program lowers to a
fixed HLO -- required for the dry-run/roofline path; ``*_tol`` variants use
``lax.while_loop`` for tolerance-based stopping.

Batched multi-RHS solves: ``b`` may be ``(n,)`` or stacked ``(k, n)``.  All
vector updates broadcast over the leading batch axis; ``dot`` reduces the
*last* axis only (keeping a trailing singleton for batched inputs, so the
per-RHS alpha/beta scalars broadcast back against ``(k, n)`` vectors).
Every RHS shares the one matrix -- ``matvec`` sees the stacked block, which
is exactly the amortize-the-matrix-stream regime the batched kernels
(``ell_spmm``) exploit.  Residual traces become ``(iters + 1, k)`` and
iteration counts ``(k,)``.

Fused hot path: ``pcg``/``pcg_tol``/``pcg_pipelined`` accept a ``substrate``
(:mod:`repro.core.substrate`) bundling fused implementations of the
iteration's ops -- SpMV with the dot(p, Ap) denominator emitted from the
matrix stream, the p-update folded into the SpMV gather, and a one-pass
vector update producing x', r', z and both dots (for IC(0), with the two
triangular solves as single whole-solve kernels).  With ``substrate=None``
a reference substrate is composed from the ``matvec``/``psolve``/``dot``
arguments, reproducing the historical unfused op sequence exactly; the
engine injects fused substrates (Pallas kernels locally, collective-fused
shard substrates under ``shard_map``).

Numerical health guards (``guard=True``, the default): each iteration
inspects the reduction slots it has ALREADY computed (``rr``/``rz``/
``denom`` for PCG, the stacked ``[gamma, delta, rr]`` for the pipelined
recurrence) for NaN/Inf, indefiniteness (``rho <= 0`` where positivity is
required), residual divergence, and -- in tolerance mode -- stagnation.
Faulted RHS freeze at their last finite iterate (per-lane ``jnp.where``
select, so a clean solve is bit-identical to ``guard=False``) and the
result carries a structured per-RHS ``status`` plus the first bad
iteration.  The guards add zero collectives: every test reads a slot the
recurrence already reduced.

Convergence bookkeeping (residual-norm trace) is carried through the scan so
benchmarks can plot paper-style convergence curves without re-running.

Each solver runs under the ``control`` scope (``repro.obs.scopes``): the
loop, the stopping test, the guards, the freeze selects and the residual
ring are ``control``; the operator, preconditioner, update and reduction
ops inside carry their own, inner scopes.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax.numpy as jnp
from jax import lax

from ..obs.scopes import scope
from .substrate import SolverSubstrate, reference_substrate
from .substrate import pipe_update as _pipe_update

__all__ = ["SolveResult", "cg", "pcg", "pcg_pipelined",
           "pcg_pipelined_tol", "jacobi", "pcg_tol",
           "STATUS_CONVERGED", "STATUS_MAXITER", "STATUS_BREAKDOWN",
           "STATUS_DIVERGED", "STATUS_STAGNATED", "STATUS_UNGUARDED",
           "status_name", "ensure_status",
           "DIVERGENCE_FACTOR", "STALL_WINDOW"]

Vec = jnp.ndarray
MatVec = Callable[[Vec], Vec]
Dot = Callable[[Vec, Vec], jnp.ndarray]

# Structured per-RHS solve status.  Fixed-iteration methods report
# ``maxiter`` on clean completion (they run the full budget; there is no
# stopping test); tolerance methods distinguish converged from maxiter.
STATUS_CONVERGED = 0     # tolerance met
STATUS_MAXITER = 1       # iteration budget exhausted (or fixed-iter run)
STATUS_BREAKDOWN = 2     # NaN/Inf or indefinite M / A (rho or pAp <= 0)
STATUS_DIVERGED = 3      # residual grew past DIVERGENCE_FACTOR * |r0|
STATUS_STAGNATED = 4     # no new best residual for STALL_WINDOW iterations
STATUS_UNGUARDED = -1    # method ran without guards (jacobi, guard=False)

_STATUS_NAMES = {
    STATUS_CONVERGED: "converged",
    STATUS_MAXITER: "maxiter",
    STATUS_BREAKDOWN: "breakdown",
    STATUS_DIVERGED: "diverged",
    STATUS_STAGNATED: "stagnated",
    STATUS_UNGUARDED: "unguarded",
}

# Residual growth treated as divergence.  CG's 2-norm residual is not
# monotone and may transiently exceed |r0|, but 8 orders of magnitude of
# growth never happens on a converging SPD solve -- while injected faults
# (exponent bit-flips, dropped updates) blow through it within iterations.
DIVERGENCE_FACTOR = 1e8

# Tolerance-mode stagnation: a lane that has not produced a NEW best
# residual norm for this many consecutive iterations is stalled (singular
# or numerically rank-deficient system at the requested tolerance).
STALL_WINDOW = 100

# Sign-based breakdown tests (rho/gamma/denominator <= 0) carry information
# only while there is residual left to reduce: once ||r|| sits at the
# rounding floor relative to ||r0|| (a fixed-iteration solve running past
# convergence), the recurrence scalars are dominated by cancellation noise
# and their signs flip benignly.  Sign checks are therefore gated on the
# PRE-step residual exceeding this floor (in units of dtype eps, relative
# to ||r0||); non-finite checks are never gated -- NaN/Inf cannot appear
# in a clean solve.
SIGN_GUARD_FLOOR = 1e3


def status_name(code: int) -> str:
    """Human-readable name for a status code (``'breakdown'``, ...)."""
    return _STATUS_NAMES.get(int(code), f"unknown({int(code)})")


class SolveResult(NamedTuple):
    x: Vec                      # (n,) or (k, n) -- mirrors b
    res_norms: jnp.ndarray      # (iters + 1,) or (iters + 1, k) 2-norm trace
    iters: jnp.ndarray          # int32 () or (k,) -- iterations applied
    # per-RHS structured status (int32, STATUS_*); None from solvers that
    # predate guards -- engine programs normalize via ensure_status
    status: jnp.ndarray | None = None
    # 1-based iteration at which a guard first tripped (res_norms[bad_iter]
    # is where the lane froze); -1 = no fault
    bad_iter: jnp.ndarray | None = None


@scope("reduce")
def _default_dot(u: Vec, v: Vec) -> jnp.ndarray:
    """Last-axis dot: () for (n,) vectors, (k, 1) for (k, n) batches --
    broadcastable back against the vectors it was computed from."""
    return jnp.sum(u * v, axis=-1, keepdims=u.ndim > 1)


def _norm(d: jnp.ndarray) -> jnp.ndarray:
    """sqrt of a dot result, squeezed to () / (k,) for the residual trace."""
    rn = jnp.sqrt(d)
    return rn[..., 0] if rn.ndim else rn


def _iters_like(b: Vec, iters) -> jnp.ndarray:
    """Per-RHS iteration counts: int32 () for (n,) b, (k,) for (k, n) b."""
    return jnp.full(b.shape[:-1], iters, jnp.int32)


def _sq(d: jnp.ndarray) -> jnp.ndarray:
    """Squeeze a dot result to the per-RHS scalar shape () / (k,)."""
    return d[..., 0] if d.ndim else d


def _sel(ok: jnp.ndarray, new: jnp.ndarray, old: jnp.ndarray) -> jnp.ndarray:
    """Per-RHS freeze select: lanes with ``ok`` keep the freshly computed
    value, faulted lanes keep the pre-step one.  ``jnp.where`` on an
    all-true mask returns ``new`` element-identically, so clean solves are
    bitwise unchanged by the guard plumbing."""
    o = ok.reshape(ok.shape + (1,) * (new.ndim - ok.ndim))
    return jnp.where(o, new, old)


def _guard_flags(rn, *dots):
    """Non-finite detector over a residual norm and dot-result slots."""
    bad = ~jnp.isfinite(rn)
    for d in dots:
        bad = bad | ~jnp.isfinite(_sq(d))
    return bad


def _sign_live(rn_prev, r0):
    """Lanes whose pre-step residual is still above the sign-guard floor
    (see SIGN_GUARD_FLOOR) -- only these lanes take sign-based breakdown."""
    eps = jnp.finfo(jnp.asarray(rn_prev).dtype).eps
    return rn_prev > (SIGN_GUARD_FLOOR * eps) * r0


def _fault_code(breakdown, diverged, stalled=None):
    """Merge per-lane fault predicates into a status code with priority
    breakdown > diverged > stagnated; 0 where no fault."""
    code = jnp.where(diverged, jnp.int32(STATUS_DIVERGED), jnp.int32(0))
    if stalled is not None:
        code = jnp.where(stalled & (code == 0),
                         jnp.int32(STATUS_STAGNATED), code)
    return jnp.where(breakdown, jnp.int32(STATUS_BREAKDOWN), code)


def ensure_status(res: SolveResult, b: Vec) -> SolveResult:
    """Fill missing status/bad_iter (solvers that predate guards, external
    registry entries) with UNGUARDED / -1 so every compiled program returns
    the full 5-field result."""
    if res.status is not None and res.bad_iter is not None:
        return res
    status = (res.status if res.status is not None
              else _iters_like(b, STATUS_UNGUARDED))
    bad = res.bad_iter if res.bad_iter is not None else _iters_like(b, -1)
    return SolveResult(res.x, res.res_norms, res.iters, status, bad)


@scope("control")
def cg(
    matvec: MatVec,
    b: Vec,
    x0: Vec | None = None,
    iters: int = 100,
    dot: Dot = _default_dot,
    substrate: SolverSubstrate | None = None,
    guard: bool = True,
) -> SolveResult:
    """Conjugate gradients, fixed iteration count (scan)."""
    return pcg(matvec, b, x0=x0, iters=iters, psolve=lambda r: r, dot=dot,
               substrate=substrate, guard=guard)


@scope("control")
def pcg(
    matvec: MatVec,
    b: Vec,
    psolve: Callable[[Vec], Vec],
    x0: Vec | None = None,
    iters: int = 100,
    dot: Dot = _default_dot,
    substrate: SolverSubstrate | None = None,
    guard: bool = True,
) -> SolveResult:
    """Preconditioned CG (fixed iterations, residual trace carried).

    This is the paper's workload: each iteration is one SpMV (matvec), one
    (or two, for IC(0)) SpTRSV (psolve), two dots and three axpys -- the
    exact op mix Azul keeps on-chip.  ``b`` may be ``(k, n)``: the per-RHS
    alpha/beta arrive as ``(k, 1)`` from ``dot`` and broadcast, so the k
    solves advance in lockstep off one matvec per iteration.

    The iteration is phrased against a :class:`SolverSubstrate`: with
    ``substrate=None`` a reference substrate wraps the ``matvec``/
    ``psolve``/``dot`` arguments (the historical unfused sequence); a fused
    substrate runs the same recurrence with the denominator emitted from
    the matrix stream and the three vector updates + two dots in one pass.
    The loop is phrased in *folded* form: ``p = z + beta p`` executes at
    the top of the step through ``fold_matvec_dot``, so fused substrates
    can compute it at SpMV-gather time (same recurrence, same values --
    the scan simply carries (z, beta) instead of a pre-updated p).

    With ``guard=True`` each step checks the denominators and ``rr`` it
    already reduced (NaN/Inf, ``pAp < 0`` with ``rz > 0`` or ``rz' < 0``
    => breakdown; residual blow-up => diverged) and freezes faulted RHS at
    their last finite iterate; ``status``/``bad_iter`` report per RHS.
    A clean run is bit-identical to ``guard=False``.
    """
    sub = substrate if substrate is not None else reference_substrate(
        matvec, psolve, dot
    )
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - sub.matvec(x)
    z = sub.psolve(r)
    rz = sub.dot(r, z)
    r0 = _norm(sub.dot(r, r))
    p = jnp.zeros_like(b)
    beta = jnp.zeros_like(rz)          # first fold: p = z + 0*0 = z

    if not guard:
        def step(carry, _):
            x, r, z, p, rz, beta = carry
            p, ap, denom = sub.fold_matvec_dot(z, p, beta)
            alpha = rz / jnp.where(denom == 0, 1.0, denom)
            x, r, z, rr, rz_new = sub.update(alpha, x, r, p, ap)
            beta = rz_new / jnp.where(rz == 0, 1.0, rz)
            return (x, r, z, p, rz_new, beta), _norm(rr)

        (x, r, z, p, rz, beta), norms = lax.scan(
            step, (x, r, z, p, rz, beta), None, length=iters
        )
        return SolveResult(x, jnp.concatenate([r0[None], norms]),
                           _iters_like(b, iters),
                           _iters_like(b, STATUS_UNGUARDED),
                           _iters_like(b, -1))

    # init-time guard: a non-finite initial residual / rz (operator or b
    # already poisoned) must not masquerade as a clean run
    init_bad = _guard_flags(r0, rz)
    fault0 = jnp.where(init_bad, jnp.int32(STATUS_BREAKDOWN), jnp.int32(0))
    bad0 = jnp.where(init_bad, jnp.int32(0), jnp.int32(-1))
    fault0 = fault0 + _iters_like(b, 0)      # broadcast to per-RHS shape
    bad0 = bad0 + _iters_like(b, 0)

    def step(carry, i):
        x, r, z, p, rz, beta, rn_prev, fault, bad = carry
        p2, ap, denom = sub.fold_matvec_dot(z, p, beta)
        alpha = rz / jnp.where(denom == 0, 1.0, denom)
        x2, r2, z2, rr, rz_new = sub.update(alpha, x, r, p2, ap)
        beta2 = rz_new / jnp.where(rz == 0, 1.0, rz)
        rn = _norm(rr)
        # guards read slots the update already reduced -- no new collectives
        sign_bad = (((_sq(denom) < 0) & (_sq(rz) > 0))
                    | (_sq(rz_new) < 0))
        breakdown = (_guard_flags(rn, denom, rz_new)
                     | (_sign_live(rn_prev, r0) & sign_bad))
        diverged = rn > DIVERGENCE_FACTOR * r0
        newly = (fault == 0) & (breakdown | diverged)
        fault = jnp.where(newly, _fault_code(breakdown, diverged), fault)
        bad = jnp.where(newly, (i + 1).astype(jnp.int32), bad)
        good = fault == 0
        rn_out = jnp.where(good, rn, rn_prev)
        carry = (_sel(good, x2, x), _sel(good, r2, r), _sel(good, z2, z),
                 _sel(good, p2, p), _sel(good, rz_new, rz),
                 _sel(good, beta2, beta), rn_out, fault, bad)
        return carry, rn_out

    (x, r, z, p, rz, beta, _rn, fault, bad), norms = lax.scan(
        step, (x, r, z, p, rz, beta, r0, fault0, bad0), jnp.arange(iters)
    )
    status = jnp.where(fault != 0, fault, jnp.int32(STATUS_MAXITER))
    return SolveResult(x, jnp.concatenate([r0[None], norms]),
                       _iters_like(b, iters), status, bad)


def _pipe_ops(matvec, psolve, dot, dot2, substrate):
    """Resolve the pipelined iteration's op bundle (shared by the fixed-
    and tolerance-mode variants).

    Returns ``(sub, pdots, pupd, overlapped)`` where ``pdots(r, u, w)`` is
    the stacked [gamma=(r,u), delta=(w,u), rr=(r,r)] reduction -- the
    iteration's ONE collective.  Precedence: an explicit substrate's
    ``pipe_dots`` (shard flavors psum the stack once); else the injected
    ``dot2`` (the engine's stacked-psum reducer, so even the *reference*
    distributed path keeps one collective); else a stack of ``sub.dot``.
    ``overlapped`` is True when the substrate carries the split
    communication-hiding matvec (``matvec_start``/``matvec_finish``).
    """
    sub = substrate if substrate is not None else reference_substrate(
        matvec, psolve, dot
    )
    if substrate is not None and substrate.pipe_dots is not None:
        pdots = substrate.pipe_dots
    elif dot2 is not None:
        def pdots(r, u, w):
            return dot2(r, u, w, u, r, r)
    elif sub.pipe_dots is not None:
        pdots = sub.pipe_dots
    else:
        def pdots(r, u, w):
            return jnp.stack([sub.dot(r, u), sub.dot(w, u), sub.dot(r, r)])
    pupd = sub.pipe_update if sub.pipe_update is not None else _pipe_update
    overlapped = (sub.matvec_start is not None
                  and sub.matvec_finish is not None)
    return sub, pdots, pupd, overlapped


def _pipe_scalars(first, gamma, delta, gamma_old, alpha_old):
    """The Chronopoulos-Gear scalar recurrence with breakdown guards:
    beta = gamma/gamma_old (0 on the first step), alpha = gamma / (delta -
    beta*gamma/alpha_old).  Zero denominators (converged or zero RHS) give
    alpha = 0 -- the iteration freezes instead of emitting NaN."""
    beta = jnp.where(first, 0.0,
                     gamma / jnp.where(gamma_old == 0, 1.0, gamma_old))
    denom = delta - beta * gamma / jnp.where(alpha_old == 0, 1.0, alpha_old)
    alpha = gamma / jnp.where(denom == 0, 1.0, denom)
    return beta, alpha


def _pipe_guard(gd, rn, rn_prev, r0n):
    """Guard predicates for the pipelined recurrence, read entirely off the
    iteration's single stacked reduction: gamma = (r, M^-1 r) < 0 => M
    indefinite; delta = (A u, u) < 0 with gamma > 0 => A indefinite.  Sign
    tests apply only to lanes still above the sign-guard floor."""
    gq, dq = _sq(gd[0]), _sq(gd[1])
    sign_bad = (gq < 0) | ((dq < 0) & (gq > 0))
    breakdown = (_guard_flags(rn, gd[0], gd[1])
                 | (_sign_live(rn_prev, r0n) & sign_bad))
    diverged = rn > DIVERGENCE_FACTOR * r0n
    return breakdown, diverged


@scope("control")
def pcg_pipelined(
    matvec: MatVec,
    b: Vec,
    psolve: Callable[[Vec], Vec],
    x0: Vec | None = None,
    iters: int = 100,
    dot2: Callable[..., jnp.ndarray] | None = None,
    dot: Dot = _default_dot,
    substrate: SolverSubstrate | None = None,
    guard: bool = True,
) -> SolveResult:
    """Chronopoulos-Gear pipelined PCG: ONE fused reduction per iteration.

    Standard PCG issues 2-3 separate global reductions per iteration (rz,
    pAp, ||r||) -- each a latency-bound psum across the whole pod.  The
    CG-CG recurrence computes gamma = (r,u) and delta = (w,u) on the same
    vectors, so both dots -- plus rr = (r,r), which makes the trace the
    TRUE residual norm, comparable with ``pcg``'s -- ride a single stacked
    reduction.  The initial residual norm comes from the same stacked
    reduction, so it is globally correct under ``shard_map`` too.  Beyond-
    paper optimization; numerically equivalent in exact arithmetic (Tiwari
    & Vadhiyar 2022, the paper's ref [5]).

    Communication hiding: the matvec operand of step ``k+1`` is
    ``m = M^-1 w``, computable at the *tail* of step ``k`` with no
    collective.  The scan therefore carries ``(m, halo)``: when the
    substrate supplies the split matvec (``matvec_start``/
    ``matvec_finish``), each step issues the halo pulls for the next
    operand before returning, and the in-flight exchange overlaps the
    whole update/reduction/psolve tail (double-buffered across
    iterations).  Without the split ops the step simply calls ``matvec``
    -- identical values either way (SpMV linearity; see ``commplan``).

    ``dot2(a1, b1, a2, b2, ...)`` stacks dot(ai, bi) pairs under a single
    collective (the engine injects a psum-of-stack version); a
    ``substrate`` supplies kernel-backed ops including the stacked
    ``pipe_dots`` and the one-pass 8-vector ``pipe_update``.

    Guards read the same stacked reduction (gamma < 0, delta < 0 with
    gamma > 0, NaN/Inf, divergence) -- still ONE collective per iteration.
    """
    sub, pdots, pupd, overlapped = _pipe_ops(matvec, psolve, dot, dot2,
                                             substrate)
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - sub.matvec(x)
    u = sub.psolve(r)
    w = sub.matvec(u)
    gd = pdots(r, u, w)            # ONE stacked reduction: [gamma, delta, rr]
    gamma, delta = gd[0], gd[1]
    r0 = _norm(jnp.maximum(gd[2], 0.0))
    m = sub.psolve(w)              # first matvec operand, issued pre-loop
    h = sub.matvec_start(m) if overlapped else ()

    zv = jnp.zeros_like(b)
    state = (x, r, u, w, zv, zv, zv, zv, m, h, gamma, delta,
             jnp.ones_like(gamma), jnp.ones_like(gamma))

    if not guard:
        def step(carry, i):
            (x, r, u, w, z, q, s, p, m, h, gamma, delta,
             gamma_old, alpha_old) = carry
            nv = sub.matvec_finish(h) if overlapped else sub.matvec(m)
            beta, alpha = _pipe_scalars(i == 0, gamma, delta,
                                        gamma_old, alpha_old)
            x, r, u, w, z, q, s, p = pupd(beta, alpha, x, r, u, w, z, q, s,
                                          p, m, nv)
            gd = pdots(r, u, w)    # the iteration's ONE collective
            m = sub.psolve(w)      # next operand: local, so its halo
            h = sub.matvec_start(m) if overlapped else ()  # flies over tail
            return (x, r, u, w, z, q, s, p, m, h, gd[0], gd[1], gamma,
                    alpha), _norm(jnp.maximum(gd[2], 0.0))

        state, norms = lax.scan(step, state, jnp.arange(iters))
        return SolveResult(state[0], jnp.concatenate([r0[None], norms]),
                           _iters_like(b, iters),
                           _iters_like(b, STATUS_UNGUARDED),
                           _iters_like(b, -1))

    init_bad = _guard_flags(r0, gd[0], gd[1])
    fault0 = (jnp.where(init_bad, jnp.int32(STATUS_BREAKDOWN), jnp.int32(0))
              + _iters_like(b, 0))
    bad0 = (jnp.where(init_bad, jnp.int32(0), jnp.int32(-1))
            + _iters_like(b, 0))
    state = state + (r0, fault0, bad0)

    def step(carry, i):
        (x, r, u, w, z, q, s, p, m, h, gamma, delta, gamma_old, alpha_old,
         rn_prev, fault, bad) = carry
        nv = sub.matvec_finish(h) if overlapped else sub.matvec(m)
        beta, alpha = _pipe_scalars(i == 0, gamma, delta,
                                    gamma_old, alpha_old)
        x2, r2, u2, w2, z2, q2, s2, p2 = pupd(beta, alpha, x, r, u, w, z, q,
                                              s, p, m, nv)
        gd = pdots(r2, u2, w2)     # the iteration's ONE collective
        rn = _norm(jnp.maximum(gd[2], 0.0))
        m2 = sub.psolve(w2)
        h2 = sub.matvec_start(m2) if overlapped else ()
        breakdown, diverged = _pipe_guard(gd, rn, rn_prev, r0)
        newly = (fault == 0) & (breakdown | diverged)
        fault = jnp.where(newly, _fault_code(breakdown, diverged), fault)
        bad = jnp.where(newly, (i + 1).astype(jnp.int32), bad)
        good = fault == 0
        rn_out = jnp.where(good, rn, rn_prev)
        carry = (_sel(good, x2, x), _sel(good, r2, r), _sel(good, u2, u),
                 _sel(good, w2, w), _sel(good, z2, z), _sel(good, q2, q),
                 _sel(good, s2, s), _sel(good, p2, p), _sel(good, m2, m),
                 tuple(_sel(good, hn, ho) for hn, ho in zip(h2, h)),
                 _sel(good, gd[0], gamma), _sel(good, gd[1], delta),
                 _sel(good, gamma, gamma_old), _sel(good, alpha, alpha_old),
                 rn_out, fault, bad)
        return carry, rn_out

    state, norms = lax.scan(step, state, jnp.arange(iters))
    fault, bad = state[15], state[16]
    status = jnp.where(fault != 0, fault, jnp.int32(STATUS_MAXITER))
    return SolveResult(state[0], jnp.concatenate([r0[None], norms]),
                       _iters_like(b, iters), status, bad)


@scope("control")
def pcg_pipelined_tol(
    matvec: MatVec,
    b: Vec,
    psolve: Callable[[Vec], Vec],
    x0: Vec | None = None,
    tol: float = 1e-8,
    max_iters: int = 1000,
    dot2: Callable[..., jnp.ndarray] | None = None,
    dot: Dot = _default_dot,
    substrate: SolverSubstrate | None = None,
    guard: bool = True,
) -> SolveResult:
    """Pipelined PCG with relative-tolerance stopping (while_loop).

    Same recurrence and op bundle as :func:`pcg_pipelined`; the stopping
    test reuses the rr slot of the iteration's single stacked reduction
    (the true ``|r|``, same quantity ``pcg_tol`` tests), so tolerance mode
    still has exactly ONE collective per iteration.  The bounded residual
    ring, batched semantics, tail-fill and guard/status semantics match
    :func:`pcg_tol`."""
    sub, pdots, pupd, overlapped = _pipe_ops(matvec, psolve, dot, dot2,
                                             substrate)
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - sub.matvec(x)
    u = sub.psolve(r)
    w = sub.matvec(u)
    gd = pdots(r, u, w)
    gamma, delta = gd[0], gd[1]
    r0n = _norm(jnp.maximum(gd[2], 0.0))
    bnorm = _norm(jnp.maximum(sub.dot(b, b), 0.0))
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)
    m = sub.psolve(w)
    h = sub.matvec_start(m) if overlapped else ()
    zv = jnp.zeros_like(b)
    trace0 = jnp.zeros((max_iters + 1,) + r0n.shape, r0n.dtype).at[0].set(r0n)
    act0 = r0n / bnorm > tol
    it0 = _iters_like(b, 0)

    if not guard:
        def cond(state):
            act, k = state[16], state[18]
            return jnp.any(act) & (k < max_iters)

        def body(state):
            (x, r, u, w, z, q, s, p, m, h, gamma, delta, gamma_old,
             alpha_old, _rn, it, act, trace, k) = state
            it = it + act.astype(jnp.int32)
            nv = sub.matvec_finish(h) if overlapped else sub.matvec(m)
            beta, alpha = _pipe_scalars(k == 0, gamma, delta,
                                        gamma_old, alpha_old)
            x, r, u, w, z, q, s, p = pupd(beta, alpha, x, r, u, w, z, q, s,
                                          p, m, nv)
            gd = pdots(r, u, w)    # ONE collective; rr drives the test
            rn = _norm(jnp.maximum(gd[2], 0.0))
            trace = trace.at[k + 1].set(rn)
            act = rn / bnorm > tol
            m = sub.psolve(w)
            h = sub.matvec_start(m) if overlapped else ()
            return (x, r, u, w, z, q, s, p, m, h, gd[0], gd[1], gamma,
                    alpha, rn, it, act, trace, k + 1)

        state = lax.while_loop(
            cond, body,
            (x, r, u, w, zv, zv, zv, zv, m, h, gamma, delta,
             jnp.ones_like(gamma), jnp.ones_like(gamma), r0n, it0, act0,
             trace0, jnp.int32(0)),
        )
        x, it, trace, k = state[0], state[15], state[17], state[18]
        idx = jnp.arange(max_iters + 1)
        written = (idx <= k).reshape((-1,) + (1,) * (trace.ndim - 1))
        trace = jnp.where(written, trace, trace[k])
        return SolveResult(x, trace, it,
                           _iters_like(b, STATUS_UNGUARDED),
                           _iters_like(b, -1))

    def cond(state):
        act, k = state[16], state[18]
        return jnp.any(act) & (k < max_iters)

    def body(state):
        (x, r, u, w, z, q, s, p, m, h, gamma, delta, gamma_old, alpha_old,
         rn_prev, it, act, trace, k, fault, bad, best, since) = state
        it = it + act.astype(jnp.int32)
        nv = sub.matvec_finish(h) if overlapped else sub.matvec(m)
        beta, alpha = _pipe_scalars(k == 0, gamma, delta,
                                    gamma_old, alpha_old)
        x2, r2, u2, w2, z2, q2, s2, p2 = pupd(beta, alpha, x, r, u, w, z, q,
                                              s, p, m, nv)
        gd = pdots(r2, u2, w2)     # ONE collective; rr drives the test
        rn = _norm(jnp.maximum(gd[2], 0.0))
        m2 = sub.psolve(w2)
        h2 = sub.matvec_start(m2) if overlapped else ()
        breakdown, diverged = _pipe_guard(gd, rn, rn_prev, r0n)
        improved = rn < best
        best = jnp.minimum(rn, best)
        since = jnp.where(improved, 0, since + 1)
        stalled = act & (since >= STALL_WINDOW)
        newly = (fault == 0) & (breakdown | diverged | stalled)
        fault = jnp.where(newly, _fault_code(breakdown, diverged, stalled),
                          fault)
        bad = jnp.where(newly, k + 1, bad)
        good = fault == 0
        rn_out = jnp.where(good, rn, rn_prev)
        trace = trace.at[k + 1].set(rn_out)
        act = good & (rn / bnorm > tol)
        return (_sel(good, x2, x), _sel(good, r2, r), _sel(good, u2, u),
                _sel(good, w2, w), _sel(good, z2, z), _sel(good, q2, q),
                _sel(good, s2, s), _sel(good, p2, p), _sel(good, m2, m),
                tuple(_sel(good, hn, ho) for hn, ho in zip(h2, h)),
                _sel(good, gd[0], gamma), _sel(good, gd[1], delta),
                _sel(good, gamma, gamma_old), _sel(good, alpha, alpha_old),
                rn_out, it, act, trace, k + 1, fault, bad, best, since)

    init_bad = _guard_flags(r0n, gd[0], gd[1]) | ~jnp.isfinite(bnorm)
    fault0 = (jnp.where(init_bad, jnp.int32(STATUS_BREAKDOWN), jnp.int32(0))
              + it0)
    bad0 = jnp.where(init_bad, jnp.int32(0), jnp.int32(-1)) + it0
    act0 = (fault0 == 0) & act0
    state = lax.while_loop(
        cond, body,
        (x, r, u, w, zv, zv, zv, zv, m, h, gamma, delta,
         jnp.ones_like(gamma), jnp.ones_like(gamma), r0n, it0, act0,
         trace0, jnp.int32(0), fault0, bad0, r0n, it0),
    )
    x, it, act, trace, k = (state[0], state[15], state[16], state[17],
                            state[18])
    fault, bad = state[19], state[20]
    idx = jnp.arange(max_iters + 1)
    written = (idx <= k).reshape((-1,) + (1,) * (trace.ndim - 1))
    trace = jnp.where(written, trace, trace[k])
    status = jnp.where(fault != 0, fault,
                       jnp.where(act, jnp.int32(STATUS_MAXITER),
                                 jnp.int32(STATUS_CONVERGED)))
    return SolveResult(x, trace, it, status, bad)


@scope("control")
def pcg_tol(
    matvec: MatVec,
    b: Vec,
    psolve: Callable[[Vec], Vec],
    x0: Vec | None = None,
    tol: float = 1e-8,
    max_iters: int = 1000,
    dot: Dot = _default_dot,
    substrate: SolverSubstrate | None = None,
    guard: bool = True,
) -> SolveResult:
    """PCG with relative-tolerance stopping (while_loop).

    The body runs the same folded, substrate-phrased recurrence as
    :func:`pcg` -- with a fused substrate every iteration of the tolerance
    loop is the fused hot path (in-stream denominator, one-pass update,
    p-fold), and the stopping test reuses the ``rr`` the update already
    produced instead of paying a fresh dot.  ``substrate=None`` composes
    the reference substrate from the arguments: identical values, and in
    particular *identical iteration counts*, fused vs reference.

    Batched ``(k, n)`` b: the loop runs until *every* RHS meets the
    tolerance (or max_iters); already-converged RHS keep iterating
    harmlessly while ``iters`` records, per RHS, how many iterations it
    was still active.

    Convergence trace: the while_loop carries a *bounded* residual-norm
    ring of static shape ``(max_iters + 1,)`` (``(max_iters + 1, k)``
    batched) -- slot ``i`` holds the residual norm after iteration ``i``,
    written in place as the loop runs, so tolerance-mode solves return the
    same plottable trace as the fixed-iteration solvers at zero dynamic
    allocation.  Slots past the stopping iteration are filled with the
    final residual norm (``res_norms[-1]`` stays the final residual, and
    ``iters`` marks where the real trace ends).

    Guards (``guard=True``): breakdown/divergence as in :func:`pcg`, plus
    stagnation -- an active lane with no new best residual for
    ``STALL_WINDOW`` iterations stops with ``STATUS_STAGNATED``.  Faulted
    lanes deactivate (the loop moves on without them) and freeze at their
    last finite iterate."""
    sub = substrate if substrate is not None else reference_substrate(
        matvec, psolve, dot
    )
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - sub.matvec(x)
    z = sub.psolve(r)
    rz = sub.dot(r, z)
    bnorm = _norm(sub.dot(b, b))
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)
    p = jnp.zeros_like(b)
    beta = jnp.zeros_like(rz)          # first fold: p = z + 0*0 = z
    r0n = _norm(sub.dot(r, r))
    trace0 = jnp.zeros((max_iters + 1,) + r0n.shape, r0n.dtype).at[0].set(r0n)
    act0 = r0n / bnorm > tol
    it0 = _iters_like(b, 0)

    if not guard:
        def cond(state):
            act, k = state[6], state[8]
            return jnp.any(act) & (k < max_iters)

        def body(state):
            x, r, z, p, rz, beta, act, it, k, trace = state
            it = it + act.astype(jnp.int32)
            p, ap, denom = sub.fold_matvec_dot(z, p, beta)
            alpha = rz / jnp.where(denom == 0, 1.0, denom)
            x, r, z, rr, rz_new = sub.update(alpha, x, r, p, ap)
            beta = rz_new / jnp.where(rz == 0, 1.0, rz)
            rn = _norm(rr)
            trace = trace.at[k + 1].set(rn)
            act = rn / bnorm > tol
            return (x, r, z, p, rz_new, beta, act, it, k + 1, trace)

        x, r, z, p, rz, beta, act, it, k, trace = lax.while_loop(
            cond, body,
            (x, r, z, p, rz, beta, act0, it0, jnp.int32(0), trace0)
        )
        idx = jnp.arange(max_iters + 1)
        written = (idx <= k).reshape((-1,) + (1,) * (trace.ndim - 1))
        trace = jnp.where(written, trace, trace[k])
        return SolveResult(x, trace, it,
                           _iters_like(b, STATUS_UNGUARDED),
                           _iters_like(b, -1))

    def cond(state):
        act, k = state[6], state[8]
        return jnp.any(act) & (k < max_iters)

    def body(state):
        (x, r, z, p, rz, beta, act, it, k, trace, rn_prev, fault, bad,
         best, since) = state
        it = it + act.astype(jnp.int32)
        p2, ap, denom = sub.fold_matvec_dot(z, p, beta)
        alpha = rz / jnp.where(denom == 0, 1.0, denom)
        x2, r2, z2, rr, rz_new = sub.update(alpha, x, r, p2, ap)
        beta2 = rz_new / jnp.where(rz == 0, 1.0, rz)
        rn = _norm(rr)
        sign_bad = (((_sq(denom) < 0) & (_sq(rz) > 0))
                    | (_sq(rz_new) < 0))
        breakdown = (_guard_flags(rn, denom, rz_new)
                     | (_sign_live(rn_prev, r0n) & sign_bad))
        diverged = rn > DIVERGENCE_FACTOR * r0n
        improved = rn < best
        best = jnp.minimum(rn, best)
        since = jnp.where(improved, 0, since + 1)
        stalled = act & (since >= STALL_WINDOW)
        newly = (fault == 0) & (breakdown | diverged | stalled)
        fault = jnp.where(newly, _fault_code(breakdown, diverged, stalled),
                          fault)
        bad = jnp.where(newly, k + 1, bad)
        good = fault == 0
        rn_out = jnp.where(good, rn, rn_prev)
        trace = trace.at[k + 1].set(rn_out)
        act = good & (rn / bnorm > tol)
        return (_sel(good, x2, x), _sel(good, r2, r), _sel(good, z2, z),
                _sel(good, p2, p), _sel(good, rz_new, rz),
                _sel(good, beta2, beta), act, it, k + 1, trace, rn_out,
                fault, bad, best, since)

    init_bad = _guard_flags(r0n, rz) | ~jnp.isfinite(bnorm)
    fault0 = (jnp.where(init_bad, jnp.int32(STATUS_BREAKDOWN), jnp.int32(0))
              + it0)
    bad0 = jnp.where(init_bad, jnp.int32(0), jnp.int32(-1)) + it0
    act0g = (fault0 == 0) & act0
    state = lax.while_loop(
        cond, body,
        (x, r, z, p, rz, beta, act0g, it0, jnp.int32(0), trace0, r0n,
         fault0, bad0, r0n, it0)
    )
    x, act, it, k, trace = state[0], state[6], state[7], state[8], state[9]
    fault, bad = state[11], state[12]
    # fill the unwritten tail with the final residual: res_norms[-1] keeps
    # meaning "final residual" and plots show a flat converged tail
    idx = jnp.arange(max_iters + 1)
    written = (idx <= k).reshape((-1,) + (1,) * (trace.ndim - 1))
    trace = jnp.where(written, trace, trace[k])
    status = jnp.where(fault != 0, fault,
                       jnp.where(act, jnp.int32(STATUS_MAXITER),
                                 jnp.int32(STATUS_CONVERGED)))
    return SolveResult(x, trace, it, status, bad)


@scope("control")
def jacobi(
    matvec: MatVec,
    diag_inv: Vec,
    b: Vec,
    x0: Vec | None = None,
    iters: int = 100,
    dot: Dot = _default_dot,
) -> SolveResult:
    """Weighted Jacobi iteration: x += D^-1 (b - A x).  The paper's simplest
    distributed test case (pure SpMV + axpy, no data dependence).  With a
    ``(k, n)`` b the (n,)-shaped ``diag_inv`` broadcasts over the batch.
    Unguarded (no reduction slots to inspect): status is UNGUARDED."""
    x = jnp.zeros_like(b) if x0 is None else x0
    r0 = b - matvec(x)
    n0 = _norm(dot(r0, r0))

    def step(x, _):
        r = b - matvec(x)
        with scope("update"):
            x = x + diag_inv * r
        return x, _norm(dot(r, r))

    x, norms = lax.scan(step, x, None, length=iters)
    return SolveResult(x, jnp.concatenate([n0[None], norms]),
                       _iters_like(b, iters),
                       _iters_like(b, STATUS_UNGUARDED), _iters_like(b, -1))
