"""Breakdown guards: structured statuses on pathological inputs for every
registry method, bitwise identity of clean guarded solves with the bare
recurrence (written below, with no guard in it), parity of the fixed-count
and tolerance stopping rules, pre-loop fault capture, and the status
surface (status_name/ensure_status, plan.last_status,
engine.last_solve_info)."""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import AzulEngine, SolveSpec
from repro.core import solvers
from repro.core.solvers import (
    STALL_WINDOW,
    STATUS_BREAKDOWN,
    STATUS_CONVERGED,
    STATUS_DIVERGED,
    STATUS_MAXITER,
    STATUS_STAGNATED,
    STATUS_UNGUARDED,
    ensure_status,
    status_name,
)
from repro.core.substrate import reference_substrate
from repro.data.matrices import laplacian_2d

ALL_METHODS = ("cg", "pcg", "pcg_tol", "pcg_pipelined", "pcg_pipelined_tol",
               "jacobi")
GUARDED_METHODS = ALL_METHODS[:-1]
PCG_VARIANTS = ("pcg", "pcg_tol", "pcg_pipelined", "pcg_pipelined_tol")


def _spec_kw(method, budget=40):
    """iters/tol kwargs appropriate to fixed-iteration vs tolerance methods."""
    if method.endswith("_tol"):
        return dict(tol=1e-8, max_iters=budget)
    return dict(iters=budget)


def _solver_kw(method, budget=40):
    if method.endswith("_tol"):
        return dict(tol=1e-10, max_iters=budget)
    return dict(iters=budget)


def _setup(n=10):
    m = laplacian_2d(n)
    a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
    eng = AzulEngine(m, precond="jacobi", dtype=np.float64)
    b = a @ np.random.default_rng(0).standard_normal(m.shape[0])
    return m, a, eng, b


def _lap(n):
    return (np.diag(2.0 * np.ones(n)) - np.diag(np.ones(n - 1), 1)
            - np.diag(np.ones(n - 1), -1))


def _dense_lap(n=32):
    lap = _lap(n)
    mv = lambda x: jnp.asarray(lap) @ x
    b = np.random.default_rng(3).standard_normal(n)
    return mv, jnp.asarray(b)


def _ops(a):
    """matvec and Jacobi psolve of a scipy matrix, for (n,) and (k, n)
    vectors alike."""
    at = jnp.asarray(a.toarray().T)
    dinv = jnp.asarray(1.0 / a.diagonal())
    return (lambda v: v @ at), (lambda r: dinv * r)


def _norm(d):
    rn = jnp.sqrt(d)
    return rn[..., 0] if rn.ndim else rn


def _bare_pcg(sub, b, iters=None, tol=None):
    """Standard PCG on ``sub``'s ops in the solver's order, with no guard
    flag and no freeze select: ``iters`` steps, or -- with ``tol`` -- until
    every RHS is below ``tol * |b|`` (at most ``iters``).  Returns
    (x, residual trace, per-RHS active iterations)."""
    x = jnp.zeros_like(b)
    r = b - sub.matvec(x)
    z = sub.psolve(r)
    rz = sub.dot(r, z)
    r0 = _norm(sub.dot(r, r))
    p = jnp.zeros_like(b)
    beta = jnp.zeros_like(rz)

    def step(x, r, z, p, rz, beta):
        p, ap, denom = sub.fold_matvec_dot(z, p, beta)
        alpha = rz / jnp.where(denom == 0, 1.0, denom)
        x, r, z, rr, rz_new = sub.update(alpha, x, r, p, ap)
        beta = rz_new / jnp.where(rz == 0, 1.0, rz)
        return (x, r, z, p, rz_new, beta), _norm(rr)

    if tol is None:
        (x, *_), norms = lax.scan(lambda c, _: step(*c),
                                  (x, r, z, p, rz, beta), None, length=iters)
        return (x, jnp.concatenate([r0[None], norms]),
                jnp.full(b.shape[:-1], iters, jnp.int32))
    bnorm = _norm(sub.dot(b, b))
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)
    return _bare_tol_loop(step, (x, r, z, p, rz, beta), r0, bnorm, tol,
                          iters, b)


def _bare_tol_loop(step, carry, r0, bnorm, tol, max_iters, b):
    """The tolerance loop around a bare ``step``: the residual ring, the
    active mask and the per-RHS count, with the unwritten tail filled with
    the final residual."""
    trace0 = jnp.zeros((max_iters + 1,) + r0.shape, r0.dtype).at[0].set(r0)

    def cond(state):
        _, act, _, k, _ = state
        return jnp.any(act) & (k < max_iters)

    def body(state):
        carry, act, it, k, trace = state
        it = it + act.astype(jnp.int32)
        carry, rn = step(*carry)
        return carry, rn / bnorm > tol, it, k + 1, trace.at[k + 1].set(rn)

    carry, _, it, k, trace = lax.while_loop(
        cond, body, (carry, r0 / bnorm > tol,
                     jnp.zeros(b.shape[:-1], jnp.int32), jnp.int32(0),
                     trace0))
    written = (jnp.arange(max_iters + 1) <= k).reshape(
        (-1,) + (1,) * (trace.ndim - 1))
    return carry[0], jnp.where(written, trace, trace[k]), it


def _bare_pipelined(sub, b, iters=None, tol=None):
    """Chronopoulos-Gear pipelined PCG on ``sub``'s ops (its stacked
    ``pipe_dots`` and one-pass ``pipe_update``), bare like
    :func:`_bare_pcg`."""
    x = jnp.zeros_like(b)
    r = b - sub.matvec(x)
    u = sub.psolve(r)
    w = sub.matvec(u)
    gd = sub.pipe_dots(r, u, w)
    r0 = _norm(jnp.maximum(gd[2], 0.0))
    m = sub.psolve(w)
    zv = jnp.zeros_like(b)
    one = jnp.ones_like(gd[0])

    def step(x, r, u, w, z, q, s, p, m, gamma, delta, gamma_old, alpha_old,
             k):
        nv = sub.matvec(m)
        beta = jnp.where(k == 0, 0.0,
                         gamma / jnp.where(gamma_old == 0, 1.0, gamma_old))
        den = delta - beta * gamma / jnp.where(alpha_old == 0, 1.0,
                                               alpha_old)
        alpha = gamma / jnp.where(den == 0, 1.0, den)
        x, r, u, w, z, q, s, p = sub.pipe_update(beta, alpha, x, r, u, w, z,
                                                 q, s, p, m, nv)
        gd = sub.pipe_dots(r, u, w)
        m = sub.psolve(w)
        return ((x, r, u, w, z, q, s, p, m, gd[0], gd[1], gamma, alpha,
                 k + 1), _norm(jnp.maximum(gd[2], 0.0)))

    carry = (x, r, u, w, zv, zv, zv, zv, m, gd[0], gd[1], one, one,
             jnp.int32(0))
    if tol is None:
        (x, *_), norms = lax.scan(lambda c, _: step(*c), carry, None,
                                  length=iters)
        return (x, jnp.concatenate([r0[None], norms]),
                jnp.full(b.shape[:-1], iters, jnp.int32))
    bnorm = _norm(jnp.maximum(sub.dot(b, b), 0.0))
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)
    return _bare_tol_loop(step, carry, r0, bnorm, tol, iters, b)


def _guarded_and_bare(method, b, mv, psolve):
    """(guarded result, bare (x, trace, iters)) of one clean solve, both
    compiled whole."""
    tolerance = method.endswith("_tol")
    kw = dict(tol=1e-8, max_iters=200) if tolerance else dict(iters=40)
    if method == "cg":
        psolve = lambda r: r
        guarded = lambda b: solvers.cg(mv, b, **kw)
    else:
        guarded = lambda b: getattr(solvers, method)(mv, b, psolve, **kw)
    bare_fn = _bare_pipelined if "pipelined" in method else _bare_pcg
    tol = kw.get("tol")
    budget = kw.get("max_iters", kw.get("iters"))
    bare = lambda b: bare_fn(reference_substrate(mv, psolve), b,
                             iters=budget, tol=tol)
    return jax.jit(guarded)(b), jax.jit(bare)(b)


# -- status surface units ----------------------------------------------------


def test_status_names_cover_all_codes():
    assert status_name(STATUS_CONVERGED) == "converged"
    assert status_name(STATUS_MAXITER) == "maxiter"
    assert status_name(STATUS_BREAKDOWN) == "breakdown"
    assert status_name(STATUS_DIVERGED) == "diverged"
    assert status_name(STATUS_STAGNATED) == "stagnated"
    assert status_name(STATUS_UNGUARDED) == "unguarded"


def test_ensure_status_normalizes_preguard_results():
    mv, b = _dense_lap(8)
    res = solvers.pcg(mv, b, lambda r: r, iters=5)
    res = res._replace(status=None, bad_iter=None)     # a statusless result
    norm = ensure_status(res, b)
    assert int(norm.status) == STATUS_UNGUARDED
    assert int(norm.bad_iter) == -1


# -- solver-level breakdown inputs, every guarded method ---------------------


@pytest.mark.parametrize("method", PCG_VARIANTS)
def test_indefinite_preconditioner_is_breakdown(method):
    """psolve = -I makes rho = <r, Mr> < 0 on the first update: the guard
    must flag breakdown at iteration 1 and freeze a finite iterate."""
    mv, b = _dense_lap()
    f = getattr(solvers, method)
    res = f(mv, b, lambda r: -r, **_solver_kw(method, 50))
    assert status_name(int(res.status)) == "breakdown"
    assert int(res.bad_iter) == 1
    assert bool(np.isfinite(np.asarray(res.x)).all())


@pytest.mark.parametrize("method", GUARDED_METHODS)
def test_nan_rhs_is_preloop_breakdown(method):
    """NaN in b poisons r0 before the loop -- without init-time guards the
    tolerance methods would skip the loop (NaN > tol is False) and falsely
    report converged.  Must be breakdown with bad_iter 0."""
    mv, b = _dense_lap()
    bnan = b.at[0].set(np.nan)
    f = getattr(solvers, method)
    args = (mv, bnan) if method == "cg" else (mv, bnan, lambda r: r)
    res = f(*args, **_solver_kw(method, 50))
    assert status_name(int(res.status)) == "breakdown"
    assert int(res.bad_iter) == 0


def test_singular_operator_tol_flags_fault():
    """A singular diagonal A with b having a nullspace component cannot
    converge: pcg_tol sees the residual floor and flags rather than
    spinning to max_iters claiming progress."""
    n = 32
    d = np.ones(n)
    d[0] = 0.0
    mv = lambda x: jnp.asarray(d) * x
    b = jnp.asarray(np.ones(n))
    res = solvers.pcg_tol(mv, b, lambda r: r, tol=1e-12, max_iters=300)
    assert status_name(int(res.status)) in ("diverged", "stagnated",
                                            "breakdown")
    assert int(res.bad_iter) >= 0
    res = solvers.pcg_pipelined_tol(mv, b, lambda r: r, tol=1e-12,
                                    max_iters=400)
    assert status_name(int(res.status)) in ("diverged", "stagnated",
                                            "breakdown")


def test_nonsymmetric_operator_tol_stagnates():
    """CG on a skew-dominated (non-SPD) operator makes no progress; the
    stall detector fires after STALL_WINDOW iterations without a new best
    residual instead of burning the whole budget."""
    n = 32
    S = np.zeros((n, n))
    for i in range(n - 1):
        S[i, i + 1] = 10.0
        S[i + 1, i] = -10.0
    J = np.eye(n) + S
    mv = lambda x: jnp.asarray(J) @ x
    b = jnp.asarray(np.random.default_rng(0).standard_normal(n))
    res = solvers.pcg_tol(mv, b, lambda r: r, tol=1e-10, max_iters=300)
    assert status_name(int(res.status)) in ("stagnated", "diverged",
                                            "breakdown")
    assert int(res.iters) < 300  # flagged before exhausting the budget


# -- engine-level: zero RHS, clean statuses, bitwise identity ----------------


@pytest.mark.parametrize("method", ALL_METHODS)
def test_zero_rhs_is_clean(method):
    """b = 0 must not trip any guard: x stays finite (zero), tolerance
    methods report converged, fixed-iteration methods maxiter, jacobi
    unguarded."""
    m, _, eng, _ = _setup()
    p = eng.plan(SolveSpec(method=method, **_spec_kw(method)))
    x, _ = p(np.zeros(m.shape[0]))
    expect = ("converged" if method.endswith("_tol")
              else "unguarded" if method == "jacobi" else "maxiter")
    assert p.last_status_names == expect
    assert int(np.asarray(p.last_bad_iter)) == -1
    assert bool(np.isfinite(np.asarray(x)).all())


@pytest.mark.parametrize("method", GUARDED_METHODS)
def test_clean_solve_guarded_bitwise_identical_to_unguarded(method):
    """The freeze-on-fault guards are jnp.where selects on an all-true mask
    for healthy solves: the guarded iterate and trace must be BITWISE those
    of the bare recurrence on the same reference ops, not merely close."""
    _, a, _, b = _setup()
    res, (xu, nu, itu) = _guarded_and_bare(method, jnp.asarray(b), *_ops(a))
    assert np.asarray(res.x).tobytes() == np.asarray(xu).tobytes()
    assert np.asarray(res.res_norms).tobytes() == np.asarray(nu).tobytes()
    assert np.array_equal(np.asarray(res.iters), np.asarray(itu))
    assert int(res.bad_iter) == -1


def test_clean_batched_solve_bitwise_identical_and_statused():
    _, a, eng, b = _setup()
    B = np.stack([b, 2.0 * b, a @ np.ones(a.shape[0])])
    res, (xu, nu, itu) = _guarded_and_bare("pcg_tol", jnp.asarray(B),
                                           *_ops(a))
    assert np.asarray(res.x).tobytes() == np.asarray(xu).tobytes()
    assert np.asarray(res.res_norms).tobytes() == np.asarray(nu).tobytes()
    assert np.array_equal(np.asarray(res.iters), np.asarray(itu))
    assert [status_name(int(c)) for c in np.asarray(res.status)] == \
        ["converged"] * 3
    # the engine's batched plan reports the same per-lane surface
    pg = eng.plan(SolveSpec(method="pcg_tol", tol=1e-8, max_iters=200,
                            batch=3))
    pg(B)
    assert list(pg.last_status_names) == ["converged"] * 3
    assert [int(v) for v in np.asarray(pg.last_bad_iter)] == [-1] * 3


FIXED_K = 25                    # below STALL_WINDOW: no stall test differs


@pytest.mark.parametrize("lanes", ("single", "batch_nan_lane"))
@pytest.mark.parametrize("fixed, tolerance", (
    ("pcg", "pcg_tol"), ("pcg_pipelined", "pcg_pipelined_tol")))
def test_fixed_count_equals_tolerance_stopped_at_same_count(fixed, tolerance,
                                                            lanes):
    """A fixed count of k and the tolerance spelling held to k iterations
    (``tol=0``) are one loop body with two stopping rules: bitwise the same
    x, trace, status and bad_iter, and the same iters on healthy lanes.  A
    lane faulted before the loop counts k in fixed mode and its 0 active
    iterations in tolerance mode."""
    assert FIXED_K < STALL_WINDOW
    _, b = _dense_lap()
    if lanes == "batch_nan_lane":
        b = jnp.stack([b, 2.0 * b, b.at[5].set(np.nan)])
    lap = jnp.asarray(_lap(b.shape[-1]))
    mvb = lambda v: v @ lap            # symmetric: (n,) and (k, n) alike
    psolve = lambda r: 0.5 * r
    rf = jax.jit(lambda b: getattr(solvers, fixed)(
        mvb, b, psolve, iters=FIXED_K))(b)
    rt = jax.jit(lambda b: getattr(solvers, tolerance)(
        mvb, b, psolve, tol=0.0, max_iters=FIXED_K))(b)
    for f in ("x", "res_norms", "status", "bad_iter"):
        assert np.asarray(getattr(rf, f)).tobytes() == \
            np.asarray(getattr(rt, f)).tobytes(), f
    it_f, it_t = np.asarray(rf.iters), np.asarray(rt.iters)
    assert (it_f == FIXED_K).all()
    if lanes == "single":
        assert int(rf.status) == STATUS_MAXITER and int(it_t) == FIXED_K
    else:
        assert list(it_t) == [FIXED_K, FIXED_K, 0]
        assert [int(c) for c in np.asarray(rf.status)] == \
            [STATUS_MAXITER, STATUS_MAXITER, STATUS_BREAKDOWN]
        assert [int(c) for c in np.asarray(rf.bad_iter)] == [-1, -1, 0]


def test_guarded_clean_statuses_and_info_surface():
    """Healthy engine solves: correct terminal status per method family and
    a populated engine.last_solve_info mirror."""
    _, _, eng, b = _setup()
    p = eng.plan(SolveSpec(method="pcg_tol", tol=1e-8, max_iters=200))
    x, norms = p(b)
    assert p.last_status_names == "converged"
    info = eng.last_solve_info
    assert info["status_names"] == "converged"
    assert int(np.asarray(info["bad_iter"])) == -1
    assert int(np.asarray(info["status"])) == STATUS_CONVERGED
    assert info["iters"] >= 1
    # fixed-iteration budget exhausted is maxiter, not a fault
    p2 = eng.plan(SolveSpec(method="pcg", iters=3))
    p2(b)
    assert p2.last_status_names == "maxiter"
    assert int(np.asarray(p2.last_bad_iter)) == -1


# -- injectable plans: corrupted operands hit the guards ---------------------


def test_injectable_nan_vals_is_preloop_breakdown():
    """NaN injected into the value operand poisons the initial residual:
    the init-time guard must catch it (bad_iter 0), not report converged."""
    _, _, eng, b = _setup()
    p = eng.plan(SolveSpec(method="pcg_tol", tol=1e-8, max_iters=200,
                           injectable=True))
    vbad = eng.vals_template()
    vbad.reshape(-1)[np.flatnonzero(vbad.reshape(-1) != 0)[0]] = np.nan
    x, _ = p(b, vals=vbad)
    assert p.last_status_names == "breakdown"
    assert int(np.asarray(p.last_bad_iter)) == 0
    # clean operand through the SAME program stays healthy
    p(b, vals=eng.vals_template())
    assert p.last_status_names == "converged"


@pytest.mark.parametrize("method", ("pcg_tol", "pcg_pipelined_tol"))
def test_injectable_indefinite_operator_is_breakdown(method):
    """Negating one diagonal entry makes A indefinite: pAp goes negative
    within a few iterations and the guard freezes a finite iterate."""
    _, _, eng, b = _setup()
    p = eng.plan(SolveSpec(method=method, tol=1e-8, max_iters=200,
                           injectable=True))
    vbad = eng.vals_template()
    cols = eng.cols_template()
    slot = np.flatnonzero(cols[1] == 1)[0]
    vbad[1, slot] *= -1000.0
    x, _ = p(b, vals=vbad)
    assert p.last_status_names == "breakdown"
    assert int(np.asarray(p.last_bad_iter)) >= 0
    assert bool(np.isfinite(np.asarray(x)).all())
