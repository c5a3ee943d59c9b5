"""Plan/execute API: spec canonicalization, PlanCache hit/miss, the
zero-recompile execution contract (including SolveServer steady state),
the deprecated ``engine.solve(**knobs)`` shim, the bounded tolerance
convergence trace, registry extensibility, and stage-in without host
zero-fills (the resident zero guess and the direct transfer give the bits
the padded staging gave, locally and on the 2x2 halo mesh)."""

import os
import subprocess
import sys
import warnings

import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import (
    AzulEngine,
    SolveSpec,
    SolverDef,
    register_solver,
    solver_names,
    precond_names,
)
from repro.core.plan import _reset_deprecation_warnings
from repro.core.registry import unregister_solver
from repro.core.stencil import lap3d_stencil
from repro.data.matrices import laplacian_2d
from repro.serve import SolveServer


def _setup(n=10, precond="jacobi"):
    m = laplacian_2d(n)
    a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
    eng = AzulEngine(m, precond=precond, dtype=np.float64)
    b = a @ np.random.default_rng(0).standard_normal(m.shape[0])
    return m, a, eng, b


# -- PlanCache: spec-keyed hit/miss ------------------------------------------


def test_plan_cache_spec_keyed_hit_miss():
    _, _, eng, b = _setup()
    p1 = eng.plan(SolveSpec(method="pcg", iters=30))
    assert eng.plans.misses == 1 and eng.plans.hits == 0
    # equal configuration -> the SAME plan object, however it is spelled
    assert eng.plan(SolveSpec(method="pcg", iters=30)) is p1
    assert eng.plan(method="pcg", iters=30) is p1
    assert eng.plan(SolveSpec(method="pcg", iters=30, precond="jacobi")) is p1
    assert eng.plans.hits == 3
    # different configuration -> a different plan
    p2 = eng.plan(SolveSpec(method="pcg", iters=31))
    assert p2 is not p1
    p3 = eng.plan(SolveSpec(method="cg", iters=30))
    assert p3 is not p1
    assert len(eng.plans) == 3
    # canonical spec membership (layout/reorder/format resolved alike)
    assert SolveSpec(method="pcg", precond="jacobi", iters=30,
                     fused=True, layout="dense", reorder="none",
                     format="ell") in eng.plans


def test_tol_changes_never_recompile_fixed_iteration_plans():
    """The PR 3 cache-key special case, now structural: canonicalization
    nulls tol/max_iters on fixed-iteration methods, so a tol change can
    never lower (or recompile) a bit-identical pcg plan."""
    _, _, eng, b = _setup()
    p = eng.plan(SolveSpec(method="pcg", iters=25, tol=1e-3, max_iters=99))
    assert p.spec.tol is None and p.spec.max_iters is None
    for tol in (1e-2, 1e-8, 0.5):
        assert eng.plan(SolveSpec(method="pcg", iters=25, tol=tol)) is p
    assert len(eng.plans) == 1
    # tolerance methods DO key on (tol, max_iters) -- distinct programs
    t1 = eng.plan(SolveSpec(method="pcg_tol", tol=1e-6, max_iters=50))
    t2 = eng.plan(SolveSpec(method="pcg_tol", tol=1e-8, max_iters=50))
    t3 = eng.plan(SolveSpec(method="pcg_tol", tol=1e-6, max_iters=60))
    assert len({id(t1), id(t2), id(t3)}) == 3
    # ... and iters folds into max_iters (one budget field)
    t4 = eng.plan(SolveSpec(method="pcg_tol", tol=1e-6, iters=50))
    assert t4 is t1


def test_spec_validation():
    _, _, eng, _ = _setup(precond="jacobi")
    with pytest.raises(ValueError, match="unknown solver"):
        eng.plan(SolveSpec(method="sor"))
    with pytest.raises(ValueError, match="engine precond"):
        eng.plan(SolveSpec(method="pcg", precond="block_ic0"))
    with pytest.raises(ValueError, match="batch"):
        eng.plan(SolveSpec(method="pcg", batch=0))
    with pytest.raises(ValueError, match="fused"):
        eng.plan(SolveSpec(method="pcg", fused="maybe"))
    # "none" aliases to the registry's canonical "identity"
    m = laplacian_2d(8)
    e2 = AzulEngine(m, precond="none", dtype=np.float64)
    assert e2.plan(SolveSpec(method="pcg")).spec.precond == "identity"


# -- the zero-recompile contract ---------------------------------------------


def test_one_trace_per_plan_across_100_executions():
    _, _, eng, b = _setup()
    plan = eng.plan(SolveSpec(method="pcg", iters=5))
    x0, n0 = plan(b)
    for _ in range(99):
        x, norms = plan(b)
    assert plan.executions == 100
    assert plan.traces == 1, "plan retraced -- the compile-once contract broke"
    np.testing.assert_array_equal(x, x0)


def test_plans_are_shape_specialized():
    _, _, eng, b = _setup()
    plan = eng.plan(SolveSpec(method="pcg", iters=5, batch=4))
    with pytest.raises(ValueError, match="shape-specialized"):
        plan(b)                                  # (n,) into a batch-4 plan
    with pytest.raises(ValueError, match="shape-specialized"):
        plan(np.stack([b, b]))                   # (2, n) into a batch-4 plan
    x, norms = plan(np.stack([b] * 4))
    assert x.shape == (4, eng.n) and norms.shape == (6, 4)
    # shared (n,) x0 broadcasts over the batch
    x2, _ = plan(np.stack([b] * 4), x0=np.zeros(eng.n))
    np.testing.assert_array_equal(x2, x)


def test_solve_server_steady_state_zero_recompiles():
    """100 server steps across two batch buckets: one plan per bucket,
    each traced exactly once -- dispatch resolves at plan construction,
    never per step."""
    _, a, eng, _ = _setup()
    srv = SolveServer(eng, max_batch=4,
                      spec=SolveSpec(method="pcg", iters=5))
    rng = np.random.default_rng(3)
    xt = rng.standard_normal((100, eng.n))
    done = {}
    for i in range(80):                      # bucket k=1, 80 steps
        srv.submit(a @ xt[i])
        done.update(srv.step())
    for i in range(80, 100, 4):              # bucket k=4, 5 steps
        for j in range(4):
            srv.submit(a @ xt[i + j])
        done.update(srv.step())
    assert len(done) == 100
    assert srv.stats["batches"] == 85
    assert srv.stats["plans"] == 2           # one plan per bucket, total
    for k_pad, plan in srv._plans.items():
        assert plan.traces == 1, f"bucket {k_pad} retraced"
    assert srv._plans[1].executions == 80
    assert srv._plans[4].executions == 5


def test_solve_server_tolerance_outcomes_carry_trace():
    _, a, eng, _ = _setup()
    srv = SolveServer(eng, max_batch=4,
                      spec=SolveSpec(method="pcg_tol", tol=1e-9, max_iters=60))
    rng = np.random.default_rng(4)
    xt = rng.standard_normal((3, eng.n))
    ids = [srv.submit(a @ xt[i]) for i in range(3)]
    done = srv.drain()
    # the batch loop runs until EVERY RHS converges; the ring tail-fills
    # from that global stopping iteration
    kmax = max(done[rid].iters for rid in ids)
    for i, rid in enumerate(ids):
        out = done[rid]
        np.testing.assert_allclose(out.x, xt[i], atol=1e-6)
        assert 0 < out.iters <= 60
        # the bounded ring: full (max_iters + 1,) trace, tail-filled
        assert out.res_norms.shape == (61,)
        assert np.all(out.res_norms[kmax:] == out.res_norms[kmax])


# -- deprecation shims --------------------------------------------------------


def test_solve_shim_warns_once_and_is_bit_identical():
    _, _, eng, b = _setup()
    plan = eng.plan(SolveSpec(method="pcg_tol", tol=1e-8, max_iters=80))
    xp, np_ = plan(b)
    _reset_deprecation_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        xs, ns = eng.solve(b, method="pcg_tol", tol=1e-8, max_iters=80)
        xs2, ns2 = eng.solve(b, method="pcg_tol", tol=1e-8, max_iters=80)
    deps = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(deps) == 1, "legacy solve must warn exactly once per process"
    assert "SolveSpec" in str(deps[0].message)
    # bit-identical: the shim hits the same cached plan and program
    np.testing.assert_array_equal(xs, xp)
    np.testing.assert_array_equal(ns, np_)
    np.testing.assert_array_equal(xs2, xp)
    assert len(eng.plans) == 1


def test_solve_shim_batched_routes_through_batch_plan():
    _, a, eng, _ = _setup()
    rng = np.random.default_rng(5)
    B = rng.standard_normal((3, eng.n)) @ a.T
    xs, ns = eng.solve(B, method="pcg", iters=20)
    # membership takes the CANONICAL spec (precond resolved, fused bool)
    canonical = SolveSpec(method="pcg", precond="jacobi", iters=20,
                          batch=3, fused=True, layout="dense",
                          reorder="none", format="ell")
    assert canonical in eng.plans
    plan = eng.plan(SolveSpec(method="pcg", iters=20, batch=3))
    assert plan.executions == 1              # the shim's execution
    xp, npn = plan(B)
    np.testing.assert_array_equal(xs, xp)


# -- bounded tolerance trace (plan output) -----------------------------------


def test_pcg_tol_plan_returns_bounded_trace():
    _, _, eng, b = _setup()
    plan = eng.plan(SolveSpec(method="pcg_tol", tol=1e-9, max_iters=70))
    x, norms = plan(b)
    it = int(plan.last_iters)
    assert 0 < it < 70
    assert norms.shape == (71,)
    assert norms[0] == pytest.approx(np.linalg.norm(b))
    # real trace decreases to tolerance; tail is the final residual
    assert norms[it] < 1e-8 * np.linalg.norm(b)
    assert np.all(norms[it:] == norms[it])
    assert norms[-1] == norms[it]


def test_pcg_tol_batched_trace_per_rhs():
    _, a, eng, _ = _setup()
    rng = np.random.default_rng(7)
    B = np.stack([a @ rng.standard_normal(eng.n), np.zeros(eng.n)])
    plan = eng.plan(SolveSpec(method="pcg_tol", tol=1e-9, max_iters=80,
                              batch=2))
    x, norms = plan(B)
    assert norms.shape == (81, 2)
    its = np.asarray(plan.last_iters)
    assert its[1] == 0 and 0 < its[0] < 80
    assert np.all(norms[:, 1] == 0.0)        # zero RHS: zero residual ring


# -- registry extensibility ---------------------------------------------------


def test_registry_lists_builtins():
    assert {"cg", "pcg", "pcg_pipelined", "pcg_pipelined_tol", "pcg_tol",
            "jacobi"} <= set(solver_names())
    assert {"identity", "jacobi", "block_ic0"} <= set(precond_names())


def test_register_custom_solver_runs_through_plan():
    """Adding a method is a registry entry + the iteration it runs: the
    engine lowers it through the same generic path (no engine edits)."""
    import jax.numpy as jnp
    from jax import lax

    from repro.core.solvers import SolveResult

    def run_richardson(ctx, b, x0):
        omega = 0.8
        r0 = b - ctx.matvec(x0)
        n0 = jnp.sqrt(jnp.sum(r0 * r0))

        def step(x, _):
            r = b - ctx.matvec(x)
            x = x + omega * ctx.psolve(r)
            return x, jnp.sqrt(jnp.sum(r * r))

        x, norms = lax.scan(step, x0, None, length=ctx.iters)
        return SolveResult(x, jnp.concatenate([n0[None], norms]),
                           jnp.full(b.shape[:-1], ctx.iters, jnp.int32))

    register_solver(SolverDef(name="_test_richardson", run=run_richardson))
    try:
        _, _, eng, b = _setup()
        plan = eng.plan(SolveSpec(method="_test_richardson", iters=300))
        assert plan.info["substrate"] == "reference"  # registers no fused caps
        x, norms = plan(b)
        assert norms.shape == (301,)
        assert norms[-1] < 1e-6 * norms[0]
        assert plan.traces == 1
    finally:
        unregister_solver("_test_richardson")
    with pytest.raises(ValueError, match="unknown solver"):
        eng.plan(SolveSpec(method="_test_richardson"))


# -- stage-in without host zero-fills -----------------------------------------
#
# A call without x0 passes the plan's resident zero guess, and a vector whose
# layout is the identity goes to the device without a padded host copy.  The
# reference is the staging every call made before: both b and a zero x0
# permuted and copied into zero-filled padded host buffers.


def _staged_as_before(eng, v):
    v = np.asarray(v)
    if eng._row_perm is not None:
        v = v[..., eng._row_perm]
    out = np.zeros(v.shape[:-1] + (eng.n_pad,), eng.dtype)
    if eng._pad2g is not None:
        valid = eng._pad2g < eng.n
        out[..., valid] = v[..., eng._pad2g[valid]]
    else:
        out[..., : eng.n] = v
    if eng.mesh is None:
        import jax.numpy as jnp

        return jnp.asarray(out)
    return eng._put(out, eng._bvec_spec if v.ndim == 2 else eng._vec_spec)


def _run_as_before(plan, b):
    """(x, norms, iters, status, bad_iter) of ``plan`` on ``b`` from a
    zero guess, staged as every call staged them before."""
    eng = plan.engine
    out = plan.compile()(_staged_as_before(eng, b),
                         _staged_as_before(eng, np.zeros(b.shape)))
    x, *rest = (np.asarray(o) for o in out)
    return (eng.from_device_vec(x), *rest)


def _run(plan, b, **kw):
    x, norms = plan(b, **kw)
    return x, norms, plan.last_iters, plan.last_status, plan.last_bad_iter


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


@pytest.mark.parametrize("matrix, engine_kw, batch, b_dtype", [
    ("lap2d_16", dict(dtype=np.float64), None, np.float64),   # direct
    ("lap2d_16", dict(dtype=np.float64), 3, np.float64),      # direct, batch
    ("lap2d_16", dict(dtype=np.float32), None, np.float64),   # one astype
    ("lap2d_16", dict(dtype=np.float64, reorder="rcm"), None, np.float64),
    ("lap2d_16", dict(dtype=np.float64, reorder="rcm"), 3, np.float64),
    ("lap2d_15", dict(dtype=np.float32), None, np.float64),   # n_pad > n
    ("lap3d_8", dict(dtype=np.float32), None, np.float32),    # stencil
], ids=["single", "batch", "astype", "rcm", "rcm-batch", "padded",
        "stencil"])
def test_zero_guess_and_direct_transfer_match_padded_staging(
        matrix, engine_kw, batch, b_dtype):
    kind, side = matrix.rsplit("_", 1)
    a = (laplacian_2d(int(side)) if kind == "lap2d"
         else lap3d_stencil(int(side)))
    eng = AzulEngine(a, precond="jacobi", **engine_kw)
    plan = eng.plan(SolveSpec(method="pcg_tol", tol=1e-6, max_iters=300,
                              batch=batch))
    shape = (eng.n,) if batch is None else (batch, eng.n)
    b = np.random.default_rng(7).standard_normal(shape).astype(b_dtype)
    resident = _run(plan, b)
    explicit = _run(plan, b, x0=np.zeros(shape))
    before = _run_as_before(plan, b)
    for got, exp, ref in zip(resident, explicit, before):
        assert _same_bits(got, exp) and _same_bits(got, ref)
    assert np.all(np.asarray(plan.last_status) == 0)      # converged


def test_resident_zero_guess_stays_zero_and_b_is_not_kept():
    eng = AzulEngine(laplacian_2d(16), precond="jacobi", dtype=np.float64)
    plan = eng.plan(SolveSpec(method="pcg_tol", tol=1e-6, max_iters=300))
    rng = np.random.default_rng(3)
    b = rng.standard_normal(eng.n)
    keep = b.copy()
    x1, _ = plan(b)
    x1_bits = x1.copy()
    plan(keep, x0=x1)                        # a warm start in between
    plan(rng.standard_normal(eng.n))
    b[:] = 7.0                               # the caller reuses its buffer
    x2, _ = plan(keep)
    assert _same_bits(x2, x1_bits) and _same_bits(x1, x1_bits)
    zero = np.asarray(plan._zero_x0)
    assert zero.shape == (eng.n_pad,) and not zero.any()


_DIST_STAGE_SCRIPT = """
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
from repro import obs
from repro.core import AzulEngine, SolveSpec
from repro.data.matrices import laplacian_2d
from repro.launch.mesh import make_mesh
from test_plan import _run, _run_as_before, _same_bits

eng = AzulEngine(laplacian_2d(16), mesh=make_mesh((2, 2), ("data", "model")),
                 precond="jacobi", dtype=np.float64)
staged = obs.REGISTRY.get("repro_solve_staged_copies_total")
for batch in (None, 3):
    plan = eng.plan(SolveSpec(method="pcg_tol", tol=1e-6, max_iters=300,
                              layout="halo", batch=batch))
    assert plan.spec.layout == "halo"
    shape = (eng.n,) if batch is None else (batch, eng.n)
    b = np.random.default_rng(5).standard_normal(shape)
    resident = _run(plan, b)
    explicit = _run(plan, b, x0=np.zeros(shape))
    before = _run_as_before(plan, b)
    for got, exp, ref in zip(resident, explicit, before):
        assert _same_bits(got, exp) and _same_bits(got, ref), batch
    assert not np.asarray(plan._zero_x0).any()
# the halo layout of this grid is the identity: nothing was staged
assert eng._pad2g is None and eng.n_pad == eng.n
assert staged.value(phase="in") == staged.value(phase="out") == 0
print("STAGE_DIST_OK")
"""


@pytest.mark.dist
def test_zero_guess_and_direct_transfer_match_padded_staging_2x2_halo():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join(["src", "tests"])
    r = subprocess.run(
        [sys.executable, "-c", _DIST_STAGE_SCRIPT], capture_output=True,
        text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)), timeout=560)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert "STAGE_DIST_OK" in r.stdout
