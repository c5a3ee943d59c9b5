"""HPCG's multigrid on the stored path (``repro.core.multigrid``) against
its float64 reference (``repro.core.mg_ref``), at small sizes on the CPU.

1. **The generator** is HPCG's: n and nnz = (3nx-2)(3ny-2)(3nz-2), the
   f2c maps, b = A 1 = 27 - nnz_i.
2. **The colouring** is first fit in row order: 8 colours on a 27-point
   grid, and no two coupled rows share one.
3. **One V-cycle** matches the reference in colour order, and M^-1 is
   symmetric.
4. **MG-PCG** takes the reference's iterations; Jacobi in its place, or a
   V-cycle without its post-smoothing, does not.
5. **The engine** refuses the hierarchy on a mesh, ``precond="mg"``
   without one, and counts set-up and V-cycles in ``repro.obs``.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import AzulEngine, SolveSpec, mg_ref, multigrid
from repro.core.multigrid import MGHierarchy
from repro.core.stencil import lap3d_stencil
from repro.data.matrices import hpcg_matrix, hpcg_problem, laplacian_3d
from repro.obs.scopes import parse_hlo

SPEC = SolveSpec(method="pcg_tol", tol=1e-5, max_iters=200)


def _scipy(a):
    return sp.csr_matrix((a.data, a.indices, a.indptr), shape=a.shape)


@pytest.fixture(autouse=True)
def own_scopes(monkeypatch):
    """Keep this module's plans out of the process-wide scope map, where
    their instruction names would collide with other tests' plans."""
    from repro.core import plan as plan_module
    from repro.obs.scopes import ScopeMap

    monkeypatch.setattr(plan_module, "_SCOPES", ScopeMap())


@pytest.fixture(scope="module")
def h16():
    return hpcg_problem(16, 16, 16)


# -- 1. the generator -----------------------------------------------------------


@pytest.mark.parametrize("grid", [(16, 16, 16), (16, 24, 32)])
def test_generator_is_hpcgs(grid):
    nx, ny, nz = grid
    h = hpcg_problem(nx, ny, nz)
    a = _scipy(h.levels[0])
    assert a.shape == (nx * ny * nz,) * 2
    assert a.nnz == (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)
    assert abs(a - mg_ref.operator(nx, ny, nz)).max() == 0
    assert np.all(a.diagonal() == 26.0)
    assert set(np.unique(a.data)) == {-1.0, 26.0}
    assert abs(a - a.T).max() == 0
    # b = A 1: 27 - nnz_i, so 0 inside the grid
    b = a @ np.ones(a.shape[0])
    np.testing.assert_array_equal(b, 27 - np.diff(a.indptr))
    assert b.reshape(nz, ny, nx)[1:-1, 1:-1, 1:-1].max() == 0
    # each coarse level halves every extent; f2c: (i, j, k) -> (2i, 2j, 2k)
    for lv, f in enumerate(h.f2c):
        cx, cy, cz = nx >> (lv + 1), ny >> (lv + 1), nz >> (lv + 1)
        fx, fy = nx >> lv, ny >> lv
        coarse = _scipy(h.levels[lv + 1])
        assert abs(coarse - mg_ref.operator(cx, cy, cz)).max() == 0
        k, j, i = np.unravel_index(np.arange(cx * cy * cz), (cz, cy, cx))
        np.testing.assert_array_equal(f, 2 * k * fx * fy + 2 * j * fx + 2 * i)


def test_generator_refuses_a_grid_that_does_not_halve():
    with pytest.raises(ValueError, match="multiple of 8"):
        hpcg_problem(12, 16, 16)


# -- 2. the colouring ---------------------------------------------------------


@pytest.mark.parametrize("grid", [(16, 16, 16), (8, 12, 6), (2, 2, 2)])
def test_greedy_colouring(grid):
    a = hpcg_matrix(*grid)
    c = multigrid.greedy_colours(a)
    assert c.max() + 1 == 8
    rows = np.repeat(np.arange(a.shape[0]), np.diff(a.indptr))
    off = rows != a.indices
    assert not np.any(c[rows[off]] == c[a.indices[off]])
    np.testing.assert_array_equal(c, mg_ref.colours(_scipy(a)))


def test_colouring_refuses_an_unsymmetric_pattern():
    from repro.core.formats import csr_from_scipy

    a = csr_from_scipy(sp.csr_matrix(np.array([[1.0, 1.0], [0.0, 1.0]])))
    with pytest.raises(ValueError, match="symmetric"):
        multigrid.greedy_colours(a)


# -- 3. one V-cycle -----------------------------------------------------------


def _vcycle(h, dtype, r):
    ops = multigrid.build(h, dtype)
    return np.asarray(jax.jit(lambda v: multigrid.vcycle(ops, v))(
        jnp.asarray(r, dtype)), np.float64)


def test_one_vcycle_matches_the_reference_in_colour_order(h16):
    r = np.random.default_rng(0).standard_normal(h16.shape[0])
    lvls, f2c = mg_ref.levels(16, 16, 16, order="colour")
    want = mg_ref.vcycle(lvls, f2c, r)
    # float32: 112 dependent colour steps, each rounding at ~6e-8, through
    # a contracting smoother; it measures ~1e-7, so 1e-5 is a hundredfold
    # margin that an order off by one colour step (~1e-1) cannot meet
    got = _vcycle(h16, np.float32, r)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-5
    # the same program in float64 agrees to rounding
    got64 = _vcycle(h16, np.float64, r)
    assert np.linalg.norm(got64 - want) / np.linalg.norm(want) < 1e-13
    # HPCG's own order is a different smoother: the comparison can tell
    lex = mg_ref.vcycle(*mg_ref.levels(16, 16, 16, order="lexicographic"), r)
    assert np.linalg.norm(got - lex) / np.linalg.norm(lex) > 1e-3


def test_vcycle_is_symmetric(h16):
    rng = np.random.default_rng(1)
    r, s = rng.standard_normal((2, h16.shape[0]))
    ops = multigrid.build(h16, np.float64)
    m = jax.jit(lambda v: multigrid.vcycle(ops, v))
    lhs, rhs = float(s @ np.asarray(m(r))), float(r @ np.asarray(m(s)))
    assert abs(lhs - rhs) <= 1e-12 * abs(lhs)


# -- 4. MG-PCG's iterations ---------------------------------------------------


def _iters(engine, b):
    plan = engine.plan(SPEC)
    x, _ = plan(b.astype(np.float32))
    assert plan.last_status_names == "converged"
    return int(plan.last_iters)


def _matches_reference(iters, grid):
    """The iteration test: the program's count within one of the float64
    reference's in colour order."""
    _, want, _ = mg_ref.pcg(*mg_ref.levels(*grid, order="colour"),
                            _b(grid), rtol=SPEC.tol)
    return abs(iters - want) <= 1


def _b(grid):
    return _scipy(hpcg_matrix(*grid)) @ np.ones(int(np.prod(grid)))


@pytest.mark.parametrize("g", [16, 32])
def test_mg_pcg_takes_the_reference_iterations(g):
    grid = (g, g, g)
    eng = AzulEngine(hpcg_problem(*grid), precond="mg", dtype=np.float32)
    assert eng.substrate_kind("pcg_tol") == "reference"
    assert _matches_reference(_iters(eng, _b(grid)), grid)


def test_jacobi_in_place_of_mg_fails_the_iteration_test(h16):
    eng = AzulEngine(h16, precond="jacobi", dtype=np.float32)
    assert not _matches_reference(_iters(eng, _b((16, 16, 16))), (16,) * 3)


def test_a_vcycle_without_post_smoothing_fails_the_iteration_test(
        h16, monkeypatch):
    # the V-cycle unrolls at trace time: of each V-cycle's 2L - 1 SymGS
    # calls the last L - 1 are the post-smoothings; drop those
    calls, n_levels = [0], len(h16.levels)
    real = multigrid.symgs

    def pre_only(lv, r, x):
        k = calls[0] % (2 * n_levels - 1)
        calls[0] += 1
        return real(lv, r, x) if k < n_levels else x

    monkeypatch.setattr(multigrid, "symgs", pre_only)
    eng = AzulEngine(h16, precond="mg", dtype=np.float32)
    assert not _matches_reference(_iters(eng, _b((16, 16, 16))), (16,) * 3)
    assert calls[0] > 0


def test_batched_and_padded_vectors(h16):
    eng = AzulEngine(h16, precond="mg", dtype=np.float64, row_pad=3000)
    assert eng.n_pad > eng.n
    b = _b((16, 16, 16))
    bb = np.stack([b, 2.0 * b])
    plan = eng.plan(SolveSpec(method="pcg_tol", tol=1e-8, max_iters=200,
                              batch=2))
    x, _ = plan(bb)
    np.testing.assert_allclose(x, [[1.0], [2.0]] * np.ones(eng.n), atol=1e-6)
    one = eng.plan(SolveSpec(method="pcg_tol", tol=1e-8, max_iters=200))
    xs, _ = one(b)
    np.testing.assert_allclose(x[0], xs, rtol=0, atol=1e-12)


# -- 5. the engine ------------------------------------------------------------


def test_engine_refuses_the_hierarchy_on_a_mesh(h16):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    with pytest.raises(ValueError, match="one device"):
        AzulEngine(h16, mesh=mesh, precond="mg")
    with pytest.raises(ValueError, match="one device"):
        AzulEngine(h16, mesh=mesh, precond="jacobi")


@pytest.mark.parametrize("op", ["csr", "stencil"])
def test_mg_needs_a_hierarchy(op):
    a = laplacian_3d(8) if op == "csr" else lap3d_stencil(8)
    with pytest.raises(ValueError, match="multigrid levels"):
        AzulEngine(a, precond="mg")
    eng = AzulEngine(a, precond="jacobi")
    with pytest.raises(ValueError, match="spec precond 'mg'"):
        eng.plan(SolveSpec(method="pcg_tol", precond="mg"))


def test_engine_refuses_reorder_and_stencil_format_with_a_hierarchy(h16):
    with pytest.raises(ValueError, match="reorder"):
        AzulEngine(h16, precond="mg", reorder="rcm")
    with pytest.raises(ValueError, match="stencil"):
        AzulEngine(h16, precond="mg", format="stencil")


def test_hierarchy_is_checked():
    h = hpcg_problem(8, 8, 8)
    with pytest.raises(ValueError, match="f2c"):
        multigrid.build(MGHierarchy(h.levels, h.f2c[:1]), np.float32)
    bad = (np.zeros_like(h.f2c[0]),) + h.f2c[1:]
    with pytest.raises(ValueError, match="distinct rows"):
        multigrid.build(MGHierarchy(h.levels, bad), np.float32)


def test_setup_and_vcycles_are_counted(h16):
    reg = obs.REGISTRY
    vc = reg.get("repro_mg_vcycles_total")
    before = vc.value()
    setups = reg.get("repro_mg_setup_seconds").samples()[0][1].count
    eng = AzulEngine(h16, precond="mg", dtype=np.float32)
    assert reg.get("repro_mg_setup_seconds").samples()[0][1].count == setups + 1
    rows = dict(reg.get("repro_mg_level_rows").samples())
    colours = dict(reg.get("repro_mg_colours").samples())
    for lv, n in enumerate((4096, 512, 64, 8)):
        assert rows[(str(lv),)].value == n
        assert colours[(str(lv),)].value == 8
    assert any(s.name == "mg.setup" for s in obs.TRACER.spans())
    iters = _iters(eng, _b((16, 16, 16)))
    assert vc.value() == before + iters + 1      # one per iteration, + r0
    assert eng.device_bytes() > AzulEngine(
        h16, precond="jacobi", dtype=np.float32).device_bytes()


def test_vcycle_layers_carry_smooth_and_transfer(h16):
    eng = AzulEngine(h16, precond="mg", dtype=np.float32)
    found = parse_hlo(eng.plan(SPEC).compile().as_text())
    assert {"smooth", "transfer", "precond", "update", "control"} <= set(
        found.values())
