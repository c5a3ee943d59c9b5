"""Matrix-free stencil operators: matvec vs the assembled CSR oracle, the
engine's stencil mode (same SolverDef plumbing as stored matrices), and the
forcing rules that keep stencils out of modes that need stored values."""

import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp

from repro.core.engine import AzulEngine
from repro.core.plan import SolveSpec
from repro.core.stencil import (lap2d_stencil, lap3d_stencil, stencil_diag,
                                stencil_matvec)
from repro.data.matrices import laplacian_2d, laplacian_3d


def _as_scipy(m):
    return sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)


@pytest.mark.parametrize("nx,ny", [(5, 7), (8, 8), (16, 4)])
def test_lap2d_matvec_matches_assembled(nx, ny):
    st = lap2d_stencil(nx, ny)
    a = _as_scipy(laplacian_2d(nx, ny))
    assert st.n == a.shape[0]
    x = np.random.default_rng(0).standard_normal(st.n)
    y = np.asarray(stencil_matvec(st, jnp.asarray(x)))
    np.testing.assert_allclose(y, a @ x, atol=1e-5)


@pytest.mark.parametrize("n", [3, 4, 6])
def test_lap3d_matvec_matches_assembled(n):
    st = lap3d_stencil(n)
    a = _as_scipy(laplacian_3d(n))
    x = np.random.default_rng(1).standard_normal(st.n)
    y = np.asarray(stencil_matvec(st, jnp.asarray(x)))
    np.testing.assert_allclose(y, a @ x, atol=1e-5)
    assert stencil_diag(st) == 6.0


def test_stencil_matvec_padded_and_batched():
    st = lap2d_stencil(6, 5)          # n = 30, pads to 32
    n, n_pad = st.n, 32
    rng = np.random.default_rng(2)
    x = rng.standard_normal((3, n_pad))
    x[:, n:] = 0.0
    y = np.asarray(stencil_matvec(st, jnp.asarray(x), n_pad))
    assert y.shape == (3, n_pad)
    a = _as_scipy(laplacian_2d(6, 5))
    np.testing.assert_allclose(y[:, :n], (a @ x[:, :n].T).T, atol=1e-5)
    np.testing.assert_allclose(y[:, n:], 0.0)


@pytest.mark.parametrize("batched", [False, True])
def test_engine_stencil_solve_matches_assembled(batched):
    st = lap2d_stencil(12, 9)
    m = laplacian_2d(12, 9)
    rng = np.random.default_rng(3)
    b = rng.standard_normal((2, st.n) if batched else (st.n,))
    e_st = AzulEngine(st, mesh=None, precond="jacobi", dtype=np.float64)
    e_ms = AzulEngine(m, mesh=None, precond="jacobi", dtype=np.float64)
    assert e_st.format_choice == "stencil"
    spec = SolveSpec(method="pcg_tol", tol=1e-9, iters=400,
                     batch=2 if batched else None)
    p_st = e_st.plan(spec)
    p_ms = e_ms.plan(spec)
    assert p_st.info["format"] == "stencil"
    x_st, _ = p_st(b)
    x_ms, _ = p_ms(b)
    np.testing.assert_allclose(x_st, x_ms, atol=1e-7)
    assert int(np.max(np.asarray(p_st.last_iters))) == \
        int(np.max(np.asarray(p_ms.last_iters)))


def test_engine_stencil_guard_and_spmv():
    st = lap3d_stencil(5)
    eng = AzulEngine(st, mesh=None, precond="none", dtype=np.float64)
    b = np.random.default_rng(4).standard_normal(st.n)
    p = eng.plan(SolveSpec(method="pcg_tol", tol=1e-8, iters=300))
    x, _ = p(b)
    assert p.last_status_names == "converged"
    a = _as_scipy(laplacian_3d(5))
    np.testing.assert_allclose(np.asarray(eng.spmv(x)), a @ x.T if x.ndim == 2
                               else a @ x, atol=1e-6)


def test_engine_stencil_forcing_rules():
    st = lap2d_stencil(8)
    # modes that need stored matrix values are rejected up front
    with pytest.raises(ValueError):
        AzulEngine(st, mesh=None, precond="block_ic0")
    with pytest.raises(ValueError):
        AzulEngine(st, mesh=None, format="hyb")
    eng = AzulEngine(st, mesh=None, precond="jacobi", dtype=np.float64)
    with pytest.raises(ValueError):
        eng.plan(SolveSpec(method="pcg", iters=5, injectable=True))
    with pytest.raises(ValueError):
        eng.plan(SolveSpec(method="pcg", iters=5, format="ell"))
    with pytest.raises(ValueError):
        eng.vals_template()
    # and the converse: a stored-matrix engine cannot claim format=stencil
    m = laplacian_2d(8)
    with pytest.raises(ValueError):
        AzulEngine(m, mesh=None, format="stencil")
    eng_m = AzulEngine(m, mesh=None, dtype=np.float64)
    with pytest.raises(ValueError):
        eng_m.plan(SolveSpec(method="pcg", iters=5, format="stencil"))


@pytest.mark.slow
def test_engine_stencil_large_n_smoke():
    """The point of matrix-free: n = 262144 builds in O(n) memory (no
    assembled CSR, no ELL) and takes solver iterations immediately."""
    st = lap2d_stencil(512)
    eng = AzulEngine(st, mesh=None, precond="jacobi", dtype=np.float32)
    assert eng.ell is None
    assert eng.device_bytes() <= 32 * st.n      # vectors only, no matrix
    b = np.random.default_rng(6).standard_normal(st.n).astype(np.float32)
    x, norms = eng.solve(b, method="pcg", iters=8)
    assert np.isfinite(np.asarray(norms)).all()
    assert float(norms[-1]) < float(norms[0])
