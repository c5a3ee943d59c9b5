"""Single-device sparse ops vs scipy ground truth."""

import numpy as np
import scipy.sparse as sp
from _hypothesis_compat import given, settings, strategies as st
from scipy.linalg import solve_triangular

import jax.numpy as jnp

from repro.core.formats import (bcsr_from_csr, csr_from_scipy, ell_from_csr,
                                hyb_from_csr, sell_from_csr)
from repro.core.levels import build_schedule
from repro.core.spops import (extract_diag_ell, spmv_bcsr, spmv_ell,
                              spmv_hyb_padded, spmv_sell_flat, sptrsv_ell,
                              sptrsv_ell_unrolled)


@given(st.integers(4, 80), st.floats(0.02, 0.4), st.integers(0, 10**6))
@settings(max_examples=25, deadline=None)
def test_spmv_ell_matches_scipy(n, density, seed):
    a = sp.random(n, n, density=density, random_state=seed, format="csr")
    a.setdiag(1.5)
    m = csr_from_scipy(a.tocsr())
    x = np.random.default_rng(seed).standard_normal(n)
    e = ell_from_csr(m, dtype=np.float64)
    y = np.asarray(spmv_ell(e, jnp.asarray(x)))
    assert np.allclose(y, a @ x, atol=1e-9)


@given(st.integers(4, 60), st.floats(0.05, 0.3), st.integers(0, 10**6),
       st.sampled_from([(2, 4), (8, 16)]))
@settings(max_examples=15, deadline=None)
def test_spmv_bcsr_matches_scipy(n, density, seed, blk):
    a = sp.random(n, n, density=density, random_state=seed, format="csr")
    a.setdiag(1.5)
    m = csr_from_scipy(a.tocsr())
    x = np.random.default_rng(seed).standard_normal(n)
    b = bcsr_from_csr(m, bm=blk[0], bn=blk[1], dtype=np.float64)
    y = np.asarray(spmv_bcsr(b, jnp.asarray(x)))
    assert np.allclose(y, a @ x, atol=1e-9)


@given(st.integers(2, 60), st.floats(0.05, 0.5), st.integers(0, 10**6))
@settings(max_examples=20, deadline=None)
def test_sptrsv_matches_scipy(n, density, seed):
    a = sp.random(n, n, density=density, random_state=seed, format="csr")
    l = (sp.tril(a, k=-1) + sp.eye(n) * 2.0).tocsr()
    m = csr_from_scipy(l)
    e = ell_from_csr(m, dtype=np.float64)
    sched = build_schedule(m)
    b = np.random.default_rng(seed).standard_normal(n)
    x = np.asarray(sptrsv_ell(e, sched, jnp.asarray(b)))
    ref = solve_triangular(np.asarray(l.todense()), b, lower=True)
    assert np.allclose(x, ref, atol=1e-8)


@given(st.integers(4, 60), st.floats(0.05, 0.4), st.integers(0, 10**6))
@settings(max_examples=15, deadline=None)
def test_spmv_sell_matches_scipy(n, density, seed):
    a = sp.random(n, n, density=density, random_state=seed, format="csr")
    a.setdiag(1.5)
    m = csr_from_scipy(a.tocsr())
    x = np.random.default_rng(seed).standard_normal(n)
    s = sell_from_csr(m, slice_height=8, row_pad=8, dtype=np.float64)
    x_pad = np.zeros(s.rows_padded)
    x_pad[:n] = x
    y = np.asarray(spmv_sell_flat(s, jnp.asarray(x_pad)))
    assert np.allclose(y[:n], a @ x, atol=1e-9)
    assert np.allclose(y[n:], 0.0)


@given(st.integers(4, 60), st.floats(0.05, 0.4), st.integers(0, 10**6))
@settings(max_examples=15, deadline=None)
def test_spmv_hyb_matches_scipy(n, density, seed):
    a = sp.random(n, n, density=density, random_state=seed, format="csr")
    a.setdiag(1.5)
    m = csr_from_scipy(a.tocsr())
    x = np.random.default_rng(seed).standard_normal(n)
    h = hyb_from_csr(m, row_pad=8, dtype=np.float64)
    x_pad = np.zeros(h.rows_padded)
    x_pad[:n] = x
    y = np.asarray(spmv_hyb_padded(h, jnp.asarray(x_pad)))
    assert np.allclose(y[:n], a @ x, atol=1e-9)


@given(st.integers(8, 60), st.floats(0.05, 0.4), st.integers(0, 10**6))
@settings(max_examples=10, deadline=None)
def test_sptrsv_scan_bitwise_matches_unrolled(n, density, seed):
    """The lax.scan wavefront (O(1) traced statements, sublinear compile in
    levels) must match the unrolled per-level Python loop it replaced to
    within a few ulps -- same arithmetic, different program shape.  XLA
    does not promise bitwise equality across program shapes (it may fuse
    or contract the level body differently), so the bound is 64 float64
    ulps relative to the solution's magnitude."""
    import jax

    a = sp.random(n, n, density=density, random_state=seed, format="csr")
    l = (sp.tril(a, k=-1) + sp.eye(n) * 2.0).tocsr()
    m = csr_from_scipy(l)
    e = ell_from_csr(m, dtype=np.float64)
    sched = build_schedule(m)
    b = np.random.default_rng(seed).standard_normal(n)
    x_scan = np.asarray(jax.jit(
        lambda bb: sptrsv_ell(e, sched, bb))(jnp.asarray(b)))
    x_unrl = np.asarray(jax.jit(
        lambda bb: sptrsv_ell_unrolled(e, sched, bb))(jnp.asarray(b)))
    ulp = np.finfo(np.float64).eps
    np.testing.assert_allclose(x_scan, x_unrl, rtol=64 * ulp,
                               atol=64 * ulp * np.abs(x_unrl).max())


def test_extract_diag():
    a = sp.diags([np.arange(1.0, 9.0)], [0]).tocsr() + sp.random(
        8, 8, density=0.2, random_state=0
    ).tocsr()
    a = sp.tril(a).tocsr()
    m = csr_from_scipy(a)
    e = ell_from_csr(m, dtype=np.float64)
    d = np.asarray(extract_diag_ell(e))
    assert np.allclose(d, a.diagonal())
