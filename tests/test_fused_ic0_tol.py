"""Fused IC(0) and tolerance-mode solve paths: property verification of the
whole-solve SpTRSV kernel and the p-fold SpMV kernels against the ``ref``
oracles (interpret mode), fused-vs-reference equivalence for
``precond="block_ic0"`` PCG and for ``pcg_tol`` (single and batched RHS),
the iteration-count regression (``pcg_tol`` must stop at the SAME iteration
fused vs reference), and the substrate-selection acceptance checks for the
``launch/solve`` configurations.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp

from _hypothesis_compat import given, settings, strategies as st
from repro.core.engine import AzulEngine
from repro.core.formats import csr_from_scipy, ell_from_csr
from repro.core.levels import build_schedule
from repro.core.precond import ic0
from repro.core.solvers import pcg
from repro.core.spops import extract_diag_ell, spmv_ell_padded, sptrsv_ell
from repro.core.substrate import (fused_ic0_local_substrate,
                                  modeled_ic0_traffic, modeled_vector_traffic)
from repro.data.matrices import laplacian_2d, random_spd
from repro.kernels import ops, ref
from repro.kernels.spmv_dot import ell_spmm_pfold_dot, ell_spmv_pfold_dot


def _lower_ell(n, density, seed, dtype=np.float64):
    a = sp.random(n, n, density=density, random_state=seed, format="csr")
    l = (sp.tril(a, -1) + sp.eye(n) * 2.0).tocsr()
    m = csr_from_scipy(l)
    return m, ell_from_csr(m, row_pad=8, width_pad=8, dtype=dtype)


# -- kernel-level properties (interpret mode vs oracles) ---------------------


@given(st.integers(10, 90), st.sampled_from([0.05, 0.25]), st.integers(0, 10**6))
@settings(max_examples=10, deadline=None)
def test_sptrsv_solve_dot_matches_spops(n, density, seed):
    """The whole-solve kernel must reproduce the level-by-level oracle AND
    emit the exact in-stream dot."""
    m, e = _lower_ell(n, density, seed)
    sched = build_schedule(m)
    rp = e.rows_padded
    rng = np.random.default_rng(seed)
    b = jnp.zeros(rp).at[:n].set(jnp.asarray(rng.standard_normal(n)))
    w = jnp.zeros(rp).at[:n].set(jnp.asarray(rng.standard_normal(n)))
    diag = extract_diag_ell(e)
    dinv = jnp.ones(rp).at[:n].set(1.0 / diag[:n])

    x_o = sptrsv_ell(e, sched, b[:n])
    ops.backend_mode("interpret")
    try:
        x_k, pp_k = ops.sptrsv_solve_dot(e.cols, e.vals, dinv, b, sched.rows,
                                         w, n_rows=n)
    finally:
        ops.backend_mode("auto")
    x_r, pp_r = ref.sptrsv_solve_dot_ref(e.cols, e.vals, dinv, b, sched.rows,
                                         w, n)
    np.testing.assert_allclose(np.asarray(x_k)[:n], np.asarray(x_o), atol=1e-10)
    np.testing.assert_allclose(np.asarray(x_k), np.asarray(x_r), atol=1e-12)
    np.testing.assert_allclose(float(pp_k),
                               float(jnp.sum(w[:n] * x_o)), atol=1e-10)
    np.testing.assert_allclose(float(pp_k), float(pp_r), atol=1e-12)


@given(st.integers(12, 80), st.integers(1, 4), st.booleans(),
       st.integers(0, 10**6))
@settings(max_examples=10, deadline=None)
def test_pfold_kernels_match_ref(n, k, f64, seed):
    """p = z + beta*p folded into the gather: kernel == oracle == unfused
    composition, single and multi-RHS."""
    dtype = np.float64 if f64 else np.float32
    a = sp.random(n, n, density=0.15, random_state=seed, format="csr")
    a.setdiag(2.0)
    e = ell_from_csr(csr_from_scipy(a.tocsr()), row_pad=8, width_pad=8,
                     dtype=dtype)
    rp = e.rows_padded
    rng = np.random.default_rng(seed)
    tol = 1e-11 if f64 else 1e-4
    z = jnp.asarray(rng.standard_normal(rp), dtype)
    p = jnp.asarray(rng.standard_normal(rp), dtype)
    beta = dtype(rng.standard_normal())
    pn_k, y_k, pap_k = ell_spmv_pfold_dot(e.cols, e.vals, z, p, beta,
                                          tm=8, tw=8, interpret=True)
    pn_r, y_r, pap_r = ref.ell_spmv_pfold_dot_ref(e.cols, e.vals, z, p, beta)
    pn_c = z + beta * p
    y_c = spmv_ell_padded(e.cols, e.vals, pn_c)
    np.testing.assert_allclose(np.asarray(pn_k), np.asarray(pn_r), atol=tol)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_c), atol=tol)
    np.testing.assert_allclose(float(pap_k), float(pap_r), rtol=100 * tol,
                               atol=tol)
    # batched
    Z = jnp.asarray(rng.standard_normal((rp, k)), dtype)
    Pm = jnp.asarray(rng.standard_normal((rp, k)), dtype)
    bb = jnp.asarray(rng.standard_normal(k), dtype)
    pnb_k, yb_k, papb_k = ell_spmm_pfold_dot(e.cols, e.vals, Z, Pm, bb,
                                             tm=8, tw=8, interpret=True)
    pnb_r, yb_r, papb_r = ref.ell_spmm_pfold_dot_ref(e.cols, e.vals, Z, Pm, bb)
    np.testing.assert_allclose(np.asarray(pnb_k), np.asarray(pnb_r), atol=tol)
    np.testing.assert_allclose(np.asarray(yb_k), np.asarray(yb_r), atol=tol)
    np.testing.assert_allclose(np.asarray(papb_k), np.asarray(papb_r),
                               rtol=100 * tol, atol=tol)


# -- solver-level: fused IC(0) substrate == reference -------------------------


@given(st.integers(20, 70), st.integers(0, 10**6), st.booleans())
@settings(max_examples=6, deadline=None)
def test_pcg_ic0_fused_substrate_matches_reference(n, seed, batched):
    m = random_spd(n, density=0.08, seed=seed)
    e = ell_from_csr(m, dtype=np.float64)
    rp = e.rows_padded
    f = ic0(m, dtype=np.float64)
    rng = np.random.default_rng(seed)
    b = rng.standard_normal((3, n) if batched else (n,))
    b_pad = jnp.zeros(b.shape[:-1] + (rp,), jnp.float64).at[..., :n].set(
        jnp.asarray(b)
    )

    def mv(x):
        if x.ndim == 2:
            from repro.core.spops import spmm_ell_padded
            return spmm_ell_padded(e.cols, e.vals, x)
        return spmv_ell_padded(e.cols, e.vals, x)

    from repro.core.precond import apply_ic0

    def ps1(r):
        z = apply_ic0(f, r[:n])
        return jnp.zeros(rp, r.dtype).at[:n].set(z)

    def ps(r):
        import jax
        return jax.vmap(ps1)(r) if r.ndim == 2 else ps1(r)

    res_ref = pcg(mv, b_pad, psolve=ps, iters=40)
    sub = fused_ic0_local_substrate(e.cols, e.vals, f, n, rp)
    res_fused = pcg(mv, b_pad, psolve=ps, iters=40, substrate=sub)
    np.testing.assert_allclose(np.asarray(res_fused.x), np.asarray(res_ref.x),
                               atol=1e-9)
    np.testing.assert_allclose(np.asarray(res_fused.res_norms),
                               np.asarray(res_ref.res_norms),
                               rtol=1e-8, atol=1e-10)


# -- pcg_tol: fused == reference, INCLUDING the stopping iteration -----------


@pytest.mark.parametrize("precond", ["jacobi", "none", "block_ic0"])
@pytest.mark.parametrize("batched", [False, True])
def test_engine_pcg_tol_fused_matches_reference(precond, batched):
    m = laplacian_2d(12)
    a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
    rng = np.random.default_rng(7)
    xt = rng.standard_normal((3, m.shape[0]) if batched else (m.shape[0],))
    b = xt @ a.T if batched else a @ xt
    eng = AzulEngine(m, precond=precond, dtype=np.float64)
    xf, nf = eng.solve(b, method="pcg_tol", tol=1e-9, max_iters=400, fused=True)
    it_f = np.asarray(eng.last_solve_info["iters"])
    xu, nu = eng.solve(b, method="pcg_tol", tol=1e-9, max_iters=400, fused=False)
    it_u = np.asarray(eng.last_solve_info["iters"])
    # THE regression contract: identical stopping iteration, fused vs ref
    np.testing.assert_array_equal(it_f, it_u)
    np.testing.assert_allclose(xf, xu, atol=1e-9)
    np.testing.assert_allclose(nf, nu, rtol=1e-7, atol=1e-12)
    # and it actually solved to tolerance
    res = b - (xf @ a.T if batched else a @ xf)
    assert np.linalg.norm(res) < 1e-7 * max(np.linalg.norm(b), 1.0)
    assert int(np.max(it_f)) < 400


def test_engine_pcg_tol_ic0_interpret_kernels_match():
    """Tolerance + IC(0) with the real kernel bodies (interpret mode): the
    whole-solve SpTRSV and p-fold kernels inside the while_loop."""
    m = laplacian_2d(9)
    a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
    b = a @ np.random.default_rng(5).standard_normal(m.shape[0])
    eng = AzulEngine(m, precond="block_ic0", dtype=np.float64)
    ops.backend_mode("interpret")
    try:
        xi, _ = eng.solve(b, method="pcg_tol", tol=1e-9, max_iters=200,
                          fused=True)
        it_i = int(np.asarray(eng.last_solve_info["iters"]))
    finally:
        ops.backend_mode("auto")
    xr, _ = eng.solve(b, method="pcg_tol", tol=1e-9, max_iters=200, fused=False)
    it_r = int(np.asarray(eng.last_solve_info["iters"]))
    assert it_i == it_r
    np.testing.assert_allclose(xi, xr, atol=1e-9)


def test_engine_ic0_fixed_iters_fused_matches_unfused():
    m = laplacian_2d(12)
    a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
    rng = np.random.default_rng(3)
    for b in (rng.standard_normal(m.shape[0]),
              rng.standard_normal((4, m.shape[0]))):
        eng = AzulEngine(m, precond="block_ic0", dtype=np.float64)
        xf, nf = eng.solve(b, method="pcg", iters=60, fused=True)
        assert eng.last_solve_info["substrate"] == "fused_ic0"
        xu, nu = eng.solve(b, method="pcg", iters=60, fused=False)
        assert eng.last_solve_info["substrate"] == "reference"
        np.testing.assert_allclose(xf, xu, atol=1e-9)
        np.testing.assert_allclose(nf, nu, rtol=1e-8, atol=1e-12)


# -- acceptance: per-backend substrate selection ------------------------------


def test_substrate_selection_per_backend():
    """Capability resolution is backend-aware for ``block_ic0``: the fused
    whole-solve SpTRSV substrate buys HBM traffic with on-chip compute, a
    trade that only pays where the Pallas kernels dispatch -- on plain CPU
    it is ~7x SLOWER than the reference apply (BENCH_pcg tol_solves at
    lap2d_32), so ``fused="auto"`` prefers the reference IC(0) apply there
    and picks ``fused_ic0`` once kernels are active (interpret/TPU).  An
    explicit ``fused=True`` forces the fused path on any backend."""
    m = laplacian_2d(8)
    b = np.random.default_rng(0).standard_normal(m.shape[0])
    eng = AzulEngine(m, mesh=None, mode="2d", precond="block_ic0",
                     dtype=np.float64)      # the driver's default knobs
    # plain CPU ('auto' dispatch, kernels inactive): reference preferred
    assert not ops.kernels_active()
    assert eng.substrate_kind("pcg_tol") == "reference"
    assert eng.substrate_kind("pcg_tol", fused=True) == "fused_ic0"
    eng.solve(b, method="pcg_tol", tol=1e-8, max_iters=100)
    assert eng.last_solve_info["substrate"] == "reference"
    assert eng.last_solve_info["fused"] is False
    # kernels active (interpret mode): 'auto' picks the fused substrate --
    # the plan cache keys on the dispatch mode, so no stale program serves
    ops.backend_mode("interpret")
    try:
        assert ops.kernels_active()
        assert eng.substrate_kind("pcg_tol") == "fused_ic0"
        eng.solve(b, method="pcg_tol", tol=1e-8, max_iters=100)
        assert eng.last_solve_info["substrate"] == "fused_ic0"
        assert eng.last_solve_info["fused"] is True
    finally:
        ops.backend_mode("auto")
    # jacobi/identity fused substrates are pure-fusion wins (no
    # compute-for-traffic trade): 'auto' keeps them fused on every backend
    for method in ("pcg", "pcg_tol", "cg"):
        for pc in ("jacobi", "none"):
            e2 = AzulEngine(m, precond=pc, dtype=np.float64)
            assert e2.substrate_kind(method) != "reference", (method, pc)


def test_ic0_auto_resolves_reference_where_kernel_cannot_compile(monkeypatch):
    """On a TPU the ops dispatch to compiled kernels, and the whole-solve
    SpTRSV kernel behind ``fused_ic0`` does not compile there: 'auto'
    must resolve block_ic0 to the reference apply (and say so in the plan
    info), while the Jacobi plans stay fused."""
    monkeypatch.setattr(ops, "_dispatch", lambda: (True, False))
    assert ops.kernels_active()
    assert not ops.kernel_usable("sptrsv_solve_dot")
    assert ops.kernel_usable("cg_update")
    m = laplacian_2d(8)
    eng = AzulEngine(m, precond="block_ic0", dtype=np.float32)
    assert eng.substrate_kind("pcg_tol") == "reference"
    assert eng.plan(method="pcg_tol", tol=1e-5).info["substrate"] == "reference"
    assert eng.substrate_kind("pcg_tol", fused=True) == "fused_ic0"
    assert AzulEngine(m, precond="jacobi",
                      dtype=np.float32).substrate_kind("pcg_tol") == "fused"


@pytest.mark.slow
def test_launch_solve_cli_reports_fused_substrate(capsys):
    """The driver itself, end to end, reports the forced-fused substrate
    (``--fused on``; the CPU default is the reference IC(0) apply)."""
    import json as _json

    from repro.launch import solve as launch_solve

    launch_solve.main([
        "--matrix", "lap2d_32", "--method", "pcg_tol",
        "--precond", "block_ic0", "--tol", "1e-6", "--iters", "120",
        "--fused", "on", "--dtype", "float64",
    ])
    out = _json.loads(capsys.readouterr().out)
    assert out["substrate"] == "fused_ic0"
    assert out["fused"] is True
    assert out["layout"] == "dense" and out["reorder"] == "none"
    assert out["iters_run"] <= 120
    assert out["rel_error"] < 1e-4


# -- serving: tolerance-mode coalesced solves --------------------------------


def test_solve_server_tolerance_mode():
    from repro.serve import SolveServer

    m = laplacian_2d(10)
    a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
    eng = AzulEngine(m, precond="block_ic0", dtype=np.float64)
    srv = SolveServer(eng, max_batch=4, method="pcg_tol", iters=300, tol=1e-9)
    rng = np.random.default_rng(1)
    xt = rng.standard_normal((5, m.shape[0]))
    ids = [srv.submit(a @ xt[i]) for i in range(5)]
    done = srv.drain()
    assert set(done) == set(ids)
    for i, rid in enumerate(ids):
        np.testing.assert_allclose(done[rid].x, xt[i], atol=1e-6)
        assert 0 < done[rid].iters <= 300        # per-request tol iterations
    # CPU default: 'auto' resolution prefers the reference IC(0) apply
    # where kernels are inactive (see test_substrate_selection_per_backend)
    assert eng.last_solve_info["substrate"] == "reference"


# -- traffic models -----------------------------------------------------------


def test_ic0_traffic_model():
    """Fused IC(0) traffic is level-count independent; the reference path
    scales with the wavefront count -- the whole point of the fusion."""
    lo = modeled_ic0_traffic(8.0, 4, 4)
    hi = modeled_ic0_traffic(8.0, 60, 60)
    assert hi["fused_words_per_n"] == lo["fused_words_per_n"]
    assert hi["unfused_words_per_n"] > lo["unfused_words_per_n"]
    assert hi["reduction"] > lo["reduction"] > 1.0


def test_fold_traffic_model():
    t = modeled_vector_traffic(8.0)
    assert t["fused_fold_words_per_n"] < t["fused_words_per_n"]
    assert t["reduction"] == round(
        t["unfused_words_per_n"] / t["fused_fold_words_per_n"], 3
    )
