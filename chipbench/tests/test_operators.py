"""Each configuration's operator, as the program takes it, against the
float64 reference, at a tiny size; the right-hand sides drawn from seeds."""

from __future__ import annotations

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests import tiny

CONFIG_CELLS = {}
for _w in harness.load_benchmark()["workloads"]:
    CONFIG_CELLS.setdefault(_w["config"], _w["name"])


@pytest.fixture(params=sorted(CONFIG_CELLS), ids=str)
def cell(request):
    return tiny.cell(CONFIG_CELLS[request.param])


def test_program_operator_matches_reference(cell):
    cfg, op = cell.cfg, cell.operator
    n = op.n(cfg)
    x = np.random.default_rng(0).standard_normal((3, n))
    want = op.reference_matvec(cfg)(x)
    prog = op.program_operator(cfg)
    if op.stored(cfg):
        import scipy.sparse as sp

        a = sp.csr_matrix((prog.data, prog.indices, prog.indptr),
                          shape=prog.shape)
        got = (a @ x.T).T
    else:
        from repro.core.stencil import stencil_matvec_host

        got = stencil_matvec_host(prog, x)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)


def test_reference_is_the_laplacian(cell):
    cfg, op = cell.cfg, cell.operator
    a = op.scipy_csr(cfg)
    x = np.random.default_rng(1).standard_normal(op.n(cfg))
    np.testing.assert_allclose(op.reference_matvec(cfg)(x), a @ x,
                               rtol=0, atol=1e-12)
    assert np.all(a.diagonal() == op.diagonal(cfg))
    assert a.nnz == op.nnz(cfg)
    assert abs(a - a.T).max() == 0


def test_control_matvec_in_float32(cell):
    import jax.numpy as jnp

    cfg, op = cell.cfg, cell.operator
    x = np.random.default_rng(2).standard_normal(op.n(cfg))
    got = np.asarray(op.control_matvec(cfg, jnp.float32)(
        jnp.asarray(x, jnp.float32)), np.float64)
    want = op.reference_matvec(cfg)(x.astype(np.float32))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_rhs_drawn_from_the_seed(cell):
    cfg, op = cell.cfg, cell.operator
    make = cell.rhs.make
    big = 2**40 + 12345                       # seeds may exceed 32 bits
    a = make(cfg, op, np.random.default_rng([big, 0]), 3)
    b = make(cfg, op, np.random.default_rng([big, 0]), 3)
    c = make(cfg, op, np.random.default_rng([big + 1, 0]), 3)
    assert a.shape == (3, op.n(cfg)) and a.dtype == np.float32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.all(np.isfinite(a)) and np.all(np.linalg.norm(a, axis=1) > 0)
