"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jax; it compiles for a described (not
attached) ``v5e:2x2`` topology while the tests themselves run on the CPU.
This catches what interpret mode cannot: block shapes that break the
(8, 128) tiling rule, in-kernel gathers Mosaic does not lower, and VMEM
overruns.  Shapes are the real ones: n = 2**20 rows, ELL width 8, f32, and
a ragged n = 10**6 for the service operator.

The topology is described inside a module-scoped fixture only: only one
process may load the TPU library, so it must not happen at import time.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N = 1 << 20
W = 8
K = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Dispatch the ops to the compiled (non-interpret) kernels, as they
    are dispatched on a TPU backend."""
    monkeypatch.setattr(ops, "_dispatch", lambda: (True, False))


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("n", [N, 10**6])
@pytest.mark.parametrize("op", ["ell_spmv", "ell_spmm", "ell_spmv_pfold_dot",
                                "ell_spmm_pfold_dot"])
def test_ell_kernel_compiles_for_v5e(op, n, one_chip, compiled_kernels):
    cols = _sds(one_chip, (n, W), jnp.int32)
    vals = _sds(one_chip, (n, W))
    vec = _sds(one_chip, (n,))
    mat = _sds(one_chip, (n, K))
    f = getattr(ops, op)
    if op == "ell_spmv":
        txt = _compile_text(f, cols, vals, vec)
    elif op == "ell_spmm":
        txt = _compile_text(f, cols, vals, mat)
    elif op == "ell_spmv_pfold_dot":
        txt = _compile_text(f, cols, vals, vec, vec, _sds(one_chip, ()))
    else:
        txt = _compile_text(f, cols, vals, mat, mat, _sds(one_chip, (K,)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("n", [N, 10**6])
@pytest.mark.parametrize("k,jacobi", [(None, True), (None, False),
                                      (K, True), (K, False)])
def test_cg_update_compiles_for_v5e(k, jacobi, n, one_chip, compiled_kernels):
    shape = (n,) if k is None else (k, n)
    v = _sds(one_chip, shape)
    alpha = _sds(one_chip, () if k is None else (k, 1))
    dinv = _sds(one_chip, (n,)) if jacobi else None

    def step(a, x, r, p, ap, *d):
        return ops.cg_update(a, x, r, p, ap, *d)

    args = (alpha, v, v, v, v) + ((dinv,) if jacobi else ())
    assert "tpu_custom_call" in _compile_text(step, *args)


@pytest.mark.parametrize("op", sorted(ops.UNCOMPILABLE))
def test_uncompilable_kernels_still_refused(op, one_chip, compiled_kernels):
    """``ops.UNCOMPILABLE`` routes work away from these kernels on TPU; the
    day one compiles, this fails and the entry should go."""
    n, levels, width = 4096, 64, 128
    cols = _sds(one_chip, (n, W), jnp.int32)
    vals = _sds(one_chip, (n, W))
    vec = _sds(one_chip, (n,))
    sched = _sds(one_chip, (levels, width), jnp.int32)
    if op == "sptrsv_solve_dot":
        fn = lambda c, v, d, b, s: ops.sptrsv_solve_dot(c, v, d, b, s)
        args = (cols, vals, vec, vec, sched)
    else:
        fn = lambda c, v, d, b, x, r: ops.sptrsv_level_step(c, v, d, b, x, r)
        args = (cols, vals, vec, vec, _sds(one_chip, (n + 1,)),
                _sds(one_chip, (width,), jnp.int32))
    with pytest.raises(Exception):
        _compile_text(fn, *args)


def test_ell_tiles_are_lane_multiples():
    """The pickers never hand Mosaic a ragged lane tile: row/vector tiles
    are multiples of 128 unless they span the whole axis."""
    cols = np.zeros((10**6, W), np.int32)
    for k in (1, 8, 32):
        tm, tw = ops._tiles_2d("ell_spmm", cols, np.float32, None, None, k)
        assert tm % 128 == 0 and tw == W
    assert ops._lane_tile(1000, 8192) == 1000
    assert ops._lane_tile(10**6, 1000) == 896


@pytest.mark.parametrize("kind", ["stored", "stencil"])
def test_plan_layers_keep_their_scopes_for_v5e(kind, one_chip,
                                               compiled_kernels):
    """In the v5e compile of a whole Jacobi-PCG plan, the XLA gather
    fusion, the ELL kernel, the cg_update kernel and the solver loop are
    named by their ``repro.obs.scopes`` layer (small grids: the program's
    structure does not depend on n)."""
    import re

    from repro.core import AzulEngine, SolveSpec
    from repro.core.stencil import lap3d_stencil
    from repro.data.matrices import laplacian_2d
    from repro.obs.scopes import parse_hlo

    a = laplacian_2d(128) if kind == "stored" else lap3d_stencil(32)
    eng = AzulEngine(a, precond="jacobi", dtype=np.float32)
    plan = eng.plan(SolveSpec(method="pcg_tol", tol=1e-5, max_iters=8000))
    vec = _sds(one_chip, (eng.n_pad,))
    text = plan.fn.lower(vec, vec).compile().as_text()
    found = parse_hlo(text)

    def names(pattern):
        return [m.group(1) for m in re.finditer(
            r"^\s*(?:ROOT\s+)?%(\S+) = [^\n]*" + pattern, text, re.M)]

    assert {found[n] for n in names(r" while\(")} == {"control"}
    cg = [n for n in names(r'custom_call_target="tpu_custom_call"')
          if n.startswith("cg_update.")]
    assert cg and {found[n] for n in cg} == {"update"}
    gathers = names(r" fusion\([^\n]*kind=kCustom")
    if kind == "stored":
        assert gathers and {found[n] for n in gathers} == {"gather"}
        ell = [n for n in names(r'custom_call_target="tpu_custom_call"')
               if n.startswith("stream_rows")]
        assert ell and {found[n] for n in ell} == {"matvec"}
    else:
        assert not gathers


def test_mg_plan_compiles_for_v5e(one_chip, compiled_kernels):
    """HPCG's multigrid-preconditioned CG (``precond="mg"``) compiles for a
    v5e at 16^3 with all four levels (the program unrolls the same 112
    colour steps per V-cycle at 64^3), and its colour steps and level
    transfers keep their ``smooth`` and ``transfer`` scopes."""
    from repro.core import AzulEngine, SolveSpec
    from repro.data.matrices import hpcg_problem
    from repro.obs.scopes import parse_hlo

    eng = AzulEngine(hpcg_problem(16, 16, 16), precond="mg",
                     dtype=np.float32)
    plan = eng.plan(SolveSpec(method="pcg_tol", tol=1e-5, max_iters=500))
    vec = _sds(one_chip, (eng.n_pad,))
    found = parse_hlo(plan.fn.lower(vec, vec).compile().as_text())
    assert {"smooth", "transfer", "control"} <= set(found.values())
