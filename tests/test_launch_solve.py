"""The solve CLI (``repro.launch.solve``): generated operators by name, the
``--dtype`` switch, the host-checked residual in its report, and the
persistent compilation cache its entry point turns on."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.data.matrices import laplacian_2d, named
from repro.launch import compile_cache
from repro.launch import solve as launch_solve

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _restore_cache_dir():
    """Entry points set the process-wide cache directory; put it back."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def _run(capsys, *argv):
    assert launch_solve.main(list(argv)) == 0
    return json.loads(capsys.readouterr().out)


@pytest.mark.parametrize("matrix,n", [("lap2d_12", 144), ("lap3d_6", 216),
                                      ("stencil:lap3d_6", 216)])
def test_cli_generated_operator_float32_converges(capsys, matrix, n):
    out = _run(capsys, "--matrix", matrix, "--method", "pcg_tol",
               "--precond", "jacobi", "--dtype", "float32", "--tol", "1e-5",
               "--max-iters", "500")
    assert out["n"] == n
    assert out["dtype"] == "float32"
    assert out["status"] == "converged"
    assert out["substrate"] == "fused"
    assert out["iters_run"] < 500
    # float64 host check of the float32 solve, against the true operator
    assert out["true_rel_residual"] <= 1e-4
    assert out["compile_s"] > 0 and out["solve_s"] > 0
    assert out["device"]["platform"] == jax.devices()[0].platform


def test_cli_float64_reaches_f64_tolerance(capsys):
    out = _run(capsys, "--matrix", "lap2d_12", "--method", "pcg_tol",
               "--dtype", "float64", "--tol", "1e-10", "--max-iters", "500")
    assert out["dtype"] == "float64"
    assert out["status"] == "converged"
    assert out["true_rel_residual"] <= 1e-9


def test_named_matrices():
    m = named("lap2d_12")
    assert m.shape == (144, 144)
    np.testing.assert_array_equal(m.indptr, laplacian_2d(12).indptr)
    assert named("lap3d_4").shape == (64, 64)
    assert named("banded_1k").shape == (1000, 1000)    # suite names still work
    with pytest.raises(ValueError, match="unknown matrix"):
        named("lap4d_3")


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
