"""The byte counts of chipbench/work.py against hand-worked numbers."""

from __future__ import annotations

import pytest

from chipbench import harness, work
from chipbench.operators import laplacian


def _cfg(name):
    return harness.resolve(
        next(w["name"] for w in harness.load_benchmark()["workloads"]
             if w["config"] == name)).cfg


def test_twelve_vector_passes():
    assert work.vector_passes() == 12


@pytest.mark.parametrize("name, expect", [
    # 5,238,784 nnz x (4 + 4) + 12 x 1,048,576 x 4
    ("poisson2d_1024", 41_910_272 + 50_331_648),
    # matrix-free: 12 x 2,097,152 x 4
    ("poisson3d_128_mf", 100_663_296),
    # 20,963,328 nnz x 8 + 12 x 4,194,304 x 4
    ("poisson2d_2048", 167_706_624 + 201_326_592),
])
def test_bytes_per_iteration(name, expect):
    w = work.per_iteration(_cfg(name))
    assert w["bytes"] == expect
    assert w["operator_bytes"] + w["vector_bytes"] == expect


def test_hand_worked_totals():
    assert work.per_iteration(_cfg("poisson2d_1024"))["bytes"] == 92_241_920
    assert work.per_iteration(_cfg("poisson3d_128_mf"))["bytes"] == 100_663_296


@pytest.mark.parametrize("grid", [[5, 7], [4, 5, 6], [1024, 1024]])
def test_nnz_formula_matches_assembly(grid):
    cfg = {"operator": {"kind": "laplacian", "grid": grid, "storage": "csr"}}
    if grid == [1024, 1024]:
        assert laplacian.nnz(cfg) == 5_238_784
    else:
        assert laplacian.nnz(cfg) == laplacian.scipy_csr(cfg).nnz
