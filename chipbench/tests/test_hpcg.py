"""The hpcg_64 configuration's parts: the operator against scipy and HPCG's
sizes, the benchmark's float64 multigrid reference against the program's
own (``repro.core.mg_ref``), the smoother's bytes, its two readers, and a
16^3 cell run end to end through the harness, whose iterations the
reference decides: a V-cycle without its post-smoothing is caught there."""

from __future__ import annotations

import copy
import dataclasses
from types import SimpleNamespace as NS

import numpy as np
import pytest

from chipbench import harness, mg_reference, scopes, work_mg
from chipbench.tests import tiny

GRID16 = (16, 16, 16)


@pytest.fixture(autouse=True)
def own_scopes(monkeypatch):
    """Keep these tests' plans out of the process-wide scope map, where
    their instruction names would collide with other tests' plans."""
    from repro.core import plan as plan_module
    from repro.obs.scopes import ScopeMap

    monkeypatch.setattr(plan_module, "_SCOPES", ScopeMap())


def _cell(grid=GRID16):
    c = harness.resolve("hpcg_64.solo")
    cfg = copy.deepcopy(c.cfg)
    cfg["operator"]["grid"] = list(grid)
    return dataclasses.replace(c, cfg=cfg)


def _cfg(grid):
    return _cell(grid).cfg


# -- the operator -------------------------------------------------------------


@pytest.mark.parametrize("grid, n, nnz", [
    ((64, 64, 64), 262_144, 6_859_000),
    ((104, 104, 104), 1_124_864, 29_791_000),
])
def test_hpcg_sizes(grid, n, nnz):
    op = _cell(grid).operator
    assert op.n(_cfg(grid)) == n and op.nnz(_cfg(grid)) == nnz


def test_operator_at_64_is_scipys():
    cfg = _cfg((64, 64, 64))
    op = _cell().operator
    a = op.scipy_csr(cfg)
    assert a.shape == (262_144, 262_144) and a.nnz == 6_859_000
    assert [op.n({"operator": {"grid": g}}) for g in op.level_dims(cfg)] == [
        262_144, 32_768, 4_096, 512]


@pytest.mark.parametrize("grid", [GRID16, (16, 24, 32), (6, 4, 2)])
def test_operator_against_the_programs_reference(grid):
    from repro.core import mg_ref

    op = _cell().operator
    cfg = _cfg(grid)
    a = op.scipy_csr(cfg)
    assert abs(a - mg_ref.operator(*grid)).max() == 0
    assert a.nnz == op.nnz(cfg)
    prog = op.program_operator(cfg)
    want_levels, want_f2c = mg_ref.hierarchy(*grid, len(op.level_dims(cfg)))
    assert len(prog.levels) == len(want_levels)
    for got, want in zip(prog.f2c, want_f2c):
        np.testing.assert_array_equal(got, want)


def test_levels_follow_the_grid():
    op = _cell().operator
    assert op.level_dims(_cfg((64, 64, 64)))[-1] == (8, 8, 8)
    assert len(op.level_dims(_cfg((12, 12, 12)))) == 3    # 12, 6, 3


# -- the float64 reference ----------------------------------------------------


@pytest.mark.parametrize("order", ["colour", "lexicographic"])
def test_reference_agrees_with_the_programs_reference(order):
    from repro.core import mg_ref

    gs = mg_reference.grids(*GRID16, 4, order)
    lvls, f2c = mg_ref.levels(*GRID16, order=order)
    r = np.random.default_rng(3).standard_normal(16 ** 3)
    np.testing.assert_allclose(mg_reference.mg(gs, r),
                               mg_ref.vcycle(lvls, f2c, r),
                               rtol=0, atol=1e-12)
    it, hist = mg_reference.cg(gs, 1e-5, 500)
    b = gs[0].a @ np.ones(16 ** 3)
    _, want, want_hist = mg_ref.pcg(lvls, f2c, b, rtol=1e-5)
    assert it == want
    np.testing.assert_allclose(hist, want_hist, rtol=1e-9)


def test_colour_order_costs_iterations():
    assert mg_reference.iterations(GRID16) == 9
    assert mg_reference.iterations(GRID16, order="lexicographic") == 8


# -- the smoother's bytes -----------------------------------------------------


def test_smoother_bytes_at_64():
    w = work_mg.per_vcycle(_cfg((64, 64, 64)))
    # per sweep nnz x 4 B + 3 n x 4 B; 4 sweeps, and 2 on the coarsest
    want = [4 * (6_859_000 + 3 * 262_144) * 4, 4 * (830_584 + 3 * 32_768) * 4,
            4 * (97_336 + 3 * 4_096) * 4, 2 * (10_648 + 3 * 512) * 4]
    assert [lv["bytes"] for lv in w["levels"]] == want
    assert w["bytes"] == 139_040_576


# -- the readers --------------------------------------------------------------


def _run(reduced, iters=(26, 27)):
    answers = [harness.Answer(b=0, t_due=0.0, t_done=1.0, iters=i,
                              status="converged") for i in iters]
    tr = harness.Traced(t0=0.0, t1=2.0, answers=answers, reduced=reduced)
    return NS(window=harness.Window(t0=0.0, t1=2.0, answers=answers,
                                    traced=tr),
              cell=_cell((64, 64, 64)), setup_s=1.0, work={},
              peaks={"hbm_bytes_per_s": 819e9})


def _read(name, run):
    return harness.plugin("metrics", name).read(run)


def test_smooth_readers(monkeypatch):
    monkeypatch.setattr(scopes, "scope_map",
                        lambda: {"fusion.9": "smooth", "gather.2": "smooth",
                                 "fusion.4": "gather"})
    run = _run({"top_ops": [["fusion.9 kLoop", 4.0], ["gather.2", 1.0],
                            ["fusion.4 kCustom", 3.0]]})
    assert _read("smooth_ms.solo", run) == pytest.approx(1e3 * 5.0 / 53)
    # 55 V-cycles (53 iterations and one per solve) x 139,040,576 B
    want = 100.0 * 139_040_576 * 55 / 819e9 / 5.0
    assert _read("smooth_roofline.solo", run) == pytest.approx(want)


@pytest.mark.parametrize("name", ["smooth_ms.solo", "smooth_roofline.solo"])
def test_smooth_readers_without_a_trace_are_none(name, monkeypatch):
    assert _read(name, _run(None)) is None
    # a program without the scope (one that predates it) reads nothing
    monkeypatch.setattr(scopes, "scope_map", lambda: {"fusion.4": "gather"})
    assert _read(name, _run({"top_ops": [["fusion.4 kCustom", 3.0]]})) is None
    monkeypatch.setattr(scopes, "scope_map", lambda: None)
    assert _read(name, _run({"top_ops": [["fusion.9", 3.0]]})) is None


# -- the cell, end to end -----------------------------------------------------


def _iterations_match(res, grid=GRID16) -> bool:
    """The iteration comparison: the program's mean iterations within one
    of the float64 reference's, in the program's colour order."""
    return abs(res["notes"]["iters"] - mg_reference.iterations(grid)) <= 1


@pytest.fixture
def iters_note(monkeypatch):
    """Record the window's mean iterations in the result's notes."""
    loop = harness.plugin("loops", "closed")
    window = loop.window

    def recording(*args, **kw):
        w = window(*args, **kw)
        its = [a.iters for a in w.answers]
        w.notes["iters"] = sum(its) / len(its)
        return w

    monkeypatch.setattr(loop, "window", recording)


def test_tiny_cell_is_correct_and_takes_the_reference_iterations(iters_note):
    res = tiny.execute(_cell(), seconds=1.0)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 1
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert res["notes"]["compiles_in_window"] == 0
    assert _iterations_match(res)


def test_dropped_post_smoothing_is_caught_by_the_iterations(iters_note,
                                                            monkeypatch):
    from repro.core import multigrid

    calls, n_levels = [0], 4
    real = multigrid.symgs

    def pre_only(lv, r, x):
        k = calls[0] % (2 * n_levels - 1)
        calls[0] += 1
        return real(lv, r, x) if k < n_levels else x

    monkeypatch.setattr(multigrid, "symgs", pre_only)
    res = tiny.execute(_cell(), seconds=1.0)
    assert calls[0] > 0
    assert not _iterations_match(res)
