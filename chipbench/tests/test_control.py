"""The control -- the reference solver in the program's place, one precision
below the configuration's -- fails each configuration's limit, at a size a
test run holds, and the harness's own check finds it not correct, while
the program's own answers pass."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from chipbench import harness, reference
from chipbench.tests import tiny

CONFIG_CELLS = {}
for _w in harness.load_benchmark()["workloads"]:
    if _w["chips"] == 1:
        CONFIG_CELLS.setdefault(_w["config"], _w["name"])


def _control_run(cell, pool, max_iters=2000):
    """The control's answers on every RHS of ``pool``, as a harness Run."""
    solve = reference.control_solver(cell.cfg, cell.operator, max_iters)
    answers = []
    for i, b in enumerate(pool):
        x, k, rnorm = solve(b)
        answers.append(harness.Answer(
            b=i, t_due=float(i), t_done=float(i + 1), iters=int(k),
            status="converged" if int(k) < max_iters else "max_iters",
            rnorm=float(rnorm), x=np.asarray(x, np.float64)))
    w = harness.Window(t0=0.0, t1=float(len(pool)), answers=answers)
    return harness.Run(cell=cell, seed=7, seconds=w.seconds, setup_s=0.0,
                       window=w, pool=pool, device={}, work={}, peaks={})


@pytest.mark.parametrize("config", sorted(CONFIG_CELLS))
def test_control_fails_the_limit(config):
    cell = tiny.cell(CONFIG_CELLS[config])
    cfg, op = cell.cfg, cell.operator
    limit = harness.limit(cell)
    res = tiny.execute(cell, seconds=0.5)
    assert res["correct"]
    program = res["checks"]["max_rel_residual"]["value"]
    pool = cell.rhs.make(cfg, op, np.random.default_rng([7, 0]), 3)
    run = _control_run(cell, pool)
    verdict = harness.check(run)
    control = [a.residual for a in run.window.answers]
    assert program <= limit < min(control)
    assert verdict["correct"] is False and verdict["failed"] == len(pool)


def test_control_in_float32_passes():
    # the same solver one precision up reaches the limit and passes the
    # harness's check: what fails the control is its precision
    cell = tiny.cell("poisson2d_1024.solo")
    cfg = dict(cell.cfg, solver=dict(cell.cfg["solver"], dtype="float64"))
    up = dataclasses.replace(cell, cfg=cfg)
    pool = cell.rhs.make(cfg, cell.operator, np.random.default_rng(3), 2)
    verdict = harness.check(_control_run(up, pool))
    assert verdict["correct"], verdict["checks"]
