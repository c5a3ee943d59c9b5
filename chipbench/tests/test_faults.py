"""A run with the timed path broken underneath comes out not correct: once
for each fault a cell can have.

* an answer altered where it is produced (every cell);
* a solve that returns its state unchanged, x = x0 (every cell);
* a solve that reports it did not converge (every cell);
* a solve that stops a hundred times short of the rtol (every cell);
* the exchange between chips left out (the 2x2 mesh cell).
"""

from __future__ import annotations

import numpy as np
import pytest

from chipbench.tests import tiny

CELLS = ["poisson2d_1024.solo", "poisson3d_128_mf.solo"]


def _alter(x):
    x = np.array(x, copy=True)
    flat = x.reshape(-1)
    flat[flat.size // 2] += 10.0 * (np.abs(flat).max() + 1.0)
    return x


def _unchanged(x, x0):
    return np.zeros_like(x) if x0 is None else np.array(x0, x.dtype)


@pytest.fixture
def broken(monkeypatch):
    """Plant ``fault`` in SolvePlan.__call__, which every loop drives."""
    from repro.core.plan import SolvePlan

    def plant(fault):
        if fault == "not_converged":
            monkeypatch.setattr(SolvePlan, "last_status_names",
                                property(lambda self: "max_iters"))
            return
        if fault == "stops_early":
            from repro.core import plan

            spec = plan.SolveSpec
            monkeypatch.setattr(plan, "SolveSpec", lambda tol, **kw: spec(
                tol=100 * tol, **kw))
            return
        call = SolvePlan.__call__

        def faulty(self, b, x0=None, vals=None):
            x, norms = call(self, b, x0=x0, vals=vals)
            x = _alter(x) if fault == "altered" else _unchanged(x, x0)
            return x, norms

        monkeypatch.setattr(SolvePlan, "__call__", faulty)
    return plant


# the number each fault has to push over its limit
CAUGHT_BY = {"altered": "max_rel_residual", "unchanged": "max_rel_residual",
             "not_converged": "not_converged",
             "stops_early": "max_recursive_rel_residual"}


@pytest.mark.parametrize("fault", sorted(CAUGHT_BY))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault, broken):
    cell = tiny.cell(workload)
    broken(fault)
    res = tiny.execute(cell, seconds=1.0)
    assert res["correct"] is False
    assert res["failed"] > 0
    c = res["checks"][CAUGHT_BY[fault]]
    assert c["value"] > c["limit"]


MESH_FAULT = """
import json
import numpy as np
from chipbench.tests import tiny
from repro.core import noc, plan

fault = {fault!r}
if fault == "no_exchange":
    noc._ppermute = lambda x, axes, perm: x
elif fault == "altered":
    call = plan.SolvePlan.__call__
    def faulty(self, b, x0=None, vals=None):
        x, norms = call(self, b, x0=x0, vals=vals)
        x = np.array(x, copy=True); x[x.size // 2] += 10 * (abs(x).max() + 1)
        return x, norms
    plan.SolvePlan.__call__ = faulty
res = tiny.execute(tiny.cell("poisson2d_2048.mesh2x2"), seconds=1.0)
print(json.dumps(res))
"""


@pytest.mark.parametrize("fault", ["no_exchange", "altered"])
def test_mesh_fault_is_not_correct(fault):
    p = tiny.run_script(MESH_FAULT.format(fault=fault))
    assert p.returncode == 0, p.stderr[-3000:]
    res = tiny.last_json(p.stdout)
    assert res["device"]["count"] == 4
    assert res["correct"] is False and res["failed"] > 0
