"""Closed loop: one client runs single-RHS solves back to back.

Each solve is one ``SolvePlan.__call__`` from a host right-hand side to a
host x, converged to the configuration's rtol.  The solves cycle through
the run's RHS pool in an order drawn from the seed.  The window ends when
the solve that is running at ``--seconds`` completes, so no solve is cut.
Only the x of the answers the seeded ``sample`` holds are kept for the
check.

The configuration's ``mesh`` block, where present, spreads the operator
over the chips (``{"shape": [2, 2], "axes": ["data", "model"],
"layout": "halo"}``); without it the solve runs on one chip.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from chipbench.harness import Answer, Window


@dataclass
class State:
    engine: object
    plan: object


def setup(cell) -> State:
    from repro.core.engine import AzulEngine
    from repro.core.plan import SolveSpec

    cfg = cell.cfg
    sol = cfg["solver"]
    kw = {}
    mesh = cfg.get("mesh")
    if mesh:
        from repro.launch.mesh import make_mesh

        kw = {"mesh": make_mesh(tuple(mesh["shape"]), tuple(mesh["axes"])),
              "layout": mesh["layout"]}
    engine = AzulEngine(cell.operator.program_operator(cfg),
                        precond=sol["precond"], dtype=np.dtype(sol["dtype"]),
                        **kw)
    plan = engine.plan(SolveSpec(method=sol["method"], tol=sol["rtol"],
                                 max_iters=sol["max_iters"]))
    plan.compile()
    # one execution, on a zero RHS (no iteration): loads the program and
    # its operands onto the device before the window
    plan(np.zeros(engine.n, np.dtype(sol["dtype"])))
    return State(engine=engine, plan=plan)


def inputs(cell, pool: np.ndarray, rng: np.random.Generator,
           seconds: float) -> np.ndarray:
    """The order in which the solves visit the pool."""
    return rng.permutation(len(pool))


def window(cell, state: State, pool: np.ndarray, order: np.ndarray,
           seconds: float, prof, sample) -> Window:
    from repro.obs import clock

    plan = state.plan
    answers = []
    t0 = clock.now()
    now = t0
    while True:
        prof.boundary(now, t0, len(answers))
        if now - t0 >= seconds:
            break
        i = int(order[len(answers) % len(order)])
        with prof.phase("solve"):
            t_send = clock.now()
            x, norms = plan(pool[i])
            now = clock.now()
        answers.append(Answer(
            b=i, t_due=t_send, t_done=now, iters=int(plan.last_iters),
            status=str(plan.last_status_names), rnorm=float(norms[-1]),
            x=x))
        sample.offer(answers[-1])
    prof.finish()
    return Window(t0=t0, t1=now, answers=answers,
                  notes={"traces": plan.traces})
