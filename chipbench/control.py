#!/usr/bin/env python
"""Readings that the limits of ``correct`` are set from, on the chip.

    python chipbench/control.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --seconds 5 [--control-rhs 2]

Sets the cell up once.  For each of ``--seeds`` it runs the cell's own
window for ``--seconds`` (the timed path, at the cell's size and load) and
checks every answer as a run does: the largest true residual over the
seeds is the lower reading.  Then, with the program's state freed, for
each of ``--control-seeds`` it puts the control (``reference.py``:
Jacobi-PCG one precision below the configuration's) in the program's
place on the first ``--control-rhs`` right-hand sides that seed's run
solves, and judges its answers with the harness's own check, which has
to find them not correct; the smallest residual is the upper reading.
The control runs at most twice the program's largest iteration count, or
``--control-max-iters`` where no program seeds are given (the control is
not sharded, so a multi-chip cell's control runs on one chip).  One JSON
line per reading, then a summary line.  It exits 2 without a TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def _ints(s: str) -> list[int]:
    return [int(v) for v in s.split(",") if v]


def program_readings(cell, seeds, seconds, emit) -> tuple[float, int]:
    from chipbench import harness

    state = cell.loop.setup(cell)
    worst, iters = 0.0, 0
    for seed in seeds:
        pool = cell.rhs.make(cell.cfg, cell.operator,
                             np.random.default_rng([seed, 0]),
                             int(cell.traffic["pool"]))
        inp = cell.loop.inputs(cell, pool, np.random.default_rng([seed, 1]),
                               seconds)
        sample = harness.Sample(harness.CHECK_SAMPLE,
                                np.random.default_rng([seed, 2]))
        w = cell.loop.window(cell, state, pool, inp, seconds,
                             harness.Profiler(None, False), sample)
        v = harness.check(_run(cell, seed, w, pool))
        r = v["checks"]["max_rel_residual"]["value"]
        its = max((a.iters for a in w.answers), default=0)
        emit({"kind": "program", "seed": seed, "correct": v["correct"],
              "max_rel_residual": r,
              "max_recursive_rel_residual":
                  v["checks"]["max_recursive_rel_residual"]["value"],
              "answers": len(w.answers), "checked": v["checked"],
              "failed": v["failed"], "max_iters": its})
        worst, iters = max(worst, r), max(iters, its)
    del state
    gc.collect()
    return worst, iters


def _run(cell, seed, window, pool):
    from chipbench import harness

    return harness.Run(cell=cell, seed=seed, seconds=window.seconds,
                       setup_s=0.0, window=window, pool=pool, device={},
                       work={}, peaks={})


def control_readings(cell, seeds, per_seed, max_iters, emit) -> float:
    from chipbench import harness, reference

    solve = reference.control_solver(cell.cfg, cell.operator, max_iters)
    matvec = cell.operator.reference_matvec(cell.cfg)
    best = float("inf")
    for seed in seeds:
        pool = cell.rhs.make(cell.cfg, cell.operator,
                             np.random.default_rng([seed, 0]),
                             int(cell.traffic["pool"]))
        order = cell.loop.inputs(cell, pool,
                                 np.random.default_rng([seed, 1]), 1.0)
        answers = []
        for i in order[:per_seed]:
            t = time.perf_counter()
            x, k, rnorm = solve(pool[int(i)])
            x = np.asarray(x, np.float64)
            t_done = time.perf_counter()
            r = reference.rel_residual(matvec, pool[int(i)], x)
            emit({"kind": "control", "seed": seed, "rhs": int(i),
                  "rel_residual": r, "iters": int(k),
                  "seconds": t_done - t})
            best = min(best, r if np.isfinite(r) else float("inf"))
            answers.append(harness.Answer(
                b=int(i), t_due=t, t_done=t_done, iters=int(k),
                status="converged" if int(k) < max_iters else "max_iters",
                rnorm=float(rnorm), x=x))
        w = harness.Window(t0=answers[0].t_due, t1=answers[-1].t_done,
                           answers=answers)
        v = harness.check(_run(cell, seed, w, pool))
        emit({"kind": "control_check", "seed": seed,
              "correct": v["correct"], "checks": v["checks"]})
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control-rhs", type=int, default=2)
    ap.add_argument("--control-max-iters", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from chipbench import harness
    from repro.launch import compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; nothing was run", file=sys.stderr)
        return 2
    compile_cache.enable()
    cell = harness.resolve(args.workload)

    def emit(d):
        print(json.dumps(d), flush=True)

    lower, iters = (program_readings(cell, args.seeds, args.seconds, emit)
                    if args.seeds else (0.0, 0))
    cap = 2 * iters if iters > 0 else args.control_max_iters
    upper = control_readings(cell, args.control_seeds, args.control_rhs,
                             cap, emit)
    emit({"kind": "summary", "workload": args.workload,
          "lower": lower, "upper": upper,
          "limit": harness.limit(cell),
          "separation": upper / lower if lower > 0 else None})
    return 0 if cap > 0 else 2


if __name__ == "__main__":
    sys.exit(main())
