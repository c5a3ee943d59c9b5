#!/usr/bin/env python
"""Smoke test of the solver's main path on a TPU, at about a million rows.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one problem split over a 2x2 mesh

One chip runs three phases, each through the entry point a user calls:

  stored   ``repro.launch.solve`` on lap2d_1024 (n = 1,048,576, padded ELL
           on the device), pcg_tol + Jacobi in float32 to tol 1e-5;
  stencil  the same CLI on the matrix-free stencil:lap3d_128
           (n = 2,097,152);
  service  ``SolveService(max_batch=8)`` serving 8 seeded right-hand sides
           against laplacian_3d(100) (n = 10**6, float32).

``--four-chips`` runs only the distributed solve of lap2d_1024 on a 2x2
mesh (halo layout) and the one-chip solve it is compared with.

Every solve must converge and its true relative residual ||b - A x|| / ||b||,
computed on the host in float64, must be at most ``RESIDUAL_BOUND``.  The
fused plans must run compiled Pallas kernels (``tpu_custom_call`` in the
compiled program), the service must degrade no batch and retrace no plan.
Timings are host-clock seconds around synchronous calls: informational,
not a benchmark.

The script runs in one process and starts none.  It exits non-zero, before
any solve and with no result line, when JAX finds no TPU (or the ``repro``
package is not beside it), and non-zero on any failed check.  Its last
stdout line on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"
STORED = "lap2d_1024"           # n = 1,048,576
STENCIL = "stencil:lap3d_128"   # n = 2,097,152
SERVICE_GRID = 100              # laplacian_3d(100): n = 10**6
MESH_GRID = 1024                # laplacian_2d(1024) over the 2x2 mesh
TOL = 1e-5
MAX_ITERS = 8000
RESIDUAL_BOUND = 1e-4
SEED = 0


class CheckFailed(Exception):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def cli_solve(matrix: str) -> dict:
    """Run ``repro.launch.solve`` as a user would and return its report."""
    from repro.launch import solve

    argv = ["--matrix", matrix, "--method", "pcg_tol", "--precond", "jacobi",
            "--dtype", "float32", "--tol", str(TOL),
            "--max-iters", str(MAX_ITERS)]
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = solve.main(argv)
    check(rc == 0, f"{matrix}: solve CLI exited {rc}")
    rep = json.loads(buf.getvalue())
    log(f"[{matrix}] n={rep['n']} substrate={rep['substrate']} "
        f"format={rep['format']} status={rep['status']} "
        f"iters={rep['iters_run']} true_rel_residual={rep['true_rel_residual']} "
        f"tpu_custom_calls={rep['tpu_custom_calls']} "
        f"compile_s={rep['compile_s']} solve_s={rep['solve_s']}")
    check(rep["status"] == "converged", f"{matrix}: status {rep['status']}")
    check(rep["substrate"].startswith("fused"),
          f"{matrix}: substrate {rep['substrate']} is not fused")
    check(rep["tpu_custom_calls"] > 0,
          f"{matrix}: no Pallas kernel in the compiled plan")
    check(rep["true_rel_residual"] <= RESIDUAL_BOUND,
          f"{matrix}: true residual {rep['true_rel_residual']}")
    return rep


def serve_phase(grid: int = SERVICE_GRID) -> None:
    import scipy.sparse as sp

    from repro.data.matrices import laplacian_3d
    from repro.obs import clock
    from repro.serve import SolveService

    k = 8
    m = laplacian_3d(grid)
    a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
    svc = SolveService(max_batch=k)
    name = f"lap3d_{grid}"
    svc.register_operator(name, m, method="pcg_tol", tol=TOL,
                          max_iters=MAX_ITERS, precond="jacobi",
                          dtype=np.float32)
    t0 = clock.now()
    exe = svc.plan_for(name, k, "cb").compile()   # warm-up
    compile_s = clock.now() - t0
    check("tpu_custom_call" in exe.as_text(),
          "service: no Pallas kernel in the compiled chunk plan")
    rng = np.random.default_rng(SEED)
    bs = a @ rng.standard_normal((m.shape[0], k))
    rids = [svc.submit(bs[:, i]) for i in range(k)]
    t0 = clock.now()
    out = svc.drain()
    drain_s = clock.now() - t0
    res = [float(np.linalg.norm(bs[:, i] - a @ out[r].x.astype(np.float64))
                 / np.linalg.norm(bs[:, i])) for i, r in enumerate(rids)]
    statuses = [out[r].status for r in rids]
    log(f"[service {name}] n={m.shape[0]} requests={k} "
        f"statuses={statuses} iters={[out[r].iters for r in rids]} "
        f"max_true_rel_residual={max(res)} chunks={svc.stats['chunks']} "
        f"plans={svc.stats['plans']} "
        f"degraded_batches={svc.stats['degraded_batches']} "
        f"retraces={svc.retraces()} compile_s={compile_s} drain_s={drain_s}")
    check(all(s == "converged" for s in statuses), f"service: {statuses}")
    check(max(res) <= RESIDUAL_BOUND, f"service: true residuals {res}")
    check(svc.stats["degraded_batches"] == 0, "service: degraded batches")
    check(svc.retraces() == 0, "service: plans retraced after warm-up")


def four_chip_phase(grid: int = MESH_GRID) -> None:
    import jax
    import scipy.sparse as sp

    from repro.core.engine import AzulEngine
    from repro.core.plan import SolveSpec
    from repro.data.matrices import laplacian_2d
    from repro.launch.mesh import make_mesh
    from repro.obs import clock

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, need 4")
    m = laplacian_2d(grid)
    a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
    b = a @ np.random.default_rng(SEED).standard_normal(m.shape[0])
    spec = SolveSpec(method="pcg_tol", tol=TOL, max_iters=MAX_ITERS)
    mesh = make_mesh((2, 2), ("data", "model"))
    results = {}
    for name, eng in (
        ("2x2", AzulEngine(m, mesh=mesh, layout="halo", precond="jacobi",
                           dtype=np.float32)),
        ("1chip", AzulEngine(m, precond="jacobi", dtype=np.float32)),
    ):
        if eng.mesh is not None:
            held = {s.device for s in eng.vals.addressable_shards}
            log(f"[{name}] operator shards on devices {sorted(map(str, held))}")
            check(len(held) == 4, f"{name}: operator shards on {len(held)} devices")
        plan = eng.plan(spec)
        t0 = clock.now()
        exe = plan.compile()
        compile_s = clock.now() - t0
        t0 = clock.now()
        x, _ = plan(b)
        solve_s = clock.now() - t0
        x = np.asarray(x, np.float64)
        rel = float(np.linalg.norm(b - a @ x) / np.linalg.norm(b))
        iters = int(np.asarray(plan.last_iters))
        log(f"[{name}] substrate={plan.info['substrate']} "
            f"layout={plan.info['layout']} status={plan.last_status_names} "
            f"iters={iters} true_rel_residual={rel} "
            f"tpu_custom_calls={exe.as_text().count('tpu_custom_call')} "
            f"compile_s={compile_s} solve_s={solve_s}")
        check(plan.last_status_names == "converged",
              f"{name}: status {plan.last_status_names}")
        check(rel <= RESIDUAL_BOUND, f"{name}: true residual {rel}")
        check("tpu_custom_call" in exe.as_text(),
              f"{name}: no Pallas kernel in the compiled plan")
        results[name] = (x, iters)
    (x4, it4), (x1, it1) = results["2x2"], results["1chip"]
    # the solutions agree to the tolerance, measured in the residual norm
    # (the system's condition number makes ||x4 - x1|| the wrong yardstick)
    agree = float(np.linalg.norm(a @ (x4 - x1)) / np.linalg.norm(b))
    log(f"[compare] iters 2x2={it4} 1chip={it1} "
        f"||A(x4 - x1)||/||b||={agree} "
        f"||x4 - x1||/||x1||={np.linalg.norm(x4 - x1) / np.linalg.norm(x1)}")
    check(abs(it4 - it1) <= 0.02 * it1, f"iteration counts {it4} vs {it1}")
    check(agree <= RESIDUAL_BOUND, f"solutions differ by {agree}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh solve and its one-chip twin")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    log(f"device: {device}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU present (JAX found "
              f"{dev.platform!r} devices); nothing was run", file=sys.stderr)
        return 2
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repro package is missing ({SRC})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    try:
        if args.four_chips:
            four_chip_phase()
        else:
            cli_solve(STORED)
            cli_solve(STENCIL)
            serve_phase()
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
