"""Sparse-solver driver: the paper's workload end to end.

    PYTHONPATH=src python -m repro.launch.solve --matrix lap2d_32 \
        --method pcg --precond block_ic0 --iters 100

    # the headline tolerance-mode config -- IC(0) PCG solved to 1e-8 (an
    # f64 target: pass --dtype float64):
    PYTHONPATH=src python -m repro.launch.solve --matrix lap2d_32 \
        --method pcg_tol --precond block_ic0 --tol 1e-8 --dtype float64

Add --mesh-shape 2x2 (any grid whose product <= device count) to run the
distributed AzulEngine; on the CPU container use
XLA_FLAGS=--xla_force_host_platform_device_count=N.

Matrices: the suite names (``data.matrices.suite``), generated Laplacians
of any size (``lap2d_<N>``: n = N**2, ``lap3d_<N>``: n = N**3), and
matrix-free ``stencil:`` operators.  ``--dtype`` picks the engine precision
(float32 by default -- the TPU kernels run f32; float64 turns on x64 and is
for CPU verification runs):

    # a million-row stored operator, f32, solved to 1e-5:
    PYTHONPATH=src python -m repro.launch.solve --matrix lap2d_1024 \
        --method pcg_tol --precond jacobi --tol 1e-5 --max-iters 6000

Storage formats and matrix-free operators:

    # per-matrix format autotuner (skewed rows -> HYB beats padded ELL):
    PYTHONPATH=src python -m repro.launch.solve --matrix skew_1k \
        --method pcg_tol --tol 1e-8 --format auto --dtype float64

    # million-row matrix-free solve -- no assembled CSR is ever built:
    PYTHONPATH=src python -m repro.launch.solve --matrix stencil:lap2d_1024 \
        --method pcg_tol --tol 1e-6 --precond jacobi

Fault-tolerance demo flags:

    # inject a NaN into the streamed values at iteration 15 and let the
    # chunked restart driver detect it, roll back, and reconverge:
    PYTHONPATH=src python -m repro.launch.solve --matrix lap2d_32 \
        --method pcg_tol --max-iters 400 --inject nan --inject-at 15 \
        --ft-chunk 20 --dtype float64

The in-loop numerical health guards are always on, in the one loop of
each CG recurrence (a fixed ``--iters`` count and a ``--tol`` are two
stopping rules of that loop).  Every run reports the structured solve
``status`` (converged | maxiter | breakdown | diverged | stagnated |
unguarded, the last for jacobi) in the JSON output, with the true relative
residual ``||b - A x|| / ||b||`` computed on the host in float64, the
plan's compile and solve seconds (host clock), and the number of Pallas
TPU custom calls in the compiled program.
"""

from __future__ import annotations

import argparse
import json

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--matrix", default="lap2d_32",
                    help="suite name, lap2d_<N>, lap3d_<N>, or "
                         "stencil:lap2d_<N> / stencil:lap3d_<N>")
    ap.add_argument("--dtype", default="float32",
                    choices=("float32", "float64"),
                    help="engine precision (float64 enables x64)")
    ap.add_argument("--method", default="pcg",
                    choices=("pcg", "pcg_tol", "pcg_pipelined",
                             "pcg_pipelined_tol", "pcg_pipe", "cg",
                             "jacobi"))   # pcg_pipe = pcg_pipelined alias
    ap.add_argument("--precond", default="jacobi",
                    choices=("jacobi", "block_ic0", "none"))
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--tol", type=float, default=1e-8,
                    help="relative residual target (pcg_tol)")
    ap.add_argument("--max-iters", type=int, default=None,
                    help="iteration cap for pcg_tol (default: --iters)")
    ap.add_argument("--fused", default="auto", choices=("auto", "on", "off"),
                    help="fused-substrate knob (auto = on where supported)")
    ap.add_argument("--format", default="auto", dest="fmt",
                    choices=("auto", "ell", "sell", "hyb", "bcsr"),
                    help="operator storage format (auto = per-matrix "
                         "autotuner; local mode only -- distributed plans "
                         "stream padded ELL)")
    ap.add_argument("--mode", default="2d", choices=("1d", "2d"))
    ap.add_argument("--mesh-shape", default="",
                    help="e.g. 2x2 -- empty = single device")
    ap.add_argument("--layout", default="auto",
                    choices=("auto", "halo", "dense"),
                    help="distributed comm layout: halo = the compiled "
                         "pull schedule, dense = blanket collectives, "
                         "auto = halo where it moves fewer bytes")
    ap.add_argument("--reorder", default="none", choices=("none", "rcm"),
                    help="bandwidth-reducing RCM reordering (shrinks halos)")
    ap.add_argument("--balance", default="nnz", choices=("nnz", "rows"),
                    help="row-block load balance (nnz = prefix-sum splits)")
    ap.add_argument("--inject", default="",
                    choices=("", "nan", "bitflip", "halo_drop",
                             "halo_perturb", "delay"),
                    help="inject a deterministic fault (repro.ft.inject) "
                         "and recover via the chunked restart driver")
    ap.add_argument("--inject-at", type=int, default=10,
                    help="global solver iteration the fault fires at")
    ap.add_argument("--inject-seed", type=int, default=0)
    ap.add_argument("--ft-chunk", type=int, default=25,
                    help="restart-driver chunk size (iterations between "
                         "verify/checkpoint points)")
    ap.add_argument("--checkpoint-dir", default="",
                    help="persist solver state every chunk; reruns resume")
    args = ap.parse_args(argv)

    import jax
    if args.dtype == "float64":
        # without x64 jax silently downcasts, and a --tol 1e-8 solve floors
        # out at the f32 rounding level (maxiter/stagnated, not converged)
        jax.config.update("jax_enable_x64", True)
    from .compile_cache import enable as enable_compile_cache
    enable_compile_cache()

    from ..core.engine import AzulEngine
    from ..core.plan import SolveSpec
    from ..obs import clock

    if args.matrix.startswith("stencil:"):
        # matrix-free operator, e.g. stencil:lap2d_1024 or stencil:lap3d_64
        # -- no assembled CSR, O(n) memory, so n can reach millions
        from ..core.stencil import (lap2d_stencil, lap3d_stencil,
                                    stencil_matvec_host)
        kind, _, size = args.matrix[len("stencil:"):].partition("_")
        builder = {"lap2d": lap2d_stencil, "lap3d": lap3d_stencil}[kind]
        m = builder(int(size))
        nnz = m.nnz_equiv
        host_matvec = lambda v: stencil_matvec_host(m, v)
    else:
        import scipy.sparse as sp
        from ..data.matrices import named
        m = named(args.matrix)
        nnz = m.nnz
        a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
        host_matvec = lambda v: a @ v

    mesh = None
    if args.mesh_shape:
        from .mesh import make_mesh
        shape = tuple(int(x) for x in args.mesh_shape.split("x"))
        mesh = make_mesh(shape, ("data", "model")[: len(shape)])

    rng = np.random.default_rng(0)
    x_true = rng.standard_normal(m.shape[0])
    b = host_matvec(x_true)             # float64 on the host
    fused = {"auto": "auto", "on": True, "off": False}[args.fused]
    eng = AzulEngine(m, mesh=mesh, mode=args.mode, precond=args.precond,
                     balance=args.balance, dtype=np.dtype(args.dtype),
                     fused=fused, layout=args.layout, reorder=args.reorder,
                     format=args.fmt)

    spec = SolveSpec(method=args.method, iters=args.iters,
                     tol=args.tol, max_iters=args.max_iters,
                     fused=fused, layout=args.layout)

    if args.inject:
        # fault-injected solve through the chunked restart driver: detect,
        # roll back to the last verified state, reconverge
        from ..ft import FaultInjector, FaultSpec, SolveRestartManager
        from ..ft.straggler import StepTimer
        mgr = SolveRestartManager(
            eng, spec, chunk=args.ft_chunk,
            checkpoint_dir=args.checkpoint_dir or None, timer=StepTimer())
        inj = FaultInjector(eng, FaultSpec(
            kind=args.inject, iteration=args.inject_at,
            seed=args.inject_seed, delay_s=0.5))
        rep = mgr.solve(b, injector=inj)
        x = rep.x
        rel = float(np.linalg.norm(x - x_true) / np.linalg.norm(x_true))
        out = {
            "matrix": args.matrix, "n": m.shape[0], "nnz": nnz,
            "method": args.method, "precond": args.precond,
            "mode": eng.mode, "injected": args.inject,
            "injected_at": args.inject_at,
            "status": rep.status, "iterations": rep.iterations,
            "chunks": rep.chunks, "restarts": rep.restarts,
            "faults": rep.faults, "resumed_from": rep.resumed_from,
            "straggler_chunks": rep.straggler_chunks,
            "rel_residual": rep.rel_residual, "rel_error": rel,
        }
        print(json.dumps(out, indent=1))
        return 0 if rep.status == "converged" else 1

    # plan/execute: lower and compile the spec once, run the compiled plan
    plan = eng.plan(spec)
    t0 = clock.now()
    compiled = plan.compile()
    compile_s = clock.now() - t0
    t0 = clock.now()
    x, norms = plan(b)                   # returns host arrays: synchronous
    solve_s = clock.now() - t0
    rel = float(np.linalg.norm(x - x_true) / np.linalg.norm(x_true))
    true_rel = float(np.linalg.norm(b - host_matvec(np.asarray(x, np.float64)))
                     / np.linalg.norm(b))
    out = {
        "matrix": args.matrix, "n": m.shape[0], "nnz": nnz,
        "dtype": args.dtype,
        "method": args.method, "precond": args.precond,
        "iters": args.iters, "mode": eng.mode,
        "substrate": plan.info["substrate"],
        "fused": bool(plan.spec.fused),
        "format": plan.info["format"],
        "layout": plan.info["layout"],
        "reorder": plan.info["reorder"],
        "final_residual": float(norms[-1] if norms.ndim == 1 else norms[-1, 0]),
        "true_rel_residual": true_rel,
        "rel_error": rel,
        "status": plan.last_status_names,
        "bad_iter": int(np.asarray(plan.last_bad_iter)),
        "compile_s": compile_s,
        "solve_s": solve_s,
        "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
    }
    if "noc" in plan.info:
        out["noc"] = plan.info["noc"]
    if plan.spec.tol is not None:
        out["tol"] = plan.spec.tol
        out["iters_run"] = int(np.asarray(plan.last_iters))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
