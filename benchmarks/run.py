"""Benchmark harness -- one module per paper table/figure:

  bench_spmv     Fig. 1  (fraction of peak; interconnect-traffic reduction)
  bench_sptrsv   Fig. 2  (available parallelism per level + solve timing)
  bench_pcg      §IV     (end-to-end PCG convergence/throughput/verify)
  bench_kernels  §IV-D   (kernel functional verification matrix)

Prints ``name,us_per_call,derived`` CSV.  Roofline tables (dry-run derived)
live in EXPERIMENTS.md and are produced by repro.roofline, not here.

``--json BENCH_pcg.json`` additionally records the PCG perf trajectory
(fused vs unfused per-iteration timing, multi-RHS batch sweep, modeled
vector-HBM traffic, tolerance-mode convergence traces) as machine-readable
JSON -- the artifact CI archives per commit.  All solver benchmarks run
through the plan/execute API (``engine.plan(SolveSpec(...))``), so the
recorded trajectory is the trajectory of the production solve surface.  ``--smoke`` shrinks everything to tiny sizes/iterations so the
CI job (interpret-mode kernels on CPU) finishes in minutes:

    PYTHONPATH=src REPRO_KERNEL_MODE=interpret \
        python -m benchmarks.run --smoke --json BENCH_pcg.json
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback

import jax

jax.config.update("jax_enable_x64", True)  # solver benches verify at f64


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="",
                    help="write the bench_pcg payload (perf trajectory) here")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes/iters: CI smoke of the whole harness")
    ap.add_argument("--batch-sizes", default="1,4",
                    help="multi-RHS sweep for the JSON payload")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable as enable_compile_cache
    enable_compile_cache()

    from . import bench_kernels, bench_pcg, bench_spmv, bench_sptrsv

    ok = True
    print("name,us_per_call,derived")
    modules = (bench_kernels,) if args.smoke else (
        bench_spmv, bench_sptrsv, bench_pcg, bench_kernels,
    )
    for mod in modules:
        try:
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}")
        except Exception:
            ok = False
            traceback.print_exc()

    if args.json:
        try:
            iters = 5 if args.smoke else 60
            matrices = ("lap2d_32",) if args.smoke else (
                "lap2d_32", "banded_1k", "rspd_1k",
            )
            ks = [int(x) for x in args.batch_sizes.split(",") if x]
            if args.smoke:
                ks = ks[:2]
            frows, fused_payload = bench_pcg.run_fused_compare(
                iters=iters, matrices=matrices
            )
            brows, batch_payload = bench_pcg.run_batch_sweep(
                ks, iters=iters, matrices=matrices[:1]
            )
            tol_mats = matrices[:1] if args.smoke else ("lap2d_32", "banded_1k")
            trows, tol_payload = bench_pcg.run_tol_solves(
                max_iters=120 if args.smoke else 400, matrices=tol_mats
            )
            prows, pipe_payload = bench_pcg.run_pipelined_solves(
                max_iters=120 if args.smoke else 400, matrices=tol_mats
            )
            grows, guarded_payload = bench_pcg.run_guarded_solves(
                max_iters=120 if args.smoke else 400,
                matrices=matrices[:1]
            )
            # comm-plan traffic records are host-side NumPy (no devices,
            # milliseconds) -- full coverage even in the smoke run
            nrows, noc_payload = bench_pcg.run_noc_plans()
            from . import bench_serve
            srows, serving_payload = bench_serve.run_serving(
                smoke=args.smoke)
            orows, obs_payload = bench_pcg.run_observability(
                iters=30 if args.smoke else 60,
                repeats=3 if args.smoke else 5,
                matrix=matrices[0])
            # the format-portfolio A/B and the SpTRSV plan-scaling record
            # keep full settings even in smoke: both are the regression
            # gate's signal for ROADMAP item 4 (skewed solves are tiny, and
            # the ~1000-level trace-cost contrast IS the measurement)
            krows, formats_payload = bench_pcg.run_formats()
            xrows, scaling_payload = bench_pcg.run_plan_scaling()
            formats_payload += scaling_payload
            for name, us, derived in (frows + brows + trows + prows +
                                      grows + nrows + srows + orows +
                                      krows + xrows):
                print(f"{name},{us:.1f},{derived}")
            for e in tol_payload:
                # tolerance-mode convergence from the bounded trace ring
                print(f"# pcg_tol {e['matrix']}/{e['precond']} "
                      f"({e['iters_fused']} iters): {e['trace_spark']}")
            with open(args.json, "w") as f:
                json.dump(
                    bench_pcg.collect_json(fused_payload, batch_payload,
                                           tol_payload, noc_payload,
                                           pipe_payload, guarded_payload,
                                           serving_payload, obs_payload,
                                           formats_payload),
                    f, indent=1)
            print(f"# wrote {args.json}")
        except Exception:
            ok = False
            traceback.print_exc()
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
