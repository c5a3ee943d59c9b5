"""Serving driver: batched generation / continuous batching demo, plus the
always-on sparse-solve service.

    PYTHONPATH=src python -m repro.launch.serve --arch paligemma-3b --smoke \
        --batch 4 --prompt-len 32 --gen 16

    # solve service: submit RHS against registered operators, drain the
    # continuous-batching tick loop
    PYTHONPATH=src python -m repro.launch.serve --solver --matrix lap2d_32 \
        --requests 12 --coalesce 8 --method pcg_tol --tol 1e-8

    # several resident operators in one process, round-robin traffic
    PYTHONPATH=src python -m repro.launch.serve --solver \
        --operators lap2d_32,banded_1k --requests 12

    # load generator: open-loop Poisson arrivals at 50 req/s
    PYTHONPATH=src python -m repro.launch.serve --solver --matrix lap2d_32 \
        --load-gen open --rate 50 --requests 40
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _solver_main(args) -> int:
    """Serve sparse solves through :class:`repro.serve.SolveService`:
    register one operator per ``--operators`` name (or ``--matrix``),
    submit ``--requests`` RHS round-robin, and drain the continuous-
    batching tick loop -- or hand the service to the load generator
    (``--load-gen open|closed``)."""
    jax.config.update("jax_enable_x64", True)  # f64 engine, like the benches

    from ..core.plan import SolveSpec
    from ..data.matrices import suite
    from ..obs import start_metrics_server
    from ..serve import SolveService, run_load

    metrics_srv = None
    if args.metrics_port is not None:
        # scrape target up BEFORE any work so a Prometheus poller pointed
        # here sees the whole run (queue depth, chunk/tick histograms,
        # plan-cache counters); /metrics.json and /trace.json ride along
        metrics_srv = start_metrics_server(port=args.metrics_port)
        print(f"metrics: {metrics_srv.url}")

    mats = suite("small")
    mats.update(suite("large"))
    names = [s for s in (args.operators.split(",") if args.operators
                         else [args.matrix]) if s]
    for name in names:
        if name not in mats:
            raise SystemExit(
                f"unknown matrix {name!r}; available: {', '.join(sorted(mats))}"
            )

    mesh = None
    if args.mesh_shape:
        from .mesh import make_mesh
        shape = tuple(int(x) for x in args.mesh_shape.split("x"))
        if len(shape) != 2:
            raise SystemExit("--mesh-shape must be RxC, e.g. 2x2")
        mesh = make_mesh(shape, ("data", "model"))

    # one frozen spec drives every operator's warm pool; the service builds
    # per-(operator, bucket) plans from it -- dispatch resolves at plan
    # construction, never per tick
    spec = SolveSpec(method=args.method, iters=args.iters, tol=args.tol,
                     layout=args.layout)
    svc = SolveService(max_batch=args.coalesce, chunk=args.chunk)
    for name in names:
        svc.register_operator(name, mats[name], spec=spec,
                              precond=args.precond, dtype=np.float64,
                              layout=args.layout, reorder=args.reorder,
                              mesh=mesh)

    import scipy.sparse as sp
    rng = np.random.default_rng(0)

    if args.load_gen:
        n0 = mats[names[0]].shape[0]
        rhs = rng.standard_normal((min(args.requests, 32), n0))
        res = run_load(svc, lambda i: rhs[i % rhs.shape[0]],
                       operator=names[0], mode=args.load_gen,
                       requests=args.requests, rate=args.rate,
                       concurrency=args.concurrency)
        res.update({"matrix": names[0], "n": n0, "method": args.method})
        print(json.dumps(res, indent=1))
        if metrics_srv is not None:
            metrics_srv.close()
        return 0

    x_true, ids = {}, []
    for i in range(args.requests):
        name = names[i % len(names)]
        m = mats[name]
        a = sp.csr_matrix((m.data, m.indices, m.indptr), shape=m.shape)
        xt = rng.standard_normal(m.shape[0])
        rid = svc.submit(a @ xt, name)
        x_true[rid] = xt
        ids.append(rid)

    t0 = time.perf_counter()
    done = svc.drain()
    dt = time.perf_counter() - t0
    err = max(float(np.abs(done[rid].x - x_true[rid]).max()) for rid in ids)
    out = {
        "operators": names, "requests": args.requests,
        "coalesce": args.coalesce, "chunk": args.chunk,
        "ticks": svc.stats["ticks"], "chunks": svc.stats["chunks"],
        "rebuckets": svc.stats["rebuckets"],
        "bucket_plans": svc.stats["plans"],
        "resident_bytes": svc.resident_bytes(),
        "wall_s": round(dt, 3),
        "solves_per_s": round(args.requests / dt, 2),
        "verify_maxerr": err,
    }
    if args.method.endswith("tol"):
        its = [done[rid].iters for rid in ids]
        out["tol"] = args.tol
        out["iters_mean"] = round(float(np.mean(its)), 2)
        out["iters_max"] = int(np.max(its))
    print(json.dumps(out, indent=1))
    if metrics_srv is not None:
        metrics_srv.close()
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--slots", action="store_true",
                    help="exercise the SlotServer continuous-batching path")
    # sparse-solver serving path
    ap.add_argument("--solver", action="store_true",
                    help="serve sparse solves (request-coalescing batched path)")
    ap.add_argument("--matrix", default="lap2d_32")
    ap.add_argument("--operators", default="",
                    help="comma-separated suite matrices to register as "
                         "resident operators (overrides --matrix)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--coalesce", type=int, default=8,
                    help="max RHS coalesced into one batched solve")
    ap.add_argument("--chunk", type=int, default=25,
                    help="iterations per continuous-batching chunk")
    ap.add_argument("--load-gen", default="", choices=("", "open", "closed"),
                    help="run the load generator instead of a fixed drain")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="open-loop offered load, requests/second")
    ap.add_argument("--concurrency", type=int, default=4,
                    help="closed-loop client population")
    ap.add_argument("--method", default="pcg_tol",
                    help="pcg_tol (tolerance-stopped) | pcg | cg | ...")
    ap.add_argument("--precond", default="jacobi")
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--tol", type=float, default=1e-8,
                    help="relative residual target for --method pcg_tol")
    ap.add_argument("--mesh-shape", default="",
                    help="e.g. 2x2 -- empty = single device")
    ap.add_argument("--layout", default="auto",
                    choices=("auto", "halo", "dense"),
                    help="distributed comm layout (see launch.solve)")
    ap.add_argument("--reorder", default="none", choices=("none", "rcm"),
                    help="bandwidth-reducing RCM reordering")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="expose Prometheus /metrics (+ /metrics.json, "
                         "/trace.json) on this port for the run; 0 picks "
                         "an ephemeral port (printed at startup)")
    args = ap.parse_args(argv)
    from .compile_cache import enable as enable_compile_cache
    enable_compile_cache()

    if args.solver:
        return _solver_main(args)
    if args.arch is None:
        ap.error("--arch is required unless --solver is given")

    from ..configs import get, get_smoke
    from ..models import model as M
    from ..serve import SlotServer, generate

    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab_size, size=(args.batch, args.prompt_len))

    t0 = time.perf_counter()
    out = generate(params, cfg, jnp.asarray(prompts, jnp.int32), steps=args.gen)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print("generated:", np.asarray(out)[:, :8], "...")
    result = {
        "arch": cfg.name, "batch": args.batch, "gen": args.gen,
        "wall_s": round(dt, 3),
        "tokens_per_s": round(args.batch * args.gen / dt, 1),
    }

    if args.slots:
        srv = SlotServer(params, cfg, batch_slots=args.batch,
                         max_len=args.prompt_len + args.gen + 8)
        ids = [srv.submit(prompts[i], args.gen) for i in range(args.batch)]
        done = {}
        while len(done) < len(ids):
            done.update(srv.step())
        result["slot_server_completed"] = len(done)

    print(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
