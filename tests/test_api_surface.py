"""Public-API surface snapshot for ``repro.core`` and ``repro.serve``.

The plan/execute redesign froze the solve surface: specs in, compiled
plans out, new methods/preconditioners through the registry.  This test
pins the exported names and the parameter lists of the public callables so
*any* future drift -- a renamed export, a widened ``solve()`` signature, a
new positional parameter -- fails review explicitly instead of slipping
through.  When a change is deliberate, update this snapshot AND the README
API/migration tables in the same commit.

Runs in the ordinary fast test matrix (no markers), so every CI job
enforces it.
"""

import inspect

import repro.core as core
import repro.obs as obs
import repro.serve as serve

# -- exported names -----------------------------------------------------------

CORE_EXPORTS = {
    # formats
    "CSR", "ELL", "BCSR",
    # communication plans (structure-compiled halo schedules)
    "CommPlan",
    # engine + plan/execute API
    "AzulEngine", "SolveSpec", "SolvePlan", "PlanCache", "chunk_spec",
    # registry
    "SolverDef", "PrecondDef",
    "register_solver", "register_precond",
    "get_solver", "get_precond",
    "solver_names", "precond_names",
}

SERVE_EXPORTS = {
    # the always-on service (management plane) and its load generator
    "SolveService", "OperatorInfo", "run_load",
    # request/response records
    "SolveOutcome", "SolveRequest", "SolveRequestError",
    # deprecated coalescer (thin shim over SolveService)
    "SolveServer",
    # LM generation demo
    "generate", "SlotServer",
}

OBS_EXPORTS = {
    # the injectable process clock (FakeClock lives on obs.clock)
    "clock",
    # metrics: registry + primitives + the kill switch
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "log_buckets", "DEFAULT_LATENCY_BUCKETS",
    "enabled", "set_enabled", "disabled",
    # tracing: span ring buffer + Chrome export + jax.profiler bridge
    "Span", "Tracer", "TRACER", "span", "set_jax_bridge",
    # exposition: Prometheus text, JSON snapshot, /metrics HTTP server
    "render_prometheus", "snapshot", "MetricsServer", "start_metrics_server",
}

# -- callable signatures (parameter name tuples) ------------------------------

SIGNATURES = {
    "core.AzulEngine.__init__": (
        "self", "a", "mesh", "mode", "row_axes", "col_axes", "precond",
        "balance", "dtype", "row_pad", "width_pad", "fused", "layout",
        "reorder", "format",
    ),
    "core.AzulEngine.plan": ("self", "spec", "kwargs"),
    "core.AzulEngine.solve": (                    # deprecated shim, frozen
        "self", "b", "method", "iters", "x0", "fused", "tol", "max_iters",
    ),
    "core.AzulEngine.spmv": ("self", "x"),
    "core.AzulEngine.substrate_kind": ("self", "method", "fused"),
    "core.AzulEngine.build_sptrsv": ("self", "l_csr"),
    "core.AzulEngine.to_device_vec": ("self", "v"),
    "core.AzulEngine.from_device_vec": ("self", "v"),
    "core.SolveSpec.__init__": (
        "self", "method", "precond", "iters", "tol", "max_iters", "batch",
        "fused", "layout", "reorder", "injectable", "format",
    ),
    "core.SolvePlan.__call__": ("self", "b", "x0", "vals"),
    "core.PlanCache.get": ("self", "spec", "build", "env"),
    "core.register_solver": ("sdef",),
    "core.register_precond": ("pdef",),
    "core.get_solver": ("name",),
    "core.get_precond": ("name",),
    "core.chunk_spec": ("spec", "chunk", "batch", "fixed_length"),
    "core.AzulEngine.device_bytes": ("self",),
    "serve.SolveService.__init__": (
        "self", "max_batch", "chunk", "queue_max", "memory_limit",
        "aging", "deadline_chunk", "timer",
    ),
    "serve.SolveService.register_operator": (
        "self", "name", "a", "engine", "spec", "method", "iters", "tol",
        "max_iters", "precond", "dtype", "layout", "reorder", "mesh",
        "max_batch", "chunk",
    ),
    "serve.SolveService.submit": (
        "self", "b", "operator", "tol", "max_iters", "deadline", "priority",
    ),
    "serve.SolveService.tick": ("self",),
    "serve.SolveService.drain": ("self",),
    "serve.SolveService.plan_for": ("self", "operator", "k_pad", "flavor"),
    "serve.SolveService.unregister_operator": ("self", "name"),
    "serve.SolveService.operators": ("self",),
    "serve.run_load": (
        "service", "make_rhs", "operator", "mode", "requests", "rate",
        "concurrency", "seed", "tol", "max_iters",
    ),
    "serve.SolveServer.__init__": (
        "self", "engine", "max_batch", "method", "iters", "tol",
        "max_iters", "spec", "deadline_chunk", "timer",
    ),
    "serve.SolveServer.submit": ("self", "b", "deadline"),
    "serve.SolveServer.step": ("self",),
    "serve.SolveServer.drain": ("self",),
    "serve.SolveServer.plan_for": ("self", "k_pad"),
    "obs.Registry.counter": ("self", "name", "help", "labelnames"),
    "obs.Registry.gauge": ("self", "name", "help", "labelnames"),
    "obs.Registry.histogram": ("self", "name", "help", "labelnames",
                               "buckets"),
    "obs.span": ("name", "kind", "attrs"),
    "obs.render_prometheus": ("registry",),
    "obs.snapshot": ("registry",),
    "obs.start_metrics_server": ("port", "host", "registry", "tracer"),
    "obs.clock.override": ("clock",),
}

_MODULES = {"core": core, "serve": serve, "obs": obs}


def _resolve(path: str):
    parts = path.split(".")
    obj = _MODULES[parts[0]]
    for p in parts[1:]:
        obj = getattr(obj, p)
    return obj


def test_core_exports_exact():
    assert set(core.__all__) == CORE_EXPORTS
    for name in CORE_EXPORTS:
        assert hasattr(core, name), f"repro.core.{name} missing"


def test_serve_exports_exact():
    assert set(serve.__all__) == SERVE_EXPORTS
    for name in SERVE_EXPORTS:
        assert hasattr(serve, name), f"repro.serve.{name} missing"


def test_obs_exports_exact():
    assert set(obs.__all__) == OBS_EXPORTS
    for name in OBS_EXPORTS:
        assert hasattr(obs, name), f"repro.obs.{name} missing"


def test_public_signatures_frozen():
    drift = []
    for path, want in SIGNATURES.items():
        got = tuple(inspect.signature(_resolve(path)).parameters)
        if got != want:
            drift.append(f"{path}: {want} -> {got}")
    assert not drift, "public API signature drift:\n" + "\n".join(drift)


def test_builtin_registry_population():
    assert {"cg", "pcg", "pcg_pipelined", "pcg_pipelined_tol", "pcg_tol",
            "jacobi"} <= set(core.solver_names())
    assert {"identity", "jacobi", "block_ic0"} <= set(core.precond_names())
    # capability metadata the engine dispatch relies on
    assert core.get_solver("pcg_tol").tolerance is True
    assert core.get_solver("pcg").tolerance is False
    assert core.get_solver("pcg_pipelined_tol").tolerance is True
    assert core.get_precond("none").name == "identity"   # alias resolution
    assert core.get_solver("pcg_pipe").name == "pcg_pipelined"  # PR 6 alias
    assert core.get_precond("block_ic0").fused_local_kind == "fused_ic0"
    # halo comm-plan capability: every substrate-phrased method supports
    # it; the pipelined variants additionally lower the split
    # communication-hiding matvec (comm_overlap)
    assert {"identity", "jacobi", "block_ic0"} <= set(
        core.get_solver("pcg").halo_dist)
    assert core.get_solver("pcg_tol").halo_dist == core.get_solver("pcg").halo_dist
    assert core.get_solver("pcg_pipelined").halo_dist == core.get_solver(
        "pcg").halo_dist
    assert core.get_solver("pcg_pipelined").comm_overlap is True
    assert core.get_solver("pcg").comm_overlap is False
    assert core.get_precond("block_ic0").fused_local_needs_kernels is True


def test_solvespec_is_frozen_and_hashable():
    spec = core.SolveSpec(method="pcg", iters=10)
    assert spec == core.SolveSpec(method="pcg", iters=10)
    assert hash(spec) == hash(core.SolveSpec(method="pcg", iters=10))
    try:
        spec.iters = 11
        raise AssertionError("SolveSpec must be frozen")
    except AttributeError:
        pass
