"""Plan/execute API: a frozen ``SolveSpec`` lowered once into a compiled
``SolvePlan``.

The paper's Azul design separates static configuration (tile grid,
partition, task program) from streaming execution.  This module is that
split for the solve surface:

* :class:`SolveSpec` -- the frozen, hashable description of ONE solve
  configuration (method, tolerance/iteration budget, batch shape, fused
  knob).  ``AzulEngine.plan(spec)`` canonicalizes it against the engine
  (registry-validated method, engine preconditioner, resolved fused bool,
  tolerance fields nulled for fixed-iteration methods so equivalent specs
  collapse to one cache key) and lowers it ONCE.
* :class:`SolvePlan` -- the callable result: it owns its jitted program,
  the substrate selection, the device-resident operand buffers it closes
  over, and ``info`` (substrate kind, method, fused flag, batch).  Call it
  like a function: ``x, norms = plan(b)``.  Executing a plan never
  re-resolves dispatch and traces exactly once per (spec, shape) --
  ``plan.traces`` counts retraces so tests and the serving path can assert
  the steady state stays compile-free.
* :class:`PlanCache` -- the spec-keyed plan store ``AzulEngine`` holds,
  replacing the hand-rolled cache-key tuples the engine used to thread
  through ``solve(**knobs)``.  Keys are (canonical spec, kernel-dispatch
  mode), so a ``kernels.ops.backend_mode`` switch can never serve a stale
  program.

``AzulEngine.solve(**knobs)`` survives as a thin deprecated shim that
builds a spec and hits the cache -- bit-identical results, one
``DeprecationWarning`` per process.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, replace
from typing import Any, Callable

import jax
import numpy as np

from . import registry
from ..obs import REGISTRY as _OBS
from ..obs import clock as _clock
from ..obs import span as _span
from ..obs.scopes import SCOPES as _SCOPES

__all__ = ["SolveSpec", "SolvePlan", "PlanCache", "chunk_spec"]

# -- observability (host-side only: never enters a traced program) ----------
_M_CACHE_HITS = _OBS.counter(
    "repro_plan_cache_hits_total", "PlanCache lookups served by a warm plan")
_M_CACHE_MISSES = _OBS.counter(
    "repro_plan_cache_misses_total", "PlanCache lookups that lowered a plan")
_M_RETRACES = _OBS.counter(
    "repro_plan_retraces_total",
    "jit retraces beyond a plan's first trace (steady-state violations)")
_M_BUILD_S = _OBS.histogram(
    "repro_plan_build_seconds", "plan lowering wall time on a cache miss")
_M_EXECUTIONS = _OBS.counter(
    "repro_solve_executions_total", "SolvePlan executions", ("method",))
_M_COMPILE_S = _OBS.histogram(
    "repro_plan_compile_seconds",
    "SolvePlan.compile() wall time: trace + lower + compile (or cache load)",
    ("method",))
_M_SOLVE_S = _OBS.histogram(
    "repro_solve_seconds",
    "execution wall time: dispatch to outputs ready (block_until_ready)",
    ("method",))
_M_STAGE_S = _OBS.histogram(
    "repro_solve_stage_seconds",
    "host staging wall time per solve: 'in' builds and transfers the "
    "device operands, 'out' copies the outputs back and un-pads x",
    ("phase",))
_M_H2D = _OBS.counter(
    "repro_solve_h2d_bytes_total",
    "bytes of the arrays SolvePlan calls hand to the device")
_M_D2H = _OBS.counter(
    "repro_solve_d2h_bytes_total",
    "bytes of the arrays SolvePlan calls take back from the device")


@dataclass(frozen=True)
class SolveSpec:
    """Frozen description of one solve configuration.

    Fields (all participate in plan-cache identity after canonicalization):

    method     registered solver name (see ``registry.solver_names()``)
    precond    preconditioner name; None = the engine's (resolved at plan
               time -- a spec naming a different preconditioner than the
               engine was built for is rejected, the factorization is an
               engine-build-time decision)
    iters      fixed iteration count (fixed-iteration methods)
    tol        relative residual target (tolerance methods; None there
               means the 1e-8 default, and is forced to None on
               fixed-iteration methods so tol changes never recompile them)
    max_iters  iteration cap for tolerance methods (None -> ``iters``)
    batch      None for a single (n,) RHS, k for a stacked (k, n) batch --
               plans are shape-specialized, the serving path builds one
               plan per batch bucket
    fused      None/'auto' (engine knob decides) | True | False;
               canonicalized to the resolved bool
    layout     distributed communication layout: None/'auto' (engine knob,
               then the compiled comm plan decides), 'halo' (force the
               structure-compiled pull schedule) or 'dense' (blanket
               collectives); canonicalized to the resolved 'halo'/'dense'
               ('dense' on local engines -- no NoC)
    reorder    row/column reordering; None = the engine's (an engine-build
               decision like ``precond`` -- the matrix is repacked under
               the permutation, so a spec naming a different reorder than
               the engine was built with is rejected)
    guard      in-loop numerical health guards (breakdown/divergence/
               stagnation detection + structured per-RHS status; see
               ``core.solvers``).  Default True; forced to False for
               methods without the ``guarded`` capability.  ``guard=False``
               on a guarded method lowers the lean pre-guard loop (the
               A/B baseline the regression gate times against).
    injectable matrix values become a runtime program argument instead of
               a closed-over constant: ``plan(b, vals=...)`` can substitute
               a (corrupted) value buffer per call without retracing --
               the fault-injection surface (``repro.ft.inject``).  Default
               False (values stay baked in; marginally faster dispatch).
    format     operator storage format the plan streams from: None/'auto'
               (the engine's per-matrix autotuned decision -- see
               ``kernels.autotune.choose_format``) or an explicit 'ell' /
               'sell' / 'hyb' / 'bcsr' / 'stencil'; canonicalized to the
               resolved name.  Pinned modes reject conflicting requests:
               distributed and injectable plans are 'ell', stencil engines
               are 'stencil'.
    """

    method: str = "pcg"
    precond: str | None = None
    iters: int = 200
    tol: float | None = None
    max_iters: int | None = None
    batch: int | None = None
    fused: Any = "auto"
    layout: str | None = None
    reorder: str | None = None
    guard: bool = True
    injectable: bool = False
    format: str | None = None


def canonicalize(spec: SolveSpec, engine) -> SolveSpec:
    """Resolve a user spec against an engine into the canonical cache key.

    Canonicalization is what kills the stringly-typed cache-key fragility:
    tolerance fields are meaningful only on tolerance methods (elsewhere
    they are forced to None), ``iters`` is folded into ``max_iters`` for
    tolerance methods, method/precond aliases resolve to registry names
    (``pcg_pipe`` and ``pcg_pipelined`` share one plan), and the tri-state
    fused knob becomes the resolved bool.  Equal configurations therefore
    collapse to equal specs -- and one compiled plan."""
    sdef = registry.get_solver(spec.method)
    pdef = registry.get_precond(engine.precond)
    if spec.precond is not None:
        want = registry.get_precond(spec.precond)
        if want.name != pdef.name:
            raise ValueError(
                f"spec precond {want.name!r} != engine precond {pdef.name!r}"
                " (the preconditioner is factored at engine build time --"
                " build an engine with precond=...)"
            )
    if spec.batch is not None and (not isinstance(spec.batch, int)
                                   or spec.batch < 1):
        raise ValueError(f"batch must be None or a positive int, got {spec.batch!r}")
    if spec.batch is not None and not sdef.batched:
        raise ValueError(f"solver {sdef.name!r} does not support batched RHS")
    local = engine.mode == "local"
    # None and 'auto' defer to the engine-level knob (mirrors ``layout``
    # below); this is the ONE place the legacy kwargs surface's knob
    # resolution lives now -- ``engine.solve`` builds a spec and trusts it
    fused_knob = spec.fused
    if fused_knob in (None, "auto"):
        fused_knob = engine.fused
    fused = registry.resolve_fused(sdef, pdef, local, fused_knob)
    if spec.reorder is not None and spec.reorder != engine.reorder:
        raise ValueError(
            f"spec reorder {spec.reorder!r} != engine reorder "
            f"{engine.reorder!r} (the matrix is repacked under the "
            "permutation at engine build time -- build an engine with "
            "reorder=...)"
        )
    # None and 'auto' both defer to the engine-level knob (an engine pinned
    # to 'dense'/'halo' stays pinned); only then does the compiled comm
    # plan decide profitability
    layout_knob = spec.layout
    if layout_knob in (None, "auto"):
        layout_knob = engine.layout
    layout = registry.resolve_layout(
        sdef, pdef, local, layout_knob,
        halo_profitable=engine.comm_plan is not None
        and engine.comm_plan.use_halo,
    )
    if sdef.tolerance:
        tol = 1e-8 if spec.tol is None else float(spec.tol)
        max_iters = spec.iters if spec.max_iters is None else int(spec.max_iters)
        iters = max_iters          # one budget field: iters mirrors the cap
    else:
        tol, max_iters, iters = None, None, int(spec.iters)
    if spec.guard not in (True, False):
        raise ValueError(f"guard must be True or False, got {spec.guard!r}")
    if spec.injectable not in (True, False):
        raise ValueError(
            f"injectable must be True or False, got {spec.injectable!r}")
    guard = bool(spec.guard) and sdef.guarded
    # None and 'auto' defer to the engine-level format knob, which (when
    # itself 'auto') resolved to the per-matrix autotuned decision at
    # engine build; pinned modes (dist/injectable/stencil) force theirs
    fmt_knob = spec.format
    if fmt_knob in (None, "auto"):
        fmt_knob = getattr(engine, "format", "auto")
        # an engine-level format knob yields to modes that pin the format
        # (injectable plans are ELL by construction); only a spec-level
        # explicit request conflicts loudly
        if fmt_knob == "auto" or spec.injectable:
            fmt_knob = None
    fmt = registry.resolve_format(
        sdef, local, fmt_knob,
        engine_choice=getattr(engine, "format_choice", "ell"),
        stencil=getattr(engine, "stencil", None) is not None,
        injectable=bool(spec.injectable),
    )
    return replace(spec, method=sdef.name, precond=pdef.name, iters=iters,
                   tol=tol, max_iters=max_iters, fused=fused, layout=layout,
                   reorder=engine.reorder, guard=guard,
                   injectable=bool(spec.injectable), format=fmt)


def chunk_spec(spec: SolveSpec, chunk: int, batch: int | None = None,
               fixed_length: bool = True) -> SolveSpec:
    """Derive the chunk spec continuous serving ticks between re-buckets.

    A chunk is ``spec`` cut down to ``chunk`` iterations so the serving
    loop can warm-start it repeatedly (``plan(b, x0=x)``) and re-bucket
    the cohort at every boundary.  Two flavors:

    * ``fixed_length=True`` (continuous batching): tolerance methods run
      with ``tol=0.0`` so EVERY lane executes exactly ``chunk`` iterations
      per call regardless of who shares the batch -- that is what makes a
      lane's trajectory bitwise independent of its cohort (convergence is
      detected host-side at chunk boundaries from the residual trace).
    * ``fixed_length=False`` (the legacy deadline path): the chunk keeps
      the real tolerance, so a chunk stops early once every lane converges.

    Fixed-iteration methods just get ``iters=chunk``.  Keep ``chunk``
    under the solver stall window (100): a converged lane riding a
    fixed-length chunk replays a flat residual, and a longer chunk would
    trip the stagnation guard on it.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    sdef = registry.get_solver(spec.method)
    if sdef.tolerance:
        return replace(spec, batch=batch, iters=int(chunk),
                       max_iters=int(chunk),
                       tol=0.0 if fixed_length else spec.tol)
    return replace(spec, batch=batch, iters=int(chunk), max_iters=None,
                   tol=None)


class SolvePlan:
    """A compiled solve: spec + jitted program + operand buffers + info.

    Built by ``AzulEngine.plan(spec)``; execute with ``plan(b, x0=None)``.
    The program and the device-resident operands it closes over (matrix
    blocks, diagonal, packed factor blocks) live as long as the plan --
    compile once, execute as often as traffic demands.

    Attributes
    ----------
    spec        the canonical :class:`SolveSpec` (fused resolved to bool)
    info        {"method", "precond", "substrate", "fused", "batch"}
    traces      times the program was (re)traced -- 1 in steady state
    executions  times the plan was called
    last_iters  per-RHS iteration counts of the most recent execution
    last_status per-RHS structured status codes (int32 STATUS_*) of the
                most recent execution; ``last_status_names`` spells them
    last_bad_iter  per-RHS first guard-tripped iteration (-1 = none)
    """

    def __init__(self, engine, spec: SolveSpec, fn: Callable, info: dict,
                 trace_cell: list):
        self.engine = engine
        self.spec = spec
        self._fn = fn
        self.info = info
        self._trace_cell = trace_cell
        self.executions = 0
        self._exe = None
        self._zero_x0 = None
        self._after_solve = registry.effective_precond(
            registry.get_solver(spec.method), spec.precond,
            engine.mode == "local").after_solve
        self.last_iters = None
        self.last_status = None
        self.last_bad_iter = None

    @property
    def fn(self):
        """The jitted device program ``fn(b_dev, x0_dev) -> (x, norms,
        iters, status, bad_iter)`` in the engine's padded layout (plus a
        trailing ``vals`` operand for injectable plans; exposed for
        ``.lower()`` introspection -- the roofline dry-run path)."""
        return self._fn

    @property
    def last_status_names(self):
        """``last_status`` spelled via ``solvers.status_name`` (str for a
        single RHS, list of str for a batch); None before any execution."""
        if self.last_status is None:
            return None
        from . import solvers

        st = np.asarray(self.last_status)
        if st.ndim == 0:
            return solvers.status_name(int(st))
        return [solvers.status_name(int(c)) for c in st]

    @property
    def traces(self) -> int:
        return self._trace_cell[0]

    def assert_steady(self) -> None:
        """Raise RuntimeError if this plan ever retraced.

        The compile-free steady-state contract: a built plan traces exactly
        once, however many times serving re-enters it (warm starts, cohort
        changes, value substitution).  A violation is a real serving bug
        (per-step recompiles), so fail loudly -- RuntimeError survives
        ``python -O``, unlike ``assert``."""
        if self.traces > 1:
            raise RuntimeError(
                f"plan for spec {self.spec} retraced ({self.traces} traces):"
                " the compile-free steady-state contract broke"
            )

    def _check(self, b: np.ndarray) -> None:
        n = self.engine.n
        want = (n,) if self.spec.batch is None else (self.spec.batch, n)
        if b.shape != want:
            raise ValueError(
                f"plan compiled for RHS shape {want}, got {b.shape} -- "
                "plans are shape-specialized; build a spec with the "
                "matching batch"
            )

    def _operands(self, b, x0, vals):
        """The program's device operands for host (b, x0[, vals]), and
        the bytes handed to the device for them.  ``x0=None`` is the
        plan's resident zero guess and ``vals=None`` the engine's resident
        value buffer: neither is transferred."""
        eng = self.engine
        sent = [eng.to_device_vec(b)]
        if x0 is None:
            args = (sent[0], self._zero_x0)
        else:
            sent.append(eng.to_device_vec(x0))
            args = tuple(sent)
        if self.spec.injectable:
            args += (eng.vals_operand(vals),)
            if vals is not None:
                sent.append(args[-1])
        elif vals is not None:
            raise ValueError(
                "this plan closes over the matrix values as constants; "
                "build the spec with injectable=True to pass vals per call")
        return args, sum(a.nbytes for a in sent)

    def compile(self):
        """Lower and compile the program once; returns the compiled
        executable (``.as_text()`` shows what the backend runs).  Every
        execution runs this executable, so a lowering or compile failure
        surfaces here, before anything executes -- callers that retry
        runtime faults (the serving layer) compile first and let such
        failures propagate.

        The first call runs under a ``plan_compile`` span, feeds
        ``repro_plan_compile_seconds``, and registers the executable with
        ``repro.obs.scopes.SCOPES`` (parsed only when read).

        It also places the plan's zero initial guess on the device, in the
        program's layout and sharding; every call without an ``x0`` passes
        that buffer and sends none.  No jit under ``repro.core`` donates an
        argument (no ``donate_argnums``), so no execution writes into it;
        keep it that way for this operand."""
        if self._exe is None:
            shape = ((self.engine.n,) if self.spec.batch is None
                     else (self.spec.batch, self.engine.n))
            zeros = np.zeros(shape)
            self._zero_x0 = self.engine.to_device_vec(zeros)
            args, _ = self._operands(zeros, None, None)
            method = self.spec.method
            tr0 = self._trace_cell[0]
            t0 = _clock.now()
            with _span("plan_compile", kind="plan_compile", method=method):
                exe = self._fn.lower(*args).compile()
            _M_COMPILE_S.observe(_clock.now() - t0, method=method)
            retraces = self._trace_cell[0] - tr0 - (1 if tr0 == 0 else 0)
            if retraces > 0:
                _M_RETRACES.inc(retraces)
            _SCOPES.register(self, exe)
            self._exe = exe
        return self._exe

    def __call__(self, b, x0=None, vals=None):
        """Execute: returns (x, res_norms) as numpy, mirroring the RHS
        shape; per-RHS iteration counts land in ``self.last_iters``,
        structured status in ``self.last_status``/``last_bad_iter`` (and,
        for engine-level compatibility, ``engine.last_solve_info``).

        ``vals`` (injectable plans only) substitutes the matrix value
        buffer for THIS call -- same shape/dtype as the engine's packed
        values; None runs the clean operator.

        The call is a ``solve`` span with three children -- ``stage_in``,
        ``execute`` (dispatch to outputs ready) and ``stage_out`` (every
        output back to the host in one batched ``jax.device_get``, x
        un-padded) -- and feeds ``repro_solve_stage_seconds``,
        ``repro_solve_seconds`` and the transfer byte counters.  All of it
        is host-side: the program is untouched, so instrumented solves are
        bitwise identical to bare ones (asserted in tests/test_obs.py).

        ``stage_in`` sends b and, only when one is given, x0; without one
        the plan's resident zero guess is passed and nothing is sent for
        it.  A vector the engine's layout leaves as it is (no padding, no
        row permutation) is transferred straight from the caller's array,
        after one ``astype`` where its dtype differs from the engine's;
        only a padded or permuted layout builds a host staging copy
        (``repro_solve_staged_copies_total``)."""
        b = np.asarray(b)
        self._check(b)
        exe = self.compile()
        method = self.spec.method
        eng = self.engine
        with _span("solve", kind="solve", method=method):
            t0 = _clock.now()
            with _span("solve.stage_in"):
                if x0 is not None:
                    x0 = np.asarray(x0)
                    if b.ndim == 2 and x0.ndim == 1:
                        # a shared (n,) initial guess for a (k, n) batch:
                        # broadcast so b and x0 agree on the batched
                        # sharding spec
                        x0 = np.broadcast_to(x0, b.shape)
                args, h2d = self._operands(b, x0, vals)
            t1 = _clock.now()
            with _span("solve.execute"):
                out = exe(*args)
                jax.block_until_ready(out)
            t2 = _clock.now()
            with _span("solve.stage_out"):
                d2h = sum(a.nbytes for a in out)
                x, norms, its, status, bad = jax.device_get(out)
                x = eng.from_device_vec(x)
                self.last_iters = its
                self.last_status = status
                self.last_bad_iter = bad
            t3 = _clock.now()
        if self._after_solve is not None:
            self._after_solve(self.last_iters)
        _M_EXECUTIONS.inc(method=method)
        _M_SOLVE_S.observe(t2 - t1, method=method)
        _M_STAGE_S.observe(t1 - t0, phase="in")
        _M_STAGE_S.observe(t3 - t2, phase="out")
        _M_H2D.inc(h2d)
        _M_D2H.inc(d2h)
        self.executions += 1
        info = dict(self.info)
        info["iters"] = self.last_iters
        info["status"] = self.last_status
        info["status_names"] = self.last_status_names
        info["bad_iter"] = self.last_bad_iter
        eng.last_solve_info = info
        return x, norms

    def hlo_summary(self, refresh: bool = False) -> dict:
        """Collective-instruction summary of this plan's lowered program
        (``roofline.collect.analyze_stablehlo_text`` over
        ``fn.lower(...).as_text()``), cached into ``info["hlo"]``:
        ``count_by_op`` keyed by HLO collective names (``all-reduce``,
        ``collective-permute``, ...) plus ``total_count``.  Tests that
        used to hand-count ``stablehlo.all_reduce`` substrings read this
        instead.

        The introspection lowering re-traces the program outside the jit
        execution cache, so its trace is excluded from ``plan.traces`` --
        inspecting a plan does not break the steady-state contract."""
        if not refresh and "hlo" in self.info:
            return self.info["hlo"]
        from ..roofline.collect import analyze_stablehlo_text

        eng = self.engine
        shape = ((eng.n,) if self.spec.batch is None
                 else (self.spec.batch, eng.n))
        b = np.zeros(shape)
        args = (eng.to_device_vec(b), eng.to_device_vec(b))
        if self.spec.injectable:
            args += (eng.vals_operand(None),)
        before = self._trace_cell[0]
        try:
            txt = self._fn.lower(*args).as_text()
        finally:
            self._trace_cell[0] = before
        self.info["hlo"] = analyze_stablehlo_text(txt)
        return self.info["hlo"]

    def __repr__(self) -> str:
        s = self.spec
        return (f"SolvePlan({s.method}, precond={s.precond}, "
                f"substrate={self.info['substrate']}, batch={s.batch}, "
                f"traces={self.traces}, executions={self.executions})")


class PlanCache:
    """Spec-keyed store of compiled plans (the engine's ``plans`` attr).

    Keys are (canonical SolveSpec, env) where env captures trace-relevant
    global state (the kernel dispatch mode) -- equal specs hit, anything
    else misses and lowers exactly once.  ``hits``/``misses`` feed the
    serving stats; membership tests take a canonical spec."""

    def __init__(self):
        self._plans: dict = {}
        self.hits = 0
        self.misses = 0

    def get(self, spec: SolveSpec, build: Callable, env: tuple = ()):
        key = (spec, env)
        plan = self._plans.get(key)
        if plan is None:
            self.misses += 1
            _M_CACHE_MISSES.inc()
            t0 = _clock.now()
            with _span("plan_build", kind="plan_build", method=spec.method):
                plan = build(spec)
            _M_BUILD_S.observe(_clock.now() - t0)
            self._plans[key] = plan
        else:
            self.hits += 1
            _M_CACHE_HITS.inc()
        return plan

    def __len__(self) -> int:
        return len(self._plans)

    def __contains__(self, spec: SolveSpec) -> bool:
        return any(k[0] == spec for k in self._plans)

    def specs(self) -> list:
        return [k[0] for k in self._plans]

    def clear(self) -> None:
        self._plans.clear()


# ---------------------------------------------------------------------------
# deprecation bookkeeping for the legacy kwargs surface
# ---------------------------------------------------------------------------

_WARNED: set = set()


def warn_deprecated(key: str, message: str) -> None:
    """Emit ``message`` as a DeprecationWarning ONCE per process per key
    (legacy call sites keep working; they just say so, once)."""
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(message, DeprecationWarning, stacklevel=3)


def _reset_deprecation_warnings() -> None:
    """Test hook: make the next legacy call warn again."""
    _WARNED.clear()
