"""Solver / preconditioner registry: capability metadata driving plan lowering.

The plan/execute API (:mod:`repro.core.plan`) lowers a frozen ``SolveSpec``
into a compiled ``SolvePlan``.  What used to be if/elif ladders inside
``AzulEngine`` (``_resolve_fused`` / ``substrate_kind`` / ``_solve_local`` /
``_solve_compiled``) is now a capability lookup against this registry:

* a :class:`SolverDef` names the iteration (``run`` adapts the uniform
  :class:`SolveContext` to the actual :mod:`repro.core.solvers` callable)
  and declares what it supports -- tolerance stopping, batching, whether it
  consumes the engine preconditioner, whether its fused update applies
  M^-1 in-stream, and *which preconditioners it can run fused against*,
  locally and under ``shard_map``;
* a :class:`PrecondDef` names the preconditioner, its aliases, the
  capability flags lowering needs (``uses_dinv``, ``factorized``) and the
  substrate kind its fused application lowers to.

Adding a solver or preconditioner is a ``register_solver`` /
``register_precond`` call plus the kernel/apply it needs -- the engine,
``SolveSpec`` validation, ``substrate_kind`` reporting, serving, and the
benchmarks all pick it up through the registry (see README "Extending the
registry").
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

from .multigrid import count_vcycles, make_psolve

__all__ = [
    "SolverDef",
    "PrecondDef",
    "SolveContext",
    "register_solver",
    "register_precond",
    "unregister_solver",
    "unregister_precond",
    "get_solver",
    "get_precond",
    "solver_names",
    "precond_names",
    "resolve_fused",
    "resolve_layout",
    "resolve_format",
    "substrate_kind",
    "effective_precond",
]


# ---------------------------------------------------------------------------
# definitions
# ---------------------------------------------------------------------------

# Storage formats a solver's substrate can stream the operator from.  The
# substrate-phrased methods are format-oblivious (they consume matvec /
# fold_matvec_dot closures), so every registered solver declares the full
# set; a method hard-wired to one layout would restrict this.
_ALL_FORMATS = frozenset({"ell", "sell", "hyb", "bcsr", "stencil"})


@dataclass
class SolveContext:
    """The uniform operator bundle plan lowering hands a solver's ``run``.

    ``matvec``/``psolve``/``dot``/``dot2``/``substrate`` are already bound
    to the engine's layout (local padded-ELL closures, or per-tile NoC
    closures inside ``shard_map``); ``dot``/``dot2`` are ``None`` where the
    solver's layout-oblivious default applies (local mode).
    """

    matvec: Callable
    psolve: Callable
    dinv: Any = None                  # inverse-diagonal operand (jacobi)
    dot: Callable | None = None
    dot2: Callable | None = None
    substrate: Any = None             # SolverSubstrate or None (reference)
    iters: int = 0
    tol: float | None = None
    max_iters: int | None = None
    guard: bool = True                # in-loop numerical health guards


@dataclass(frozen=True)
class SolverDef:
    """Capability metadata + adapter for one iterative method.

    ``fused_local`` / ``fused_dist`` list the *engine* preconditioner names
    the method supports a fused substrate with, per mode.  ``tolerance``
    marks while_loop methods (they read ``tol``/``max_iters`` and return
    the bounded convergence trace); ``preconditioned`` marks methods that
    consume the engine preconditioner at all (``cg`` does not);
    ``needs_dinv`` marks methods whose iteration itself consumes the
    inverse diagonal (the ``jacobi`` smoother); ``fused_precond_apply``
    marks methods whose fused update applies M^-1 in-stream, so a
    factorized preconditioner lowers them to its heavyweight substrate
    kind (``fused_ic0`` / ``fused_shard_ic0``).  ``*_precond_override``
    remaps the preconditioner used to build ``psolve`` per mode.
    ``halo_dist`` lists the preconditioner names the method's distributed
    lowering may run on a compiled halo-exchange communication plan
    (:mod:`repro.core.commplan`) instead of dense collectives -- the
    substrate-phrased methods whose matvec is the engine's NoC closure.
    ``comm_overlap`` marks methods whose recurrence can consume the split
    communication-hiding matvec (``matvec_start``/``matvec_finish``): on a
    halo layout the engine lowers their SpMV as interior/frontier passes
    with the pull schedule double-buffered across iterations.  ``guarded``
    marks methods with in-loop numerical health guards (they accept
    ``guard`` and return a structured per-RHS ``status``/``bad_iter``;
    canonicalization forces ``guard=False`` for methods without the
    capability, whose programs report STATUS_UNGUARDED).  ``aliases``
    are alternate spellings ``get_solver`` resolves to this entry;
    canonicalization rewrites specs to the canonical name so aliased plans
    share one cache slot.
    """

    name: str
    run: Callable[[SolveContext, Any, Any], Any]   # (ctx, b, x0) -> SolveResult
    tolerance: bool = False
    batched: bool = True
    preconditioned: bool = True
    needs_dinv: bool = False
    fused_precond_apply: bool = False
    fused_local: frozenset = frozenset()
    fused_dist: frozenset = frozenset()
    halo_dist: frozenset = frozenset()
    local_precond_override: dict = field(default_factory=dict)
    dist_precond_override: dict = field(default_factory=dict)
    comm_overlap: bool = False
    guarded: bool = False
    formats: frozenset = _ALL_FORMATS
    aliases: tuple = ()


@dataclass(frozen=True)
class PrecondDef:
    """Capability metadata + local apply builder for one preconditioner.

    ``local_apply(engine)`` returns the single-device ``psolve`` closure
    over the engine's device-resident operands.  The distributed per-tile
    apply is built by engine lowering from the capability flags
    (``uses_dinv`` -> the sharded inverse diagonal, ``factorized`` -> the
    packed per-tile factor blocks).  ``fused_local_needs_kernels`` marks
    preconditioners whose local fused substrate only pays when the Pallas
    kernels are actually dispatching (the compute-for-traffic trade of the
    whole-solve SpTRSV): with kernels inactive, ``fused="auto"``
    resolution prefers the reference apply (an explicit ``fused=True``
    still forces the fused path).  ``hierarchy`` marks preconditioners
    that need the operator's multigrid levels (an engine built from a
    :class:`~repro.core.multigrid.MGHierarchy`, on one device).
    ``after_solve(iters)`` is host-side accounting a plan runs after each
    execution with the per-RHS iteration counts.
    """

    name: str
    aliases: tuple = ()
    uses_dinv: bool = False
    factorized: bool = False
    hierarchy: bool = False
    fused_local_kind: str = "fused"
    fused_shard_kind: str = "fused_shard"
    fused_local_needs_kernels: bool = False
    local_apply: Callable | None = None
    after_solve: Callable | None = None


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------

_SOLVERS: dict[str, SolverDef] = {}
_SOLVER_ALIASES: dict[str, str] = {}
_PRECONDS: dict[str, PrecondDef] = {}
_PRECOND_ALIASES: dict[str, str] = {}


def register_solver(sdef: SolverDef) -> SolverDef:
    _SOLVERS[sdef.name] = sdef
    for a in sdef.aliases:
        _SOLVER_ALIASES[a] = sdef.name
    return sdef


def register_precond(pdef: PrecondDef) -> PrecondDef:
    _PRECONDS[pdef.name] = pdef
    for a in pdef.aliases:
        _PRECOND_ALIASES[a] = pdef.name
    return pdef


def unregister_solver(name: str) -> None:
    sdef = _SOLVERS.pop(name, None)
    if sdef is not None:
        for a in sdef.aliases:
            _SOLVER_ALIASES.pop(a, None)


def unregister_precond(name: str) -> None:
    pdef = _PRECONDS.pop(name, None)
    if pdef is not None:
        for a in pdef.aliases:
            _PRECOND_ALIASES.pop(a, None)


def get_solver(name: str) -> SolverDef:
    name = _SOLVER_ALIASES.get(name, name)
    try:
        return _SOLVERS[name]
    except KeyError:
        raise ValueError(
            f"unknown solver {name!r}; registered: {', '.join(solver_names())}"
        ) from None


def get_precond(name: str) -> PrecondDef:
    name = _PRECOND_ALIASES.get(name, name)
    try:
        return _PRECONDS[name]
    except KeyError:
        raise ValueError(
            f"unknown preconditioner {name!r}; "
            f"registered: {', '.join(precond_names())}"
        ) from None


def solver_names() -> tuple:
    return tuple(sorted(_SOLVERS))


def precond_names() -> tuple:
    return tuple(sorted(_PRECONDS))


# ---------------------------------------------------------------------------
# capability resolution (the former engine if/elif ladders)
# ---------------------------------------------------------------------------


def resolve_fused(sdef: SolverDef, pdef: PrecondDef, local: bool, knob) -> bool:
    """Map the tri-state fused knob ('auto' | True | False) to a concrete
    bool: 'auto' and True mean "fused wherever this (method, precond, mode)
    supports it" -- a registry capability lookup, not a name ladder.

    'auto' additionally defers to the kernels for preconditioners marked
    ``fused_local_needs_kernels``: their local fused substrate (the
    whole-solve ``sptrsv_solve_dot`` kernel) trades on-chip compute for
    HBM traffic, a trade that only pays where that kernel actually runs.
    With kernels inactive (CPU) the reference apply is faster, and on TPU
    the kernel does not compile (``ops.UNCOMPILABLE``), so capability
    resolution prefers the reference apply in both cases.  ``True``
    remains an explicit override."""
    if knob not in ("auto", True, False):
        raise ValueError(f"fused must be 'auto', True or False, got {knob!r}")
    caps = sdef.fused_local if local else sdef.fused_dist
    supported = pdef.name in caps
    if (knob == "auto" and supported and local and sdef.fused_precond_apply
            and pdef.fused_local_needs_kernels):
        from ..kernels.ops import kernel_usable

        supported = kernel_usable("sptrsv_solve_dot")
    return supported if knob in ("auto", True) else False


def resolve_layout(sdef: SolverDef, pdef: PrecondDef, local: bool, knob,
                   halo_profitable: bool) -> str:
    """Resolve the communication-layout knob (None/'auto' | 'halo' |
    'dense') to the concrete layout a plan lowers with.

    'auto' picks 'halo' when (a) the (method, preconditioner) pair
    declares halo support and (b) the engine's compiled
    :class:`~repro.core.commplan.CommPlan` says the halo schedule moves
    strictly fewer bytes than the dense all-gather (``halo_profitable``).
    An explicit 'halo' forces the schedule (capability permitting -- for
    A/B measurement even where it does not pay); local engines have no NoC
    and always lower 'dense'."""
    if knob not in (None, "auto", "halo", "dense"):
        raise ValueError(
            f"layout must be 'auto', 'halo' or 'dense', got {knob!r}")
    if local:
        if knob == "halo":
            raise ValueError("layout='halo' needs a distributed engine "
                             "(single-device engines have no NoC)")
        return "dense"
    supported = pdef.name in sdef.halo_dist
    if knob in (None, "auto"):
        return "halo" if (supported and halo_profitable) else "dense"
    if knob == "halo" and not supported:
        raise ValueError(
            f"solver {sdef.name!r} does not support halo communication "
            f"plans with preconditioner {pdef.name!r}")
    return knob


def resolve_format(sdef: SolverDef, local: bool, knob,
                   engine_choice: str = "ell", *,
                   stencil: bool = False, injectable: bool = False) -> str:
    """Resolve the storage-format knob (None/'auto' | concrete name) to the
    format a plan streams the operator from.

    'auto' takes the engine's autotuned per-matrix decision
    (``engine_choice``, from ``kernels.autotune.choose_format``), except in
    modes that pin the layout: a stencil engine has no stored nonzeros
    ('stencil' is the only format), injectable plans carry the values as an
    ELL-shaped runtime operand, and distributed lowering shards/remaps the
    padded ELL arrays -- all three force their format and reject a
    conflicting explicit request.
    """
    if knob not in (None, "auto") and knob not in _ALL_FORMATS:
        raise ValueError(
            f"format must be 'auto' or one of "
            f"{', '.join(sorted(_ALL_FORMATS))}, got {knob!r}")
    if stencil:
        if knob not in (None, "auto", "stencil"):
            raise ValueError(
                f"format={knob!r} conflicts with a matrix-free stencil "
                "engine (no stored nonzeros to re-lay-out)")
        if injectable:
            raise ValueError(
                "injectable=True needs stored matrix values; a stencil "
                "operator generates its coefficients in-kernel")
        return "stencil"
    if knob == "stencil":
        raise ValueError("format='stencil' needs a stencil operator engine")
    if injectable:
        if knob not in (None, "auto", "ell"):
            raise ValueError(
                f"format={knob!r} conflicts with injectable=True "
                "(injected values are an ELL-shaped runtime operand)")
        return "ell"
    if not local:
        if knob not in (None, "auto", "ell"):
            raise ValueError(
                f"format={knob!r} is not supported in distributed mode "
                "(sharding and halo remap are phrased over padded ELL)")
        return "ell"
    fmt = engine_choice if knob in (None, "auto") else knob
    if fmt not in sdef.formats:
        raise ValueError(
            f"solver {sdef.name!r} does not support format {fmt!r}")
    return fmt


def substrate_kind(sdef: SolverDef, pdef: PrecondDef, local: bool,
                   fused: bool) -> str:
    """The substrate a (solver, precond, mode, resolved-fused) lowers to:
    "reference", "fused", "fused_ic0", "fused_shard" or "fused_shard_ic0".
    A factorized preconditioner only reaches its heavyweight kind through
    methods whose fused update applies M^-1 in-stream."""
    if not fused:
        return "reference"
    if sdef.fused_precond_apply:
        return pdef.fused_local_kind if local else pdef.fused_shard_kind
    return "fused" if local else "fused_shard"


def effective_precond(sdef: SolverDef, engine_precond: str,
                      local: bool) -> PrecondDef:
    """The preconditioner a solver's ``psolve`` is actually built from:
    unpreconditioned methods get identity (or jacobi when the iteration
    itself needs the diagonal), and per-mode overrides apply (none of the
    builtins override since pcg_pipelined's promotion; the hook stays for
    external methods with restricted psolve support)."""
    if not sdef.preconditioned:
        return get_precond("jacobi" if sdef.needs_dinv else "identity")
    ov = sdef.local_precond_override if local else sdef.dist_precond_override
    name = _PRECOND_ALIASES.get(engine_precond, engine_precond)
    return get_precond(ov.get(name, name))


# ---------------------------------------------------------------------------
# built-in solvers (adapters over repro.core.solvers)
# ---------------------------------------------------------------------------

_ALL_PRECONDS = frozenset({"identity", "jacobi", "block_ic0"})
_LOCAL_PRECONDS = frozenset({"identity", "jacobi"})


def _dot_kw(c: SolveContext) -> dict:
    return {"dot": c.dot} if c.dot is not None else {}


def _run_pcg(c: SolveContext, b, x0):
    from . import solvers

    return solvers.pcg(c.matvec, b, psolve=c.psolve, x0=x0, iters=c.iters,
                       substrate=c.substrate, guard=c.guard, **_dot_kw(c))


def _run_pcg_tol(c: SolveContext, b, x0):
    from . import solvers

    return solvers.pcg_tol(c.matvec, b, psolve=c.psolve, x0=x0, tol=c.tol,
                           max_iters=c.max_iters, substrate=c.substrate,
                           guard=c.guard, **_dot_kw(c))


def _run_cg(c: SolveContext, b, x0):
    from . import solvers

    return solvers.cg(c.matvec, b, x0=x0, iters=c.iters,
                      substrate=c.substrate, guard=c.guard, **_dot_kw(c))


def _pipe_kw(c: SolveContext) -> dict:
    kw = _dot_kw(c)
    if c.dot2 is not None:
        kw["dot2"] = c.dot2
    return kw


def _run_pcg_pipelined(c: SolveContext, b, x0):
    from . import solvers

    return solvers.pcg_pipelined(c.matvec, b, psolve=c.psolve, x0=x0,
                                 iters=c.iters, substrate=c.substrate,
                                 guard=c.guard, **_pipe_kw(c))


def _run_pcg_pipelined_tol(c: SolveContext, b, x0):
    from . import solvers

    return solvers.pcg_pipelined_tol(c.matvec, b, psolve=c.psolve, x0=x0,
                                     tol=c.tol, max_iters=c.max_iters,
                                     substrate=c.substrate, guard=c.guard,
                                     **_pipe_kw(c))


def _run_jacobi(c: SolveContext, b, x0):
    from . import solvers

    return solvers.jacobi(c.matvec, c.dinv, b, x0=x0, iters=c.iters,
                          **_dot_kw(c))


register_solver(SolverDef(
    name="pcg", run=_run_pcg, fused_precond_apply=True,
    fused_local=_ALL_PRECONDS, fused_dist=_ALL_PRECONDS,
    halo_dist=_ALL_PRECONDS, guarded=True,
))
register_solver(SolverDef(
    name="pcg_tol", run=_run_pcg_tol, tolerance=True,
    fused_precond_apply=True,
    fused_local=_ALL_PRECONDS, fused_dist=_ALL_PRECONDS,
    halo_dist=_ALL_PRECONDS, guarded=True,
))
register_solver(SolverDef(
    name="cg", run=_run_cg, preconditioned=False,
    fused_local=_ALL_PRECONDS, fused_dist=_ALL_PRECONDS,
    halo_dist=_ALL_PRECONDS, guarded=True,
))
register_solver(SolverDef(
    name="pcg_pipelined", run=_run_pcg_pipelined,
    fused_precond_apply=True,
    fused_local=_ALL_PRECONDS, fused_dist=_ALL_PRECONDS,
    halo_dist=_ALL_PRECONDS, comm_overlap=True, guarded=True,
    aliases=("pcg_pipe",),      # pre-promotion spelling (PR 6 migration)
))
register_solver(SolverDef(
    name="pcg_pipelined_tol", run=_run_pcg_pipelined_tol, tolerance=True,
    fused_precond_apply=True,
    fused_local=_ALL_PRECONDS, fused_dist=_ALL_PRECONDS,
    halo_dist=_ALL_PRECONDS, comm_overlap=True, guarded=True,
))
register_solver(SolverDef(
    name="jacobi", run=_run_jacobi, preconditioned=False, needs_dinv=True,
))


# ---------------------------------------------------------------------------
# built-in preconditioners
# ---------------------------------------------------------------------------


def _identity_apply(engine):
    return lambda r: r


def _jacobi_apply(engine):
    dinv = engine._dinv_pad
    return lambda r: r * dinv


def _block_ic0_apply(engine):
    import jax
    import jax.numpy as jnp

    from .precond import apply_ic0

    f = engine._ic0
    n, n_pad = engine.n, engine.n_pad

    def ps1(r):
        z = apply_ic0(f, r[:n])
        return jnp.zeros(n_pad, r.dtype).at[:n].set(z)

    def ps(r):
        return jax.vmap(ps1)(r) if r.ndim == 2 else ps1(r)

    return ps


def _mg_apply(engine):
    return make_psolve(engine._mg, engine.n, engine.n_pad)


register_precond(PrecondDef(
    name="identity", aliases=("none",), local_apply=_identity_apply,
))
register_precond(PrecondDef(
    name="jacobi", uses_dinv=True, local_apply=_jacobi_apply,
))
register_precond(PrecondDef(
    name="block_ic0", factorized=True,
    fused_local_kind="fused_ic0", fused_shard_kind="fused_shard_ic0",
    # the whole-solve SpTRSV substrate buys HBM traffic with VPU work --
    # ~7x SLOWER than the reference apply on CPU (BENCH_pcg tol_solves at
    # lap2d_32) and not compilable for TPU, so 'auto' only picks it where
    # the kernel runs (interpret mode)
    fused_local_needs_kernels=True,
    local_apply=_block_ic0_apply,
))
# HPCG's V-cycle with multicolour SymGS (repro.core.multigrid): one device
# only, and in no solver's fused set, so its psolve runs unfused
register_precond(PrecondDef(
    name="mg", hierarchy=True, local_apply=_mg_apply,
    after_solve=count_vcycles,
))
