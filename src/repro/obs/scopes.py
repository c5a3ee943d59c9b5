"""Named scopes on the device program, and which instruction is whose.

The solve programs wrap each layer boundary in :func:`scope`, a
``jax.named_scope`` from one closed vocabulary:

    scope      covers
    -----      ------
    gather     the XLA ``x[cols]`` gather of the ELL paths (and the CG
               p-update folded into it)
    matvec     the operator stream: ELL kernels, the stencil shifted adds,
               the SELL / HYB / BCSR matvecs
    precond    the preconditioner application (``psolve``)
    update     the CG vector updates (``cg_update``, ``pipe_update``, the
               substrate ``update``) and the inverse diagonal they read
    reduce     dot products and their ``psum``s
    halo       the NoC exchange of the vector (ppermute pulls, mesh
               transpose, all-gathers, the reduce-scatter of partials)
    control    the solver loop itself: convergence test, guards, the
               per-lane freeze selects, the residual-ring write
    smooth     the multigrid smoother: the Gauss-Seidel colour steps of
               every level (their gathers and slice updates included)
    transfer   the multigrid level transfers: the residual at the coarse
               rows, the restriction and the prolongation

A scope changes only HLO metadata (``op_name``), never the compiled
program, so scopes are always on.  Where scopes nest, the innermost
vocabulary element of an instruction's ``op_name`` names it; an
instruction with none (or without metadata: copies XLA inserts) is
``other``.

:data:`SCOPES` maps HLO instruction names to scopes for every executable
``SolvePlan.compile()`` built in this process.  A compiled executable is
parsed lazily -- on the first read of the map, or when its plan is freed,
whichever comes first -- so set-up pays nothing and the map outlives the
plans (a reader of a profiler trace may run after they are gone).  A name
two executables give different scopes is ``other``.
"""

from __future__ import annotations

import re
import threading
import weakref

__all__ = ["VOCABULARY", "OTHER", "scope", "scope_of", "parse_hlo",
           "ScopeMap", "SCOPES"]

VOCABULARY = ("gather", "matvec", "precond", "update", "reduce", "halo",
              "control", "smooth", "transfer")
OTHER = "other"
_VOCAB = frozenset(VOCABULARY)

# one HLO instruction line of ``compiled.as_text()``: its name and, where
# it has metadata, the op_name (the jax name stack, then the primitive)
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def scope(name: str):
    """``jax.named_scope(name)`` for a name of the vocabulary."""
    if name not in _VOCAB:
        raise ValueError(f"scope {name!r} is not one of {VOCABULARY}")
    import jax

    return jax.named_scope(name)


def scope_of(op_name: str) -> str:
    """The innermost vocabulary element of an ``op_name``'s name stack.
    The last component is the primitive (``gather``, ``reduce_sum``...),
    not a scope, so it is never read as one."""
    for part in reversed(op_name.split("/")[:-1]):
        if part in _VOCAB:
            return part
    return OTHER


def parse_hlo(text: str) -> dict[str, str]:
    """{instruction name: scope} of every instruction in an HLO module's
    text (``compiled.as_text()``)."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line, m.end())
        out[m.group(1)] = scope_of(op.group(1)) if op else OTHER
    return out


class ScopeMap:
    """Instruction-name -> scope map over registered executables.

    A registered executable is parsed by a ``weakref.finalize`` on its
    owner, which may run inside garbage collection on any thread; so the
    parse takes no lock, and merges into the map entry by entry (each
    dict operation is atomic, and a conflict only ever moves a name to
    ``other``)."""

    def __init__(self):
        self._lock = threading.Lock()     # guards _pending only
        self._map: dict[str, str] = {}
        self._pending: list = []          # weakref.finalize per executable

    def register(self, owner, executable) -> None:
        """Parse ``executable`` (anything with ``as_text()``) on the next
        read, or when ``owner`` is garbage-collected, whichever is first;
        the map keeps no reference to either once parsed."""
        fin = weakref.finalize(owner, self._absorb, executable)
        fin.atexit = False
        with self._lock:
            self._pending = [f for f in self._pending if f.alive]
            self._pending.append(fin)

    def _absorb(self, executable) -> None:
        try:
            text = executable.as_text()
        except Exception:       # an executable that cannot print adds nothing
            return
        for name, sc in parse_hlo(text).items():
            if self._map.setdefault(name, sc) != sc:
                self._map[name] = OTHER

    def mapping(self) -> dict[str, str]:
        """{instruction name: scope} over every executable registered so
        far (parses those still pending)."""
        with self._lock:
            pending, self._pending = self._pending, []
        for fin in pending:
            fin()                         # runs _absorb once, if still alive
        return self._map.copy()


#: the process-global map ``SolvePlan.compile()`` registers into
SCOPES = ScopeMap()
