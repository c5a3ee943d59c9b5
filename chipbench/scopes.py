"""Device time by layer: the profiler trace's operations (``trace_reduce``'s
``top_ops``, self seconds averaged over the chips) named by the program's
own map from HLO instruction to scope (``repro.obs.scopes.SCOPES``: gather,
matvec, precond, update, reduce, halo, control, or other).

A program without that map (one that predates it) reads nothing: the
readers built on this return None.
"""

from __future__ import annotations

OTHER = "other"


def scope_map() -> dict | None:
    """The program's {instruction name: scope} over every plan it compiled
    in this process; None where the program keeps no such map."""
    try:
        from repro.obs.scopes import SCOPES
    except ImportError:
        return None
    return SCOPES.mapping() or None


def instruction(op: str) -> str:
    """The HLO instruction name of a ``top_ops`` entry: ``fusion.4 kCustom``
    -> ``fusion.4`` (a fusion's kind follows its name after a space)."""
    return op.split(" ", 1)[0]


def seconds_by_scope(top_ops: list, mapping: dict) -> dict:
    """{scope: device self seconds} over ``top_ops`` ([name, seconds])."""
    out: dict[str, float] = {}
    for op, t in top_ops:
        sc = mapping.get(instruction(op), OTHER)
        out[sc] = out.get(sc, 0.0) + t
    return out


def ms_per_iter(run, scope: str) -> float | None:
    """Device milliseconds of ``scope`` per solver iteration completed in the
    traced window, averaged over the chips; None when the trace, the map,
    the iterations or the scope's time is missing."""
    tr = run.window.traced
    if tr is None or not tr.reduced:
        return None
    mapping = scope_map()
    if not mapping:
        return None
    iters = sum(a.iters for a in tr.answers if a.iters > 0)
    t = seconds_by_scope(tr.reduced["top_ops"], mapping).get(scope, 0.0)
    if iters == 0 or t <= 0:
        return None
    return 1e3 * t / iters
