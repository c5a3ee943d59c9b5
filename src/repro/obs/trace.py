"""Span-based tracing into a bounded ring buffer.

The structural complement to :mod:`repro.obs.metrics`: metrics say HOW
MUCH (counts, latency distributions), spans say WHEN and INSIDE WHAT.
Instrumented layers open spans around the phases that matter:

    kind             opened by
    ----             ---------
    plan_build       ``PlanCache`` miss (building the plan's program)
    plan_compile     ``SolvePlan.compile()`` (trace, lower, compile or a
                     persistent-cache load; once per plan)
    solve            every ``SolvePlan.__call__``, with three children:
    solve.stage_in   the initial guess and both host-to-device vectors
    solve.execute    dispatch of the compiled program to its outputs ready
    solve.stage_out  the outputs copied back to the host and un-padded
    tick             ``SolveService.tick``
    chunk            one continuous-batching chunk execution
    ft_chunk         one ``SolveRestartManager`` chunk (incl. recovery)

Spans nest: a span opened inside another (same thread or task -- the
nesting rides a context variable) records it as its ``parent``, and every
span of one outermost span shares that span's ``request`` id.

Spans land in a process-global bounded ring (:data:`TRACER`, default
4096 spans -- old spans fall off, memory stays bounded on an always-on
service) and export as Chrome trace-event JSON
(``chrome://tracing`` / Perfetto: :meth:`Tracer.chrome_trace`).  Like
the metrics registry, recording is fully host-side (a span never enters
a traced program) and honors :func:`repro.obs.metrics.set_enabled`.

Optional ``jax.profiler`` bridge: ``set_jax_bridge(True)`` additionally
wraps every span in ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` (``repro.solve.stage_in``), so obs spans show up on the
host plane of XLA profiler timelines, on the device trace's clock, when
one is being captured.
"""

from __future__ import annotations

import itertools
import json
import threading
from collections import Counter as _TallyCounter
from collections import deque
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass, field

from . import clock as _clock
from .metrics import enabled as _enabled

__all__ = ["Span", "Tracer", "TRACER", "span", "set_jax_bridge"]

_JAX_BRIDGE = False
_IDS = itertools.count(1)
_OPEN: ContextVar["Span | None"] = ContextVar("repro_obs_open_span",
                                              default=None)


def set_jax_bridge(flag: bool) -> bool:
    """Also emit every span as a ``jax.profiler.TraceAnnotation`` named
    ``repro.<name>`` (visible in captured XLA profiles).  Off by default;
    returns previous state."""
    global _JAX_BRIDGE
    prev, _JAX_BRIDGE = _JAX_BRIDGE, bool(flag)
    return prev


@dataclass
class Span:
    name: str
    kind: str
    start: float                    # obs-clock seconds
    end: float = 0.0
    attrs: dict = field(default_factory=dict)
    id: int = 0                     # process-unique
    parent: int | None = None       # id of the enclosing span
    request: int = 0                # id of the outermost enclosing span

    @property
    def duration(self) -> float:
        return self.end - self.start


class Tracer:
    """Bounded span ring + Chrome trace-event export."""

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self._spans: deque[Span] = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.dropped = 0                 # spans that fell off the ring

    @contextmanager
    def span(self, name: str, kind: str | None = None, **attrs):
        """Record one span around the with-block (no-op while obs is
        disabled).  ``kind`` defaults to ``name``."""
        if not _enabled():
            yield None
            return
        outer = _OPEN.get()
        sid = next(_IDS)
        s = Span(name=name, kind=kind or name, start=_clock.now(),
                 attrs=attrs, id=sid,
                 parent=None if outer is None else outer.id,
                 request=sid if outer is None else outer.request)
        token = _OPEN.set(s)
        bridge = None
        if _JAX_BRIDGE:
            try:
                import jax

                bridge = jax.profiler.TraceAnnotation(f"repro.{name}")
                bridge.__enter__()
            except Exception:
                bridge = None
        try:
            yield s
        finally:
            if bridge is not None:
                bridge.__exit__(None, None, None)
            _OPEN.reset(token)
            s.end = _clock.now()
            with self._lock:
                if len(self._spans) == self.capacity:
                    self.dropped += 1
                self._spans.append(s)

    def spans(self, kind: str | None = None) -> list[Span]:
        with self._lock:
            out = list(self._spans)
        return out if kind is None else [s for s in out if s.kind == kind]

    def counts(self) -> dict[str, int]:
        """{kind: spans currently in the ring} (sorted keys)."""
        tally = _TallyCounter(s.kind for s in self.spans())
        return dict(sorted(tally.items()))

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def chrome_trace(self) -> list[dict]:
        """Chrome trace-event list (load in chrome://tracing / Perfetto):
        one complete ('X') event per span, microsecond timestamps on the
        obs clock; ``args`` carries the span's attributes and its ``id``,
        ``parent`` and ``request``."""
        return [{
            "name": s.name, "cat": s.kind, "ph": "X",
            "ts": s.start * 1e6, "dur": max(s.duration, 0.0) * 1e6,
            "pid": 0, "tid": 0,
            "args": {**s.attrs, "id": s.id, "parent": s.parent,
                     "request": s.request},
        } for s in self.spans()]

    def export_chrome(self, path: str) -> int:
        """Write the Chrome trace JSON to ``path``; returns span count."""
        events = self.chrome_trace()
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
        return len(events)


#: the process-global tracer instrumented modules record into
TRACER = Tracer()


def span(name: str, kind: str | None = None, **attrs):
    """``TRACER.span(...)`` -- the convenience most call sites use."""
    return TRACER.span(name, kind=kind, **attrs)
