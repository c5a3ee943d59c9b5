"""One run of one benchmark cell, as parts that tests can drive without a chip.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Everything that belongs to one of them
sits in files of its own, found by name:

    chipbench/configs/<config>.json    sizes, solver, limits (the ``file``)
    chipbench/operators/<kind>.py      builds the operator the program
                                       runs, and its float64 reference
    chipbench/rhs/<kind>.py            right-hand sides drawn from a seed
    chipbench/traffic/<mix>.json       loop kind and its parameters
    chipbench/loops/<loop>.py          drives the program through a window
    chipbench/metrics/<metric>.py      reads one metric from a finished run

A run is: set-up (operator, engine, plans, warm-up, inputs from the seed),
one measured window, then -- with the program's state freed -- the check
of the answers against the float64 reference, and the result line.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import re
import shutil
import sys
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# traces go here (git-ignored), one run at a time, and are deleted once read
TRACE_DIR = ROOT / ".chipbench" / "trace"


class Refused(Exception):
    """The run cannot start: exit non-zero and print no result."""


# -- finding the cell's files by name ----------------------------------------

def load_benchmark() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise Refused(f"no {path}")
    return json.loads(path.read_text())


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"unknown {what} {name!r}")


def plugin(kind: str, name: str):
    """The module ``chipbench/<kind>/<name>.py``, loaded by path (names may
    hold dots, which an import statement cannot)."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {kind} file {path.relative_to(ROOT)}")
    modname = f"chipbench_{kind}_{re.sub(r'[^0-9A-Za-z_]', '_', name)}"
    mod = sys.modules.get(modname)
    if mod is None:
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    """One workload with everything its name resolves to."""

    name: str
    chips: int
    cfg: dict                   # the configuration file, as run
    traffic: dict               # the traffic file
    operator: object            # chipbench/operators/<cfg operator kind>.py
    rhs_kind: str               # the traffic's rhs, else the configuration's
    rhs: object                 # chipbench/rhs/<rhs_kind>.py
    loop: object                # chipbench/loops/<traffic loop>.py
    end_to_end: list            # BENCHMARK.json metric entries of this cell
    per_layer: list


def resolve(workload: str) -> Cell:
    bench = load_benchmark()
    wl = _by_name(bench["workloads"], workload, "workload")
    centry = _by_name(bench["configs"], wl["config"], "configuration")
    cfg = json.loads((ROOT / centry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{wl['traffic']}.json").read_text())
    rhs_kind = traffic.get("rhs") or cfg["rhs"]
    return Cell(
        name=workload, chips=int(wl["chips"]), cfg=cfg, traffic=traffic,
        operator=plugin("operators", cfg["operator"]["kind"]),
        rhs_kind=rhs_kind, rhs=plugin("rhs", rhs_kind),
        loop=plugin("loops", traffic["loop"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


# -- what a window produces --------------------------------------------------

@dataclass
class Answer:
    """One solve or request of the window."""

    b: int                          # index into the run's RHS pool
    t_due: float                    # scheduled arrival (closed loop: sent)
    t_done: float | None = None     # x on the host; None = never answered
    iters: int = -1
    status: str = "unanswered"
    rnorm: float | None = None      # the solver's final recursive ||r||
    x: np.ndarray | None = None     # kept only while the Sample holds it
    residual: float | None = None   # filled by the check, float64


class Sample:
    """A reservoir of ``size`` answers drawn from the seed (Algorithm R).

    The loop offers each answer as it completes; the reservoir keeps the
    x of the answers it holds and drops every other x at once, so host
    memory stays flat however many solves a window completes."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size = size
        self.rng = rng
        self.held: list = []
        self.seen = 0

    def offer(self, answer: Answer) -> None:
        j = self.seen
        self.seen += 1
        if len(self.held) < self.size:
            self.held.append(answer)
            return
        r = int(self.rng.integers(0, j + 1))
        if r < self.size:
            self.held[r].x = None
            self.held[r] = answer
        else:
            answer.x = None


@dataclass
class Traced:
    """The profiled sub-window of a ``--trace 1`` run."""

    t0: float                       # host clock (repro.obs.clock)
    t1: float
    answers: list                   # Answers completed inside it
    reduced: dict | None = None     # trace_reduce.reduce(...)


@dataclass
class Window:
    t0: float
    t1: float
    answers: list
    compiles: int = 0               # compiles or cache loads inside [t0, t1]
    traced: Traced | None = None
    notes: dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    setup_s: float
    window: Window
    pool: np.ndarray                # (P, n) float32 right-hand sides
    device: dict
    work: dict                      # work.per_iteration(cell.cfg)
    peaks: dict                     # peaks.for_kind(device kind)


# -- the profiler, switched at loop boundaries -------------------------------

class Profiler:
    """Profiles one steady sub-window of the measured window.

    Loops call :meth:`boundary` between units of work (the closed loop:
    a whole solve).  The trace starts at the first boundary at
    least ``lead_s`` into the window and stops at the first boundary after
    which it holds at least ``min_s`` seconds and ``min_units`` units.
    With ``enabled=False`` every call is a no-op.
    """

    def __init__(self, policy: dict | None, enabled: bool):
        self.enabled = enabled
        self.policy = policy or {}
        self.state = "before"
        self.t0 = self.t1 = None
        self.units0 = 0
        self._ann = None

    @contextlib.contextmanager
    def phase(self, name: str):
        """Annotate a harness phase in the trace (no-op untraced)."""
        if not self.enabled:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(f"chipbench.{name}"):
            yield

    def boundary(self, now: float, t_window0: float, units: int) -> None:
        if not self.enabled or self.state == "done":
            return
        p = self.policy
        if self.state == "before":
            if now - t_window0 >= float(p.get("lead_s", 0.0)):
                self._start(units)
        elif (now - self.t0 >= float(p.get("min_s", 0.0))
              and units - self.units0 >= int(p.get("min_units", 1))):
            self._stop()

    def finish(self) -> None:
        """End of the window: stop a trace still running."""
        if self.enabled and self.state == "on":
            self._stop()

    @staticmethod
    def clock() -> float:
        from repro.obs import clock

        return clock.now()

    def _start(self, units: int) -> None:
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        self._ann = jax.profiler.TraceAnnotation("chipbench.traced")
        self._ann.__enter__()
        self.state = "on"
        self.units0 = units
        self.t0 = self.clock()

    def _stop(self) -> None:
        import jax

        self.t1 = self.clock()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def traced(self, answers: list) -> Traced | None:
        if self.state != "done":
            return None
        inside = [a for a in answers if a.t_done is not None
                  and self.t0 <= a.t_done <= self.t1]
        return Traced(t0=self.t0, t1=self.t1, answers=inside)


class CompileCounter:
    """Counts XLA compiles and persistent-cache loads while armed."""

    def __init__(self):
        self.count = 0
        self.armed = False

    def install(self) -> None:
        from jax import monitoring

        def on_duration(event: str, duration: float, **_):
            if self.armed and event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        def on_event(event: str, **_):
            if self.armed and event == "/jax/compilation_cache/cache_hits":
                self.count += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


# -- the check against the float64 reference ---------------------------------

def limit(cell: Cell) -> float:
    """The residual limit of the cell's kind of right-hand side: how close
    float32 can come depends on it (``PERF.md`` gives the readings)."""
    return float(cell.cfg["limits"]["rel_residual"][cell.rhs_kind])


# how many answers a run keeps the x of, and checks against the reference
CHECK_SAMPLE = 16

# the solver's own final residual may exceed rtol * ||b|| (float64) by
# float32's rounding of ||b||, and by nothing more
RECURSIVE_SLACK = 1.01


def _finite(v: float) -> float:
    """A reading that is not a number (a non-finite x) reads as 1e300."""
    return v if v == v and v < 1e300 else 1e300


def check(run: Run) -> dict:
    """Judge the window's answers; returns the numbers compared, each with
    its limit, the failure count and ``correct``.

    Every answered request must be ``converged`` and must report a final
    recursive residual within the configuration's rtol (the guarantee it
    states); the answers the Sample kept are judged by their true
    residual under the float64 reference operator; none may be left
    unanswered."""
    from . import reference

    cfg = run.cell.cfg
    limit_ = limit(run.cell)
    rtol_limit = float(cfg["solver"]["rtol"]) * RECURSIVE_SLACK
    answers = run.window.answers
    kept = [a for a in answers if a.x is not None]
    matvec = run.cell.operator.reference_matvec(cfg)
    for a in kept:
        a.residual = reference.rel_residual(matvec, run.pool[a.b], a.x)
    worst = max((_finite(a.residual) for a in kept), default=None)
    bnorm = np.linalg.norm(np.asarray(run.pool, np.float64), axis=-1)
    recursive = {id(a): _finite(float(a.rnorm) / bnorm[a.b])
                 for a in answers if a.rnorm is not None}
    answered = [a for a in answers if a.t_done is not None]
    unanswered = len(answers) - len(answered)
    not_converged = sum(1 for a in answered if a.status != "converged")
    worst_recursive = max(recursive.values(), default=None)

    def bad(a: Answer) -> bool:
        return (a.t_done is None or a.status != "converged"
                or not recursive.get(id(a), 1e300) <= rtol_limit
                or (a.residual is not None and not a.residual <= limit_))

    failed = sum(1 for a in answers if bad(a))
    return {
        "checks": {
            "max_rel_residual": {"value": worst, "limit": limit_},
            "max_recursive_rel_residual": {"value": worst_recursive,
                                           "limit": rtol_limit},
            "not_converged": {"value": not_converged, "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0},
        },
        "checked": len(kept),
        "failed": failed,
        "correct": worst is not None and failed == 0,
    }


def read_metrics(run: Run, entries: list) -> dict:
    """Each metric's reader, ``chipbench/metrics/<name>.py``; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        value = plugin("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out

