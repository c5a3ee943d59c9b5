"""The per-layer readers of the program's scopes, spans and counters, on
hand-made runs; and the readings an existing reader takes from the
recorded traces, pinned as they were before these readers were added."""

from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import harness, scopes, trace_reduce

DATA = Path(__file__).resolve().parent / "data"

SCOPE_MAP = {"fusion.4": "gather", "stream_rows_dot.6": "matvec",
             "cg_update.6": "update", "broadcast.51": "update",
             "while.4": "control", "broadcast_select_fusion.8": "control"}
TOP_OPS = [["fusion.4 kCustom", 2.0], ["stream_rows_dot.6", 0.5],
           ["cg_update.6", 0.3], ["broadcast.51", 0.1],
           ["broadcast_select_fusion.8 kLoop", 0.05], ["while.4", 0.15],
           ["copy.3", 0.25]]


def _read(name, run):
    return harness.plugin("metrics", name).read(run)


def _answer(iters):
    return harness.Answer(b=0, t_due=0.0, t_done=1.0, iters=iters,
                          status="converged")


def _run(reduced, iters=(60, 40)):
    answers = [_answer(i) for i in iters]
    tr = harness.Traced(t0=0.0, t1=2.0, answers=answers, reduced=reduced)
    return NS(window=harness.Window(t0=0.0, t1=2.0, answers=answers,
                                    traced=tr),
              setup_s=1.0, work={}, peaks={})


def test_seconds_by_scope_strips_the_fusion_kind():
    got = scopes.seconds_by_scope(TOP_OPS, SCOPE_MAP)
    assert got == pytest.approx({"gather": 2.0, "matvec": 0.5,
                                 "update": 0.4, "control": 0.2,
                                 "other": 0.25})


@pytest.mark.parametrize("name, want", [
    ("gather_ms.solo", 1e3 * 2.0 / 100),
    ("update_ms.solo", 1e3 * 0.4 / 100),
    ("control_ms.solo", 1e3 * 0.2 / 100),
])
def test_scope_readers(name, want, monkeypatch):
    monkeypatch.setattr(scopes, "scope_map", lambda: dict(SCOPE_MAP))
    run = _run({"top_ops": TOP_OPS})
    assert _read(name, run) == pytest.approx(want)
    # nothing to read: no trace, no map, no iterations, no time in scope
    assert _read(name, _run(None)) is None
    assert _read(name, _run({"top_ops": TOP_OPS}, iters=(0,))) is None
    assert _read(name, _run({"top_ops": [["copy.3", 1.0]]})) is None
    monkeypatch.setattr(scopes, "scope_map", lambda: None)
    assert _read(name, run) is None


def test_scope_map_of_a_program_without_one_is_none(monkeypatch):
    import sys

    import repro.obs.scopes as program_scopes

    monkeypatch.setattr(program_scopes, "SCOPES",
                        program_scopes.ScopeMap())
    assert scopes.scope_map() is None             # compiled nothing yet
    monkeypatch.setitem(sys.modules, "repro.obs.scopes", None)
    assert scopes.scope_map() is None             # predates the scopes


@pytest.fixture
def registry(monkeypatch):
    import repro.obs

    reg = repro.obs.Registry()
    monkeypatch.setattr(repro.obs, "REGISTRY", reg)
    return reg


def test_stage_and_transfer_readers(registry):
    stage = registry.histogram("repro_solve_stage_seconds", "",
                               ("phase",))
    for t_in, t_out in ((0.010, 0.004), (0.012, 0.006)):
        stage.observe(t_in, phase="in")
        stage.observe(t_out, phase="out")
    registry.counter("repro_solve_executions_total", "",
                     ("method",)).inc(2, method="pcg_tol")
    registry.counter("repro_solve_h2d_bytes_total").inc(2 * 8_000_000)
    registry.counter("repro_solve_d2h_bytes_total").inc(2 * 4_032_016)
    run = _run({"top_ops": []})
    assert _read("stage_ms.solo", run) == pytest.approx(16.0)
    assert _read("xfer_mb.solo", run) == pytest.approx(12.032016)
    # read only from a traced window that reached a device
    assert _read("stage_ms.solo", _run(None)) is None
    assert _read("xfer_mb.solo", _run(None)) is None


@pytest.mark.parametrize("name", ["stage_ms.solo", "xfer_mb.solo"])
def test_program_readers_without_counters_are_none(name, registry):
    # a program that predates the counters registers none of them
    assert _read(name, _run({"top_ops": []})) is None
    if name == "stage_ms.solo":
        registry.histogram("repro_solve_stage_seconds", "", ("phase",))
    else:
        registry.counter("repro_solve_executions_total", "", ("method",))
        registry.counter("repro_solve_h2d_bytes_total")
        registry.counter("repro_solve_d2h_bytes_total")
    assert _read(name, _run({"top_ops": []})) is None      # no solve yet


GOLDEN = json.loads((DATA / "tpu_traces.golden.json").read_text())


@pytest.mark.parametrize("trace", sorted(GOLDEN))
def test_recorded_traces_read_as_before(trace):
    """trace_reduce and the four readers that predate the program's scopes
    give, on the recorded v5e traces, exactly what they gave then."""
    from jax.profiler import ProfileData

    want = GOLDEN[trace]
    red = trace_reduce.reduce(ProfileData.from_file(str(DATA / trace)),
                              want["n_devices"])
    assert json.loads(json.dumps(red)) == want["reduce"]
    run = _run(red, iters=(100, 120))
    run.work = {"bytes": 1e8}
    run.peaks = {"hbm_bytes_per_s": 819e9}
    got = {m: _read(m, run) for m in want["readers"]}
    assert got == want["readers"]
