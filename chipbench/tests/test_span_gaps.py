"""span_gaps: device time by scope and idle time by program span, on
hand-made events, and the traced-run wiring on the CPU."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from chipbench import span_gaps, trace_reduce


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _trace(host, ops, device="/device:TPU:0"):
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
        NS(name=device, lines=[NS(name="XLA Ops", events=ops)])])


HOST = [
    _ev("chipbench.traced", 0, 1000),
    _ev("chipbench.solve", 0, 900),
    _ev("repro.solve", 10, 880),
    _ev("repro.solve.stage_in", 10, 190),
    _ev("to_device_vec", 50, 100),           # not a span: never names a gap
    _ev("repro.solve.execute", 200, 500),
    _ev("repro.solve.stage_out", 700, 190),
]
OPS = [_ev("%fusion.4 = f32[8] fusion(f32[8] %x), kind=kCustom", 200, 300),
       _ev("%cg_update.6 = (f32[8]) custom-call(f32[8] %r)", 550, 150)]


def test_idle_is_named_by_the_innermost_program_span():
    idle = span_gaps.idle_by_span(_trace(HOST, OPS), n_devices=1)
    # gaps: 0..200 (midpoint in stage_in), 500..550 (execute: the host
    # waits on the device), 700..1000 (midpoint 850 in stage_out)
    assert idle == pytest.approx({"repro.solve.stage_out": 300e-9,
                                  "repro.solve.stage_in": 200e-9,
                                  "repro.solve.execute": 50e-9})


def test_idle_outside_program_spans_falls_back_to_the_harness():
    host = HOST[:2] + [_ev("chipbench.wait", 920, 80)]
    ops = [_ev("%a.1 = f32[8] add(f32[8] %x)", 100, 700)]
    idle = span_gaps.idle_by_span(_trace(host, ops), n_devices=1)
    # 0..100 under chipbench.solve; 800..1000 (midpoint 900) under
    # chipbench.solve too; nothing covers a gap outside both
    assert idle == pytest.approx({"chipbench.solve": 300e-9})
    far = [_ev("chipbench.traced", 0, 1000), _ev("chipbench.solve", 0, 50)]
    idle = span_gaps.idle_by_span(_trace(far, ops), n_devices=1)
    assert idle == pytest.approx({"(host idle)": 200e-9,
                                  "chipbench.solve": 100e-9})


def test_idle_reads_nothing_without_window_or_device():
    assert span_gaps.idle_by_span(_trace(HOST[1:], OPS), 1) is None
    assert span_gaps.idle_by_span(_trace(HOST, []), 1) is None
    assert span_gaps.idle_by_span(
        _trace(HOST, OPS, device="/device:TPU:3"), n_devices=1) is None


def test_by_scope_sums_self_time_per_scope():
    red = {"top_ops": [["fusion.4 kCustom", 3.0], ["cg_update.6", 1.0],
                       ["copy.1", 0.5]]}
    got = span_gaps.by_scope(red, {"fusion.4": "gather",
                                   "cg_update.6": "update"})
    assert got == {"gather": 3.0, "update": 1.0, "other": 0.5}
    assert span_gaps.by_scope(None, {"a": "gather"}) is None
    assert span_gaps.by_scope(red, None) is None


def test_instrumented_traced_run_on_the_cpu(monkeypatch):
    # off the chip the trace has no device plane: the run stays correct,
    # the trace is read once, both readings are None, and the result line
    # gains nothing
    import time

    import jax

    from chipbench.tests import tiny

    monkeypatch.setattr(trace_reduce, "reduce_dir", trace_reduce.reduce_dir)
    mod = tiny.run_module()
    found = span_gaps.instrument(mod)
    cell = tiny.cell("poisson3d_128_mf.solo",
                     trace={"lead_s": 0.1, "min_s": 0.3, "min_units": 2})
    res = mod.execute(cell, 2**33 + 5, 1.0, True, jax.devices()[:1],
                      time.perf_counter())
    assert res["correct"] and "breakdown" not in res
    assert found == {"idle_by_span": None, "reduced": None}
