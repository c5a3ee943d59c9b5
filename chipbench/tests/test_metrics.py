"""The metric readers on hand-made runs: what each reads, and that a reader
with nothing to read returns None."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from chipbench import harness


def _read(name, run):
    return harness.plugin("metrics", name).read(run)


def _answer(t_due, t_done, status="converged", iters=10):
    return harness.Answer(b=0, t_due=t_due, t_done=t_done, iters=iters,
                          status=status)


def _run(answers, t0=0.0, t1=10.0, traced=None, **kw):
    w = harness.Window(t0=t0, t1=t1, answers=answers, traced=traced)
    return NS(window=w, setup_s=kw.get("setup_s", 3.0),
              work=kw.get("work", {}), peaks=kw.get("peaks", {}))


def test_closed_loop_metrics():
    run = _run([_answer(0, 4, iters=100), _answer(4, 8, iters=110),
                _answer(8, 12, iters=120)], t1=12.0)
    assert _read("solve_s", run) == pytest.approx(4.0)
    assert _read("iters.solo", run) == pytest.approx(110.0)
    assert _read("setup_s", run) == 3.0


def test_traced_readers():
    red = {"busy_s": 2.0, "window_s": 2.5, "collective_s": 0.05}
    tr = harness.Traced(t0=0.0, t1=2.5, answers=[_answer(0, 1, iters=300),
                                                 _answer(1, 2, iters=200)],
                        reduced=red)
    run = _run([], traced=tr, work={"bytes": 1e8},
               peaks={"hbm_bytes_per_s": 1e12})
    assert _read("device_idle.solo", run) == pytest.approx(20.0)
    # 500 iterations x 1e8 B at 1e12 B/s = 0.05 s of 2 s busy
    assert _read("iter_roofline.solo", run) == pytest.approx(2.5)
    assert _read("collective_ms.mesh", run) == pytest.approx(0.1)


@pytest.mark.parametrize("name", ["device_idle.solo", "iter_roofline.solo",
                                  "collective_ms.mesh"])
def test_nothing_to_read_is_none(name):
    assert _read(name, _run([])) is None
    empty = harness.Traced(t0=0, t1=1, answers=[], reduced=None)
    assert _read(name, _run([], traced=empty)) is None
