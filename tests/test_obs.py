"""``repro.obs`` contract tests.

Three things the observability subsystem promises, each pinned here:

1. **Exposition golden.**  The Prometheus text format is an interchange
   contract (a scraper parses it byte-by-byte), so it is golden-tested
   on a private :class:`Registry` -- counter/gauge/histogram rendering,
   label escaping, cumulative ``le`` buckets, ``+Inf`` overflow.
2. **Bitwise identity.**  All recording is host-side: an instrumented
   solve returns EXACTLY the bits of a bare one (``obs.disabled()``),
   single-RHS and batched, locally and (smoke, ``dist`` marker) on a
   forced 4-device mesh.
3. **Deterministic time.**  Every host-side timing path reads the one
   injectable clock, so installing a :class:`FakeClock` makes latency
   histograms, span durations and straggler detection exact.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro import obs
from repro.core import AzulEngine, SolveSpec
from repro.data.matrices import laplacian_2d
from repro.obs.clock import FakeClock

TOL = 1e-8


# -- exposition golden --------------------------------------------------------


def test_prometheus_golden_exact_text():
    reg = obs.Registry()
    c = reg.counter("jobs_total", "jobs processed", ("queue",))
    c.inc(3, queue="fast")
    c.inc(queue='we"ird')                      # label escaping
    reg.gauge("depth", "current queue depth").set(2.5)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.01, 0.1, 1.0))
    h.observe(0.005)                           # first bucket
    h.observe(0.5)                             # third bucket
    h.observe(50.0)                            # +Inf overflow
    want = "\n".join([
        "# HELP depth current queue depth",
        "# TYPE depth gauge",
        "depth 2.5",
        "# HELP jobs_total jobs processed",
        "# TYPE jobs_total counter",
        'jobs_total{queue="fast"} 3',
        'jobs_total{queue="we\\"ird"} 1',
        "# HELP lat_seconds latency",
        "# TYPE lat_seconds histogram",
        'lat_seconds_bucket{le="0.01"} 1',
        'lat_seconds_bucket{le="0.1"} 1',
        'lat_seconds_bucket{le="1"} 2',
        'lat_seconds_bucket{le="+Inf"} 3',
        "lat_seconds_sum 50.505",
        "lat_seconds_count 3",
    ]) + "\n"
    assert obs.render_prometheus(reg) == want


def test_snapshot_roundtrips_the_same_registry():
    reg = obs.Registry()
    reg.counter("a_total", "a").inc(2)
    reg.histogram("h", "h", buckets=(1.0,)).observe(3.0)
    snap = obs.snapshot(reg)
    assert snap["a_total"]["samples"][0]["value"] == 2
    assert snap["h"]["samples"][0] == {
        "labels": {}, "sum": 3.0, "count": 1,
        "buckets": {"1": 0}, "overflow": 1}


def test_registry_idempotent_and_mismatch_raises():
    reg = obs.Registry()
    a = reg.counter("x_total", "x", ("k",))
    assert reg.counter("x_total", "x", ("k",)) is a
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x", ("k",))          # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("x_total", "x", ("other",))    # label mismatch
    with pytest.raises(ValueError):
        a.inc(-1, k="v")                           # counters only go up


def test_histogram_quantile_and_disabled_noop():
    h = obs.Registry().histogram("q", "q", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    assert h.quantile(0.5) == 1.0
    assert h.quantile(0.99) == 10.0
    with obs.disabled():
        h.observe(100.0)                       # dropped
    assert h._default().count == 4


# -- bitwise identity ---------------------------------------------------------


def _solve_pair(spec_kwargs, b):
    """(instrumented bits, bare bits) from the SAME warm plan."""
    eng = AzulEngine(laplacian_2d(16), precond="jacobi", dtype=np.float64)
    plan = eng.plan(SolveSpec(**spec_kwargs))
    plan(b)                                     # warm (compile outside arms)
    x_on = np.asarray(plan(b)[0])
    with obs.disabled():
        x_off = np.asarray(plan(b)[0])
    return x_on, x_off


def test_instrumented_solve_bitwise_identical_single():
    rng = np.random.default_rng(0)
    b = rng.standard_normal(laplacian_2d(16).shape[0])
    x_on, x_off = _solve_pair(dict(method="pcg", iters=40), b)
    assert np.array_equal(x_on, x_off)


def test_instrumented_solve_bitwise_identical_batched():
    rng = np.random.default_rng(1)
    b = rng.standard_normal((3, laplacian_2d(16).shape[0]))
    x_on, x_off = _solve_pair(dict(method="pcg", iters=40, batch=3), b)
    assert np.array_equal(x_on, x_off)


def test_solve_instrumentation_records_metrics_and_spans():
    before = obs.REGISTRY.counter(
        "repro_solve_executions_total", "", ("method",)).value(method="pcg")
    obs.TRACER.clear()
    eng = AzulEngine(laplacian_2d(8), precond="jacobi", dtype=np.float64)
    plan = eng.plan(SolveSpec(method="pcg", iters=10))
    plan(np.ones(eng.n))
    plan(np.ones(eng.n))
    after = obs.REGISTRY.counter(
        "repro_solve_executions_total", "", ("method",)).value(method="pcg")
    assert after - before == 2
    counts = obs.TRACER.counts()
    assert counts.get("solve", 0) >= 2
    assert counts.get("plan_build", 0) >= 1
    # the lazy HLO summary must not count as a plan retrace
    tr = plan.traces
    assert plan.hlo_summary() == {"count_by_op": {}, "total_count": 0.0}
    assert plan.traces == tr
    plan.assert_steady()


_DIST_SCRIPT = """
import numpy as np
import jax
jax.config.update("jax_enable_x64", True)
from repro import obs
from repro.core import AzulEngine, SolveSpec
from repro.data.matrices import laplacian_2d
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 1), ("data", "model"))
m = laplacian_2d(16)
eng = AzulEngine(m, mesh=mesh, mode="1d", precond="jacobi",
                 dtype=np.float64)
b = np.random.default_rng(0).standard_normal(m.shape[0])
plan = eng.plan(SolveSpec(method="pcg", iters=30, layout="halo"))
plan(b)
x_on = np.asarray(plan(b)[0])
with obs.disabled():
    x_off = np.asarray(plan(b)[0])
assert np.array_equal(x_on, x_off), "dist obs-on/off bits diverged"
assert obs.REGISTRY.counter(
    "repro_solve_executions_total", "", ("method",)).value(method="pcg") == 2
print("OBS_DIST_OK")
"""


@pytest.mark.dist
def test_obs_bitwise_identity_multidevice():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    env["JAX_ENABLE_X64"] = "1"
    r = subprocess.run(
        [sys.executable, "-c", _DIST_SCRIPT],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)), timeout=560,
    )
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert "OBS_DIST_OK" in r.stdout


# -- deterministic time (FakeClock) -------------------------------------------


def test_fake_clock_makes_spans_and_histograms_exact():
    tracer = obs.Tracer(capacity=8)
    h = obs.Registry().histogram("t", "t", buckets=(0.1, 1.0))
    with obs.clock.override(FakeClock()) as fake:
        with tracer.span("work", kind="chunk") as s:
            fake.advance(0.25)
        h.observe(obs.clock.now() - s.start)
    assert s.duration == 0.25
    assert h.quantile(0.5) == 1.0              # 0.25 lands in the 1.0 bucket
    # ring bound: capacity+1 spans -> exactly one dropped
    tracer.clear()
    with obs.clock.override(FakeClock()):
        for i in range(9):
            with tracer.span(f"s{i}", kind="x"):
                pass
    assert len(tracer.spans()) == 8 and tracer.dropped == 1


def test_fake_clock_sleep_advances_instead_of_blocking():
    with obs.clock.override(FakeClock(start=100.0)) as fake:
        t0 = obs.clock.now()
        obs.clock.sleep(5.0)
        assert obs.clock.now() - t0 == 5.0
        assert fake.now() == 105.0


def test_step_timer_straggler_detection_deterministic():
    from repro.ft.straggler import StepTimer

    timer = StepTimer(window=50, deadline_factor=2.0)
    with obs.clock.override(FakeClock()) as fake:
        for i in range(6):                     # steady 0.1 s steps
            with timer.timing(i):
                fake.advance(0.1)
        assert timer.last_report.is_straggler is False
        with timer.timing(6):                  # 10x blowout
            fake.advance(1.0)
    rep = timer.last_report
    assert rep.is_straggler is True
    assert rep.duration == 1.0 and rep.median == 0.1
    assert rep.shed_advice == 1


def test_chrome_trace_export(tmp_path):
    tracer = obs.Tracer()
    with obs.clock.override(FakeClock(start=1.0)) as fake:
        with tracer.span("solve", kind="solve", matrix="lap2d_16") as s:
            with tracer.span("solve.execute"):
                fake.advance(0.5)
    path = tmp_path / "trace.json"
    assert tracer.export_chrome(str(path)) == 2
    import json

    child, ev = json.loads(path.read_text())["traceEvents"]
    assert ev == {"name": "solve", "cat": "solve", "ph": "X",
                  "ts": 1.0e6, "dur": 0.5e6, "pid": 0, "tid": 0,
                  "args": {"matrix": "lap2d_16", "id": s.id,
                           "parent": None, "request": s.id}}
    assert child["name"] == "solve.execute"
    assert child["args"]["parent"] == child["args"]["request"] == s.id
    assert child["args"]["id"] != s.id


# -- HTTP exposition ----------------------------------------------------------


def test_metrics_server_serves_all_three_endpoints():
    import json
    import urllib.request

    reg = obs.Registry()
    reg.counter("up_total", "u").inc(7)
    tracer = obs.Tracer()
    with tracer.span("s", kind="tick"):
        pass
    with obs.start_metrics_server(port=0, registry=reg,
                                  tracer=tracer) as srv:
        base = f"http://{srv.host}:{srv.port}"
        with urllib.request.urlopen(f"{base}/metrics") as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            assert b"up_total 7" in r.read()
        with urllib.request.urlopen(f"{base}/metrics.json") as r:
            assert json.load(r)["up_total"]["samples"][0]["value"] == 7
        with urllib.request.urlopen(f"{base}/trace.json") as r:
            assert len(json.load(r)["traceEvents"]) == 1
