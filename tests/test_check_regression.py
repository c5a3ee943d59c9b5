"""The CI perf-regression gate (benchmarks/check_regression.py): unit
checks of the comparison logic (exact iteration counts, equivalence
thresholds, generous timing ratio, coverage), seeded-regression failures,
the --update-baseline escape hatch, and the committed artifacts actually
passing the gate (the bench-smoke job's contract)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from check_regression import check, main as gate_main  # noqa: E402

BASELINE = os.path.join(REPO, "benchmarks", "BENCH_baseline.json")
CURRENT = os.path.join(REPO, "BENCH_pcg.json")


def _payload():
    return {
        "schema": "bench_pcg/v8",
        "fused_vs_unfused": [{
            "matrix": "m", "us_per_iter_fused": 100.0,
            "us_per_iter_unfused": 120.0, "trace_rel_maxdiff": 0.0,
            "x_maxdiff": 0.0, "modeled_traffic": {"reduction": 2.0},
        }],
        "batch_sweep": [{
            "matrix": "m", "k": 4, "us_per_iter_per_rhs": 25.0,
            "batch_vs_seq_maxerr": 0.0,
        }],
        "tol_solves": [{
            "matrix": "m", "precond": "block_ic0", "tol": 1e-8,
            "substrate_fused": "fused_ic0", "iters_fused": 30,
            "iters_reference": 30, "iters_match": True, "x_maxdiff": 0.0,
            "us_per_iter_fused": 200.0, "us_per_iter_unfused": 220.0,
        }],
        "noc_plans": [{
            "matrix": "m", "reorder": "none", "mode": "1d", "grid": "8",
            "plan": "halo", "halo_width": 2,
            "gather_words_halo": 256, "gather_words_dense": 896,
            "bytes_per_iter_halo": 2048, "bytes_per_iter_dense": 7168,
            "reduction": 3.5,
            "interior_frac_nnz": 0.8, "overlap_interior_words": 300,
            "overlap_hidden_words": 256, "overlap_exposed_words": 0,
            "overlap_efficiency": 1.0,
        }],
        "pipelined": [{
            "matrix": "m", "precond": "jacobi", "tol": 1e-8,
            "iters_pipelined": 30, "iters_pcg": 30,
            "x_vs_pcg_maxdiff": 0.0, "r0_reldiff": 0.0,
            "reductions_per_iter_pipelined": 1,
            "reductions_per_iter_pcg": 2,
            "us_per_iter_pipelined": 150.0, "us_per_iter_pcg": 180.0,
        }],
        "guarded": [{
            "matrix": "m", "method": "pcg_tol", "precond": "jacobi",
            "tol": 1e-8, "iters_guarded": 30,
            "status_clean": "converged",
            "collectives_guarded": 0,
            "detects_indefinite": True, "bad_x_finite": True,
            "us_per_iter_guarded": 205.0,
        }],
        "serving": [{
            "matrix": "m", "n": 64, "method": "pcg_tol", "mode": "open",
            "requests": 24, "chunk": 20, "max_batch": 4,
            "offered_rps": 10.0, "concurrency": -1,
            "completed": 24, "rejected": 0, "errors": 0, "retraces": 0,
            "p50_ms": 40.0, "p99_ms": 90.0, "mean_ms": 45.0,
            "throughput_rps": 9.5, "chunks": 30, "rebuckets": 8,
            "plans": 3,
        }],
        "observability": [{
            "matrix": "m", "method": "pcg", "n": 64, "iters": 60,
            "repeats": 5, "us_per_iter_instrumented": 104.0,
            "us_per_iter_bare": 100.0, "overhead_ratio": 1.04,
            "bitwise_identical": True, "required_families_present": True,
            "span_kinds_present": True,
            "span_counts": {"plan_build": 1, "solve": 12},
            "metric_families": 20,
        }],
        "formats": [{
            "kind": "format_autotune", "matrix": "m", "n": 1000, "nnz": 9000,
            "chosen_format": "hyb",
            "modeled_words": {"ell": 800000, "sell": 60000, "hyb": 23000,
                              "hyb_core_width": 6},
            "modeled_reduction_vs_ell": 34.0,
            "beats_ell_modeled": True, "beats_ell_wall": True,
            "wall_gated": True, "wall_speedup_vs_ell": 2.0,
            "iters_auto": 19, "iters_ell": 19, "iters_match": True,
            "x_vs_ell_maxdiff": 0.0, "fused_matches_reference": True,
            "us_per_iter_auto": 300.0, "us_per_iter_ell": 650.0,
        }, {
            "kind": "plan_scaling", "matrix": "bidiag_1024",
            "points": [
                {"levels": 128, "plan_s_scan": 0.07, "plan_s_unrolled": 1.7},
                {"levels": 1024, "plan_s_scan": 0.04, "plan_s_unrolled": 12.6},
            ],
            "growth_scan": 0.55, "growth_unrolled": 7.4,
            "scan_sublinear_vs_unrolled": True,
        }],
    }


def test_identical_payload_passes():
    g = check(_payload(), _payload())
    assert not g.failures and g.checks > 5


def test_iteration_count_drift_fails():
    cur = _payload()
    cur["tol_solves"][0]["iters_fused"] = 31
    g = check(cur, _payload())
    assert any("iters_fused" in f for f in g.failures)


def test_fused_reference_divergence_fails():
    cur = _payload()
    cur["tol_solves"][0]["iters_match"] = False
    cur["fused_vs_unfused"][0]["trace_rel_maxdiff"] = 1e-3
    g = check(cur, _payload())
    assert any("iters_match" in f for f in g.failures)
    assert any("trace_rel_maxdiff" in f for f in g.failures)


def test_timing_regression_beyond_ratio_fails():
    cur = _payload()
    cur["fused_vs_unfused"][0]["us_per_iter_fused"] = 100.0 * 11
    g = check(cur, _payload(), timing_ratio=10.0)
    assert any("us_per_iter_fused" in f for f in g.failures)
    # within the generous ratio (cross-machine noise): fine
    cur["fused_vs_unfused"][0]["us_per_iter_fused"] = 100.0 * 9
    assert not check(cur, _payload(), timing_ratio=10.0).failures
    # faster is never a failure
    cur["fused_vs_unfused"][0]["us_per_iter_fused"] = 1.0
    assert not check(cur, _payload(), timing_ratio=10.0).failures


def test_substrate_downgrade_fails():
    """An accidentally-reference fused path (the gate's raison d'etre)."""
    cur = _payload()
    cur["tol_solves"][0]["substrate_fused"] = "reference"
    g = check(cur, _payload())
    assert any("substrate_fused" in f for f in g.failures)


def test_dropped_benchmark_fails():
    cur = _payload()
    cur["tol_solves"] = []
    g = check(cur, _payload())
    assert any("missing" in f for f in g.failures)


def test_modeled_traffic_change_fails():
    cur = _payload()
    cur["fused_vs_unfused"][0]["modeled_traffic"] = {"reduction": 3.0}
    g = check(cur, _payload())
    assert any("modeled_traffic" in f for f in g.failures)


def test_halo_plan_dense_fallback_fails():
    """A config that used to cut a halo plan and now falls back to dense
    all-gathers is a NoC-traffic regression with a dedicated message."""
    cur = _payload()
    cur["noc_plans"][0]["plan"] = "dense"
    g = check(cur, _payload())
    assert any("halo-plan regression" in f for f in g.failures)


def test_halo_width_growth_fails():
    """Halo width and modeled bytes are host-deterministic: any drift is a
    real partitioning/comm-plan behaviour change."""
    cur = _payload()
    cur["noc_plans"][0]["halo_width"] = 5
    cur["noc_plans"][0]["bytes_per_iter_halo"] = 5120
    g = check(cur, _payload())
    assert any("halo_width" in f for f in g.failures)
    assert any("bytes_per_iter_halo" in f for f in g.failures)


def test_pipelined_iteration_drift_fails():
    cur = _payload()
    cur["pipelined"][0]["iters_pipelined"] = 35
    g = check(cur, _payload())
    assert any("iters_pipelined" in f for f in g.failures)


def test_pipelined_reduction_structure_drift_fails():
    """The single-stacked-collective structure is the method's point: a
    payload claiming anything but 1-vs-2 reductions per iteration means
    the recurrence (or the record) changed."""
    cur = _payload()
    cur["pipelined"][0]["reductions_per_iter_pipelined"] = 2
    g = check(cur, _payload())
    assert any("reductions_per_iter_pipelined" in f for f in g.failures)


def test_pipelined_r0_divergence_fails():
    """The trace head must stay the globally-reduced ||b|| (the injected-
    reduction bug this gate exists to keep fixed)."""
    cur = _payload()
    cur["pipelined"][0]["r0_reldiff"] = 0.5
    g = check(cur, _payload())
    assert any("r0_reldiff" in f for f in g.failures)


def test_guard_added_collective_fails():
    """Guards read already-reduced slots: ANY new collective in the lowered
    guarded program, against the baseline's count, is a regression of the
    zero-extra-collectives invariant."""
    cur = _payload()
    cur["guarded"][0]["collectives_guarded"] = 1
    g = check(cur, _payload())
    assert [f for f in g.failures if "collectives_guarded" in f] == [
        "guarded['m', 'pcg_tol']: collectives_guarded changed 0 -> 1 "
        "(exact-match field)"]


def test_guard_detection_loss_fails():
    cur = _payload()
    cur["guarded"][0]["detects_indefinite"] = False
    g = check(cur, _payload())
    assert any("detects_indefinite" in f for f in g.failures)


def test_overlap_model_drift_fails():
    """The comm-overlap fields are host-deterministic model outputs: any
    drift is a real interior/frontier-split behaviour change."""
    cur = _payload()
    cur["noc_plans"][0]["overlap_efficiency"] = 0.5
    cur["noc_plans"][0]["overlap_exposed_words"] = 128
    g = check(cur, _payload())
    assert any("overlap_efficiency" in f for f in g.failures)
    assert any("overlap_exposed_words" in f for f in g.failures)


def test_serving_retrace_fails():
    """ANY retrace in a serving run breaks the compile-free steady-state
    contract, whatever the baseline recorded."""
    cur = _payload()
    cur["serving"][0]["retraces"] = 1
    g = check(cur, _payload())
    assert any("retraces" in f for f in g.failures)


def test_serving_count_drift_and_latency_blowup_fail():
    cur = _payload()
    cur["serving"][0]["completed"] = 20
    cur["serving"][0]["rejected"] = 4
    g = check(cur, _payload())
    assert any("completed" in f for f in g.failures)
    assert any("rejected" in f for f in g.failures)
    cur = _payload()
    cur["serving"][0]["p99_ms"] = 90.0 * 11
    g = check(cur, _payload(), timing_ratio=10.0)
    assert any("p99_ms" in f for f in g.failures)
    # within the generous ratio: latency noise is not a regression
    cur["serving"][0]["p99_ms"] = 90.0 * 9
    assert not check(cur, _payload(), timing_ratio=10.0).failures


def test_obs_bitwise_break_fails():
    """Instrumentation that changes a solve's bits breaks the host-side-
    only contract, whatever the baseline recorded."""
    cur = _payload()
    cur["observability"][0]["bitwise_identical"] = False
    g = check(cur, _payload())
    assert any("bitwise_identical" in f for f in g.failures)


def test_obs_overhead_beyond_ratio_fails():
    """Instrumented timing is bounded against the SAME RUN's bare arm
    (like guard overhead): the always-on budget is 5%."""
    cur = _payload()
    cur["observability"][0]["overhead_ratio"] = 1.2
    g = check(cur, _payload(), obs_overhead=1.05)
    assert any("overhead_ratio" in f for f in g.failures)
    cur["observability"][0]["overhead_ratio"] = 1.02
    assert not check(cur, _payload(), obs_overhead=1.05).failures


def test_obs_missing_family_fails():
    cur = _payload()
    cur["observability"][0]["required_families_present"] = False
    g = check(cur, _payload())
    assert any("required_families_present" in f for f in g.failures)


def test_format_choice_drift_fails():
    """The autotuner's pick and its model are host-deterministic: a
    different chosen format (or moved modeled words) is a real heuristic/
    model behaviour change."""
    cur = _payload()
    cur["formats"][0]["chosen_format"] = "sell"
    cur["formats"][0]["modeled_words"] = dict(
        _payload()["formats"][0]["modeled_words"], hyb=99999)
    g = check(cur, _payload())
    assert any("chosen_format" in f for f in g.failures)
    assert any("modeled_words" in f for f in g.failures)


def test_format_stops_beating_ell_fails():
    """The portfolio's reason to exist: on the gated skewed matrix the
    autotuned format must keep beating padded ELL, modeled AND wall."""
    cur = _payload()
    cur["formats"][0]["beats_ell_modeled"] = False
    cur["formats"][0]["beats_ell_wall"] = False
    g = check(cur, _payload())
    assert any("beats_ell_modeled" in f for f in g.failures)
    assert any("beats_ell_wall" in f for f in g.failures)
    # wall gate only applies where the baseline marked it robust
    cur = _payload()
    cur["formats"][0]["wall_gated"] = False
    base = _payload()
    base["formats"][0]["wall_gated"] = False
    cur["formats"][0]["beats_ell_wall"] = False
    assert not check(cur, base).failures


def test_format_fused_divergence_fails():
    cur = _payload()
    cur["formats"][0]["fused_matches_reference"] = False
    cur["formats"][0]["iters_match"] = False
    g = check(cur, _payload())
    assert any("fused_matches_reference" in f for f in g.failures)
    assert any("iters_match" in f for f in g.failures)


def test_sptrsv_scan_scaling_loss_fails():
    """The lax.scan wavefront losing its sublinear plan-time edge over the
    unrolled baseline is the compile-scaling regression item 4c gates."""
    cur = _payload()
    cur["formats"][1]["scan_sublinear_vs_unrolled"] = False
    g = check(cur, _payload())
    assert any("scan_sublinear_vs_unrolled" in f for f in g.failures)
    cur = _payload()
    cur["formats"][1]["points"][-1]["plan_s_scan"] = 0.04 * 11
    g = check(cur, _payload(), timing_ratio=10.0)
    assert any("plan_s_scan" in f for f in g.failures)


def test_sections_subset_gates_only_named_sections():
    """--sections serving: a serving-only payload (the serve-smoke job)
    checks against the full baseline without tripping coverage failures
    for the sections it does not carry."""
    cur = {"schema": "bench_pcg/v8", "serving": _payload()["serving"]}
    g = check(cur, _payload(), sections=("serving",))
    assert not g.failures and g.checks > 5
    cur["serving"][0]["retraces"] = 2
    g = check(cur, _payload(), sections=("serving",))
    assert any("retraces" in f for f in g.failures)
    # the subset gate still notices a dropped load point
    g = check({"schema": "bench_pcg/v8", "serving": []}, _payload(),
              sections=("serving",))
    assert any("missing" in f for f in g.failures)


def test_dense_to_halo_improvement_passes_plan_check():
    """The reverse direction (dense baseline -> halo current) is an
    improvement, not a regression -- but the byte fields still compare
    exactly, so flipping requires a re-baseline (a deliberate act)."""
    base = _payload()
    base["noc_plans"][0]["plan"] = "dense"
    cur = _payload()
    g = check(cur, base)
    assert any("plan" in f and "halo-plan regression" not in f
               for f in g.failures)


def test_extra_current_entries_are_fine():
    """Current may cover MORE than baseline (new matrices ride along)."""
    cur = _payload()
    cur["tol_solves"].append(dict(cur["tol_solves"][0], matrix="m2"))
    assert not check(cur, _payload()).failures


def test_update_baseline_escape_hatch(tmp_path):
    cur_p = tmp_path / "cur.json"
    base_p = tmp_path / "base.json"
    cur = _payload()
    cur["tol_solves"][0]["iters_fused"] = cur["tol_solves"][0]["iters_reference"] = 40
    cur_p.write_text(json.dumps(cur))
    base_p.write_text(json.dumps(_payload()))
    assert gate_main(["--current", str(cur_p), "--baseline", str(base_p)]) == 1
    assert gate_main(["--current", str(cur_p), "--baseline", str(base_p),
                      "--update-baseline"]) == 0
    assert gate_main(["--current", str(cur_p), "--baseline", str(base_p)]) == 0
    assert json.loads(base_p.read_text()) == cur


def test_update_baseline_refuses_degenerate_payload(tmp_path):
    """A truncated/empty payload must never become the baseline -- it would
    make every future gate run vacuously pass."""
    cur_p = tmp_path / "cur.json"
    base_p = tmp_path / "base.json"
    base_p.write_text(json.dumps(_payload()))
    empty = _payload()
    empty["tol_solves"] = []
    cur_p.write_text(json.dumps(empty))
    assert gate_main(["--current", str(cur_p), "--baseline", str(base_p),
                      "--update-baseline"]) == 1
    assert json.loads(base_p.read_text()) == _payload()   # untouched
    wrong = _payload()
    wrong["schema"] = "bench_pcg/v1"
    cur_p.write_text(json.dumps(wrong))
    assert gate_main(["--current", str(cur_p), "--baseline", str(base_p),
                      "--update-baseline"]) == 1


# -- the committed artifacts themselves ---------------------------------------


def test_committed_bench_passes_gate():
    """The recorded BENCH_pcg.json must pass against the committed baseline
    -- exactly what the bench-smoke CI job enforces per commit."""
    assert gate_main(["--current", CURRENT, "--baseline", BASELINE]) == 0


def test_committed_baseline_is_selfconsistent():
    base = json.load(open(BASELINE))
    assert base["schema"] == "bench_pcg/v8"
    assert base["tol_solves"], "baseline must pin tolerance iteration counts"
    assert base["noc_plans"], "baseline must pin the comm-plan traffic records"
    assert base["pipelined"], "baseline must pin the pipelined-PCG record"
    assert base["guarded"], "baseline must pin the guarded-solve record"
    for e in base["guarded"]:
        assert e["status_clean"] == "converged"
        assert e["collectives_guarded"] == 0
        assert e["detects_indefinite"] is True
    for e in base["pipelined"]:
        assert e["reductions_per_iter_pipelined"] == 1
        assert e["reductions_per_iter_pcg"] == 2
        assert e["r0_reldiff"] <= 1e-8
    for e in base["noc_plans"]:
        assert 0.0 <= e["overlap_efficiency"] <= 1.0
        assert (e["overlap_hidden_words"] + e["overlap_exposed_words"]
                == e["gather_words_halo"])
    # the acceptance bar: banded patterns must cut halo plans whose modeled
    # NoC bytes/iteration are strictly below the dense all-gather model
    halo = [e for e in base["noc_plans"]
            if e["matrix"] in ("lap2d_32", "banded_1k") and e["grid"] == "8"]
    assert halo and all(e["plan"] == "halo" for e in halo)
    assert all(e["bytes_per_iter_halo"] < e["bytes_per_iter_dense"]
               for e in halo)
    for e in base["tol_solves"]:
        assert e["iters_match"] is True
        assert e["iters_fused"] == e["iters_reference"]
    assert base["serving"], "baseline must pin the serving load points"
    for e in base["serving"]:
        assert e["retraces"] == 0          # compile-free steady state
        assert e["rejected"] == 0 and e["errors"] == 0
        assert e["completed"] == e["requests"]
        assert e["p50_ms"] <= e["p99_ms"]
    assert base["observability"], "baseline must pin the obs overhead record"
    for e in base["observability"]:
        assert e["bitwise_identical"] is True
        assert e["required_families_present"] is True
        assert e["overhead_ratio"] <= 1.05   # the always-on budget
    g = check(base, base)
    assert not g.failures


@pytest.mark.slow
def test_fresh_smoke_payload_passes_gate(tmp_path):
    """Regenerate the smoke payload the way CI does and run the real gate:
    iteration counts must be reproducible on this machine."""
    out = tmp_path / "BENCH_pcg.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["REPRO_KERNEL_MODE"] = "interpret"
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--smoke", "--json", str(out)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=560,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    r2 = subprocess.run(
        [sys.executable, "-m", "benchmarks.check_regression",
         "--current", str(out), "--baseline", BASELINE],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120,
    )
    assert r2.returncode == 0, f"stdout={r2.stdout}\nstderr={r2.stderr[-2000:]}"
    # seeded regression: doctor the payload, the gate must fail
    bad = json.loads(out.read_text())
    bad["tol_solves"][0]["iters_fused"] += 1
    bad_p = tmp_path / "bad.json"
    bad_p.write_text(json.dumps(bad))
    r3 = subprocess.run(
        [sys.executable, "-m", "benchmarks.check_regression",
         "--current", str(bad_p), "--baseline", BASELINE],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120,
    )
    assert r3.returncode == 1 and "iters_fused" in r3.stdout
