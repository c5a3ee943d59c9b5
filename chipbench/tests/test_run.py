"""run.py without a chip: it refuses and prints no result; the rest of a
run, driven at a tiny size, is correct and reports the cell's metrics."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.tests import tiny

ROOT = harness.ROOT


def _run_cli(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_nonzero_without_result():
    p = _run_cli(["--workload", "poisson2d_1024.solo", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_workload_exits_nonzero_without_result():
    p = _run_cli(["--workload", "nope.solo", "--seed", "1", "--seconds", "1"])
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_checkout_without_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(["--workload", "poisson2d_1024.solo", "--seed", "1",
                  "--seconds", "1"], cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "program is missing" in p.stderr


@pytest.mark.parametrize("workload", ["poisson2d_1024.solo",
                                      "poisson3d_128_mf.solo"])
def test_closed_loop_run_is_correct(workload):
    res = tiny.execute(tiny.cell(workload), seconds=1.0)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 1
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert res["notes"]["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["max_rel_residual"]["value"] <= \
        res["checks"]["max_rel_residual"]["limit"]


def test_traced_run_reports_per_layer_counters():
    # off the chip the profiler trace has no device plane: only the
    # metric read from the program's counter is there, none from the trace
    res = tiny.execute(tiny.cell("poisson3d_128_mf.solo",
                                 trace={"lead_s": 0.1, "min_s": 0.3,
                                        "min_units": 2}),
                       seconds=1.0, trace=True)
    assert res["correct"]
    assert set(res["metrics"]) == {"iters.solo"}
    assert res["metrics"]["iters.solo"]["value"] > 0
    assert not harness.TRACE_DIR.exists()


def test_host_memory_keeps_only_the_sample(monkeypatch):
    monkeypatch.setattr(harness, "CHECK_SAMPLE", 3)
    res = tiny.execute(tiny.cell("poisson2d_1024.solo"), seconds=1.0)
    assert res["correct"] and res["attempted"] > 3
    assert res["notes"]["checked"] == 3


MESH_RUN = """
import json, sys, time
import jax
from chipbench.tests import tiny
res = tiny.execute(tiny.cell("poisson2d_2048.mesh2x2"), seconds=1.0)
print(json.dumps(res))
"""


def test_mesh_run_is_correct_on_four_devices():
    p = tiny.run_script(MESH_RUN)
    assert p.returncode == 0, p.stderr[-3000:]
    res = tiny.last_json(p.stdout)
    assert res["correct"] and res["device"]["count"] == 4
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
