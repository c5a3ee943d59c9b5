"""Cells of BENCHMARK.json cut to a size a CPU test run holds, and a run of
one without the look for a chip."""

from __future__ import annotations

import copy
import dataclasses
import importlib.util
import os
import subprocess
import sys
import time

from chipbench import harness

ROOT = harness.ROOT
TINY_GRID = {2: [32, 32], 3: [12, 12, 12]}


def cell(workload: str, **traffic):
    c = harness.resolve(workload)
    cfg = copy.deepcopy(c.cfg)
    cfg["operator"]["grid"] = TINY_GRID[len(cfg["operator"]["grid"])]
    return dataclasses.replace(c, cfg=cfg, traffic=dict(c.traffic, **traffic))


def run_module():
    spec = importlib.util.spec_from_file_location(
        "chipbench_run_main", ROOT / "chipbench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def execute(c, seconds: float = 1.0, trace: bool = False, seed: int = 2**33 + 1):
    import jax

    return run_module().execute(c, seed, seconds, trace,
                                jax.devices()[:c.chips], time.perf_counter())


def subprocess_env(devices: int = 4) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT), str(ROOT / "src"), env.get("PYTHONPATH", "")])
    return env


def run_script(code: str, timeout: float = 300) -> subprocess.CompletedProcess:
    """Run ``code`` in a fresh interpreter that sees four CPU devices."""
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=subprocess_env(), capture_output=True,
                          text=True, timeout=timeout)


def last_json(text: str) -> dict:
    import json

    return json.loads(text.strip().splitlines()[-1])

