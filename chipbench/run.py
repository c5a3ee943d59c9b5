#!/usr/bin/env python
"""Run one benchmark cell once and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell is an entry of ``workloads`` in
``BENCHMARK.json``; its configuration, traffic mix, loop and metrics are
found by name under ``chipbench/`` (see ``harness.py``).  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics from a profiled sub-window of the same loop.

The run exits 2 and prints no result when the cell is unknown, when the
program (``src/repro``) is not in the checkout, or when JAX finds no TPU
or fewer chips than the cell asks for.  Otherwise the last stdout line is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (with ``--trace 1`` also ``breakdown``), and last ``checks``,
each number compared with its limit; the same checks end standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from here (repro.obs.clock)

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(cell, seed: int, seconds: float, trace: bool, devices,
            t_start: float) -> dict:
    """Set up, measure one window, check, and return the result object."""
    import jax
    import numpy as np

    from chipbench import harness, peaks, trace_reduce, work
    from repro.launch import compile_cache
    from repro.obs import clock, set_jax_bridge

    t_in = clock.now()
    compile_cache.enable()
    # every program the window runs is found in the cache on a second run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = harness.CompileCounter()
    compiles.install()

    state = cell.loop.setup(cell)
    t_built = clock.now()
    pool = cell.rhs.make(cell.cfg, cell.operator,
                         np.random.default_rng([seed, 0]),
                         int(cell.traffic["pool"]))
    t_pool = clock.now()
    inputs = cell.loop.inputs(cell, pool, np.random.default_rng([seed, 1]),
                              seconds)
    prof = harness.Profiler(cell.traffic.get("trace"), trace)
    sample = harness.Sample(harness.CHECK_SAMPLE,
                            np.random.default_rng([seed, 2]))
    set_jax_bridge(trace)
    compiles.armed = True
    window = cell.loop.window(cell, state, pool, inputs, seconds, prof,
                              sample)
    compiles.armed = False
    window.compiles = compiles.count
    window.traced = prof.traced(window.answers)
    mem = [d.memory_stats() or {} for d in devices]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    del state
    gc.collect()
    if window.traced is not None:
        window.traced.reduced = trace_reduce.reduce_dir(
            harness.TRACE_DIR, n_devices=len(devices))
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)

    dev = devices[0]
    run = harness.Run(
        cell=cell, seed=seed, seconds=seconds,
        setup_s=window.t0 - t_start, window=window, pool=pool,
        device={"platform": dev.platform, "kind": dev.device_kind,
                "count": len(jax.devices()), "memory_peak_bytes": peak},
        work=work.per_iteration(cell.cfg),
        peaks=peaks.for_kind(dev.device_kind, strict=dev.platform == "tpu"))
    verdict = harness.check(run)
    metrics = harness.read_metrics(
        run, cell.per_layer if trace else cell.end_to_end)
    result = {"correct": verdict["correct"],
              "attempted": len(window.answers),
              "failed": verdict["failed"],
              "metrics": metrics,
              "device": dict(run.device)}
    notes = {"setup_s": run.setup_s,
             "setup_phases_s": {"start": t_in - t_start,
                                "engine_plans_warmup": t_built - t_in,
                                "rhs_pool": t_pool - t_built,
                                "inputs": window.t0 - t_pool},
             "window_s": window.seconds,
             "compiles_in_window": window.compiles,
             "checked": verdict["checked"], **window.notes}
    if window.traced is not None and window.traced.reduced:
        red = window.traced.reduced
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["top_ops"][:10],
                               "idle_gaps": red["idle_gaps"][:10]}
    result["notes"] = notes
    result["checks"] = verdict["checks"]
    return result


def main(argv=None) -> int:
    args = parse(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from chipbench import harness

    try:
        cell = harness.resolve(args.workload)
        if not (ROOT / "src" / "repro").is_dir():
            raise harness.Refused(
                f"the program is missing ({ROOT / 'src' / 'repro'})")
        if str(ROOT / "src") not in sys.path:
            sys.path.insert(0, str(ROOT / "src"))
        import jax

        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise harness.Refused(
                f"JAX found no TPU ({devices[0].platform!r} devices)")
        if len(devices) < cell.chips:
            raise harness.Refused(
                f"the cell needs {cell.chips} chips, JAX found {len(devices)}")
    except harness.Refused as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 2
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     devices[:cell.chips], T_START)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
