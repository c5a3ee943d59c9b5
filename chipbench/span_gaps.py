#!/usr/bin/env python
"""Device time by layer and idle time by program span, from one profiler
trace.

Two readings, each for the result line's ``breakdown``:

``by_scope``      device self seconds per scope of the program's map
                  (``chipbench/scopes.py``), averaged over the chips.
``idle_by_span``  each idle gap of the first device, summed under the
                  innermost ``repro.*`` host event that covers the gap's
                  midpoint (the program's own spans, ``repro.solve.stage_in``
                  ...), else the innermost ``chipbench.*`` event (the
                  harness's phases), else ``(host idle)``.

The window and the device operations are read as ``trace_reduce`` reads
them.  Run as a script, it runs one cell traced, as ``run.py --trace 1``
does, and prints the same result line with the two keys added:

    python3 chipbench/span_gaps.py --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import scopes, trace_reduce  # noqa: E402

PROGRAM = "repro."
HARNESS = "chipbench."
IDLE = "(host idle)"


def by_scope(reduced: dict | None, mapping: dict | None) -> dict | None:
    """{scope: device self seconds} of a ``trace_reduce`` result."""
    if not reduced or not mapping:
        return None
    return scopes.seconds_by_scope(reduced["top_ops"], mapping)


def idle_by_span(pd, n_devices: int) -> dict | None:
    """{span name: idle seconds of the first device} over the traced window
    of a ``jax.profiler.ProfileData``; None without a window or a device
    operation in it."""
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for pl in pd.planes if pl.name == trace_reduce.HOST_PLANE
            for ln in pl.lines for e in ln.events]
    win = [(s, e) for n, s, e in host if n == trace_reduce.WINDOW_EVENT]
    if not win:
        return None
    lo, hi = win[0]
    devices = {}
    for pl in pd.planes:
        m = trace_reduce.DEVICE_PLANE.match(pl.name)
        if m is not None and int(m.group(1)) < n_devices:
            devices[int(m.group(1))] = pl
    if not devices:
        return None
    iv = [(max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
          for ln in devices[min(devices)].lines
          if ln.name == trace_reduce.OPS_LINE for e in ln.events
          if e.start_ns < hi and e.start_ns + e.duration_ns > lo]
    if not iv:
        return None
    u = trace_reduce._union(np.asarray(iv, np.float64))
    starts = np.concatenate([[lo], u[:, 1]])
    ends = np.concatenate([u[:, 0], [hi]])
    keep = ends > starts
    starts, ends = starts[keep], ends[keep]
    mids = 0.5 * (starts + ends)
    named = np.full(len(mids), IDLE, object)
    # harness phases first, then the program's spans over them: the
    # innermost covering event of the preferred kind names the gap
    for prefix in (HARNESS, PROGRAM):
        ev = [(n, s, e) for n, s, e in host if n.startswith(prefix)
              and n != trace_reduce.WINDOW_EVENT and e > s]
        if not ev:
            continue
        names = np.asarray([n for n, _, _ in ev], object)
        es = np.asarray([s for _, s, _ in ev], np.float64)
        ee = np.asarray([e for _, _, e in ev], np.float64)
        for g, mid in enumerate(mids):
            cover = np.nonzero((es <= mid) & (ee >= mid))[0]
            if cover.size:
                named[g] = names[cover[np.argmin(ee[cover] - es[cover])]]
    total: dict[str, float] = {}
    for name, s, e in zip(named, starts, ends):
        total[name] = total.get(name, 0.0) + (e - s) * 1e-9
    return dict(sorted(total.items(), key=lambda p: -p[1]))


def instrument(run_mod) -> dict:
    """Make ``run_mod`` (``chipbench/run.py`` as a module) add ``by_scope``
    and ``idle_by_span`` to the ``breakdown`` of its traced runs: its trace
    is read once, by ``trace_reduce`` and by :func:`idle_by_span`.
    Returns the dict the readings land in."""
    found = {}

    def reduce_dir(trace_dir, n_devices):
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(str(trace_reduce.find_xplane(trace_dir)))
        found["idle_by_span"] = idle_by_span(pd, n_devices)
        found["reduced"] = trace_reduce.reduce(pd, n_devices)
        return found["reduced"]

    execute = run_mod.execute

    def execute_and_name(*a, **kw):
        result = execute(*a, **kw)
        if "breakdown" in result:
            result["breakdown"]["by_scope"] = by_scope(found.get("reduced"),
                                                       scopes.scope_map())
            result["breakdown"]["idle_by_span"] = found.get("idle_by_span")
        return result

    trace_reduce.reduce_dir = reduce_dir
    run_mod.execute = execute_and_name
    return found


def main(argv=None) -> int:
    """Run one cell traced; print its result line with the two readings."""
    from chipbench import run

    args = run.parse(argv)
    instrument(run)
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
