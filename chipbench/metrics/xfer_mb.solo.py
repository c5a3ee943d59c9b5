"""Megabytes (1e6 B) moved between host and device per solve, from the
program's own counters: ``repro_solve_h2d_bytes_total`` plus
``repro_solve_d2h_bytes_total`` (the nbytes of every array a solve hands to
or takes from the device) over ``repro_solve_executions_total``, for every
solve of the run (warm-up included).  Like the trace's readings, it is
reported only from a run whose traced window reached a device."""


def read(run):
    tr = run.window.traced
    if tr is None or not tr.reduced:
        return None
    try:
        from repro.obs import REGISTRY
    except ImportError:
        return None
    h2d = REGISTRY.get("repro_solve_h2d_bytes_total")
    d2h = REGISTRY.get("repro_solve_d2h_bytes_total")
    execs = REGISTRY.get("repro_solve_executions_total")
    if h2d is None or d2h is None or execs is None:
        return None
    solves = sum(child.value for _, child in execs.samples())
    if solves == 0:
        return None
    return (h2d.value() + d2h.value()) / solves / 1e6
