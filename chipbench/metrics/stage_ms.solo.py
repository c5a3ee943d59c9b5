"""Host staging milliseconds per solve, from the program's own counter
``repro_solve_stage_seconds``: its sum over both phases ('in': the initial
guess, padding and host-to-device transfer of b and x0; 'out': the
outputs copied back and x un-padded) over its count of solves, for every
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
    fam = REGISTRY.get("repro_solve_stage_seconds")
    if fam is None:
        return None
    phases = {key: child for key, child in fam.samples()}
    stage_in, stage_out = phases.get(("in",)), phases.get(("out",))
    if stage_in is None or stage_out is None or stage_in.count == 0:
        return None
    return 1e3 * (stage_in.sum + stage_out.sum) / stage_in.count
