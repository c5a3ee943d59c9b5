"""Share of the HBM roofline the multigrid smoother reaches: the least
bytes of the V-cycles run by the solves completed in the traced window
(chipbench/work_mg.py; one V-cycle per iteration and one for the initial
residual), over the chip's HBM bandwidth (chipbench/peaks.py), divided by
the device self time under the program's ``smooth`` scope in that window
(profiler trace).  A program without the scope reads nothing."""

from chipbench import scopes, work_mg


def read(run):
    tr = run.window.traced
    bw = run.peaks.get("hbm_bytes_per_s")
    if tr is None or not tr.reduced or not bw:
        return None
    mapping = scopes.scope_map()
    if not mapping:
        return None
    t = scopes.seconds_by_scope(tr.reduced["top_ops"], mapping).get(
        "smooth", 0.0)
    vcycles = sum(a.iters + 1 for a in tr.answers if a.iters >= 0)
    if t <= 0 or vcycles == 0:
        return None
    return (100.0 * work_mg.per_vcycle(run.cell.cfg)["bytes"] * vcycles
            / bw / t)
