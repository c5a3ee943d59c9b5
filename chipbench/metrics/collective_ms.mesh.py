"""Device milliseconds per iteration of collective operations (all-reduce,
collective-permute and kin), averaged over the chips, in the traced
window (profiler trace)."""


def read(run):
    tr = run.window.traced
    if tr is None or not tr.reduced:
        return None
    iters = sum(a.iters for a in tr.answers if a.iters > 0)
    coll = tr.reduced["collective_s"]
    if iters == 0 or coll <= 0:
        return None
    return 1e3 * coll / iters
