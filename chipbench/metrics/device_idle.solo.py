"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals) / window (profiler trace)."""


def read(run):
    tr = run.window.traced
    if tr is None or not tr.reduced or tr.reduced["window_s"] <= 0:
        return None
    red = tr.reduced
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
