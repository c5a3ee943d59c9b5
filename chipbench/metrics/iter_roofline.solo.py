"""Share of the HBM roofline reached per iteration: the least bytes of the
iterations that completed in the traced window (chipbench/work.py) over
the chip's HBM bandwidth (chipbench/peaks.py), divided by the device's busy
time in that window (profiler trace)."""


def read(run):
    tr = run.window.traced
    bw = run.peaks.get("hbm_bytes_per_s")
    if tr is None or not tr.reduced or not bw:
        return None
    iters = sum(a.iters for a in tr.answers if a.iters > 0)
    busy = tr.reduced["busy_s"]
    if iters == 0 or busy <= 0:
        return None
    return 100.0 * run.work["bytes"] * iters / bw / busy
