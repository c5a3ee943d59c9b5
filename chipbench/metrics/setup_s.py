"""Seconds from process start to the first timed call: operator build,
right-hand sides, compile or cache load, warm-up (host clock)."""


def read(run):
    return run.setup_s
