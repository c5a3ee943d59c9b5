"""Mean iterations to the rtol over the solves of the window, from the
program's own count (SolvePlan.last_iters)."""


def read(run):
    its = [a.iters for a in run.window.answers if a.iters >= 0]
    return sum(its) / len(its) if its else None
