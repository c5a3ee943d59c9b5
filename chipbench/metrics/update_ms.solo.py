"""Device milliseconds per iteration under the program's ``update`` scope
(repro.obs.scopes), averaged over the chips, in the traced window: the
self time of the profiler trace's operations whose HLO instruction the
program's scope map names ``update``, over the iterations of the solves
completed inside the window."""

from chipbench import scopes


def read(run):
    return scopes.ms_per_iter(run, "update")
