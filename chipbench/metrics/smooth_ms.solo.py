"""Device milliseconds per iteration under the program's ``smooth`` scope
(repro.obs.scopes: the multigrid's Gauss-Seidel colour steps on every
level), averaged over the chips, in the traced window: the self time of
the profiler trace's operations whose HLO instruction the program's scope
map names ``smooth``, over the iterations of the solves completed inside
the window.  A program without the scope reads nothing."""

from chipbench import scopes


def read(run):
    return scopes.ms_per_iter(run, "smooth")
