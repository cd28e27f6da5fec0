"""device_idle_pct: 100 x (1 - busy / window) from the profiler trace,
busy being the union of XLA op intervals (averaged over the chips used);
the window is the profiled burst cycle, or the profiled seconds of steady
traffic."""

from readers import idle_pct


def read(run):
    return idle_pct(run)
