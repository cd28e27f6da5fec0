"""burst_cycle_ms: the sum over the window's bursts of the time from
Scheduler.run_once's start to that cycle's binds applied
(cache.wait_for_side_effects), over the number of bursts; host clock."""


def read(run):
    if not run.cycles:
        return None
    return sum(c["t2"] - c["t0"] for c in run.cycles) / len(run.cycles) * 1e3
