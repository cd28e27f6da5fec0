"""bind_drain_ms.burst: from run_once returning to the cycle's bind side
effects drained, per burst, mean; host clock."""


def read(run):
    if not run.cycles:
        return None
    return sum(c["t2"] - c["t1"] for c in run.cycles) / len(run.cycles) * 1e3
