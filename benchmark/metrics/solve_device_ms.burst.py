"""solve_device_ms.burst: device time of the solve's XLA modules (those
whose name holds "solve") in the profiled burst, chip 0, per cycle."""

import re

SOLVE = re.compile("solve", re.IGNORECASE)


def read(run):
    dev = run.device
    if dev is None:
        return None
    ns = sum(v for k, v in dev["modules_ns"].items() if SOLVE.search(k))
    if ns <= 0:
        return None
    return ns / dev["cycles_profiled"] / 1e6
