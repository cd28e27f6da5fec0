"""solve_collective_ms.burst4chip: device time of the collective ops
(``trace_reduce.COLLECTIVE``: all-gather, all-reduce, ...) on chip 0 in the
profiled burst, per cycle (device_trace, ms); None where there are none."""


def read(run):
    dev = run.device
    if dev is None or dev["collective_ns"] <= 0:
        return None
    return dev["collective_ns"] / dev["cycles_profiled"] / 1e6
