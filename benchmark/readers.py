"""Shared arithmetic of the metric readers (``metrics/<metric>.py``).

A reader is ``read(run) -> number or None``; None means it found nothing
to read, and the harness then leaves the metric out of the result line.
"""

import numpy as np


def span_ms_per_cycle(run, *names):
    """Mean, over the cycles that ran in the traced window, of the summed
    duration of the named spans in each cycle (ms). A cycle is one
    ``cycle`` span; spans are matched to it by the cycle id they carry."""
    cycles = {c for name, _, _, c in run.spans if name == "cycle"}
    if not cycles:
        return None
    total = sum(t1 - t0 for name, t0, t1, c in run.spans
                if name in names and c in cycles)
    return total / len(cycles) * 1e3


def percentile_ms(values_s, q):
    if not values_s:
        return None
    return float(np.percentile(np.asarray(values_s), q)) * 1e3


def idle_pct(run):
    dev = run.device
    if dev is None or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
