"""micro_coalesce_ms.steady: mean duration of the scheduler loop's
``micro_coalesce`` spans (the coalescing wait before a micro cycle) in the
window (program_span, ms)."""

from stages import window_spans


def read(run):
    spans = window_spans(run)
    waits = [t1 - t0 for name, t0, t1, _, _ in spans or ()
             if name == "micro_coalesce"]
    return sum(waits) / len(waits) * 1e3 if waits else None
