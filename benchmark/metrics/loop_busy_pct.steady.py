"""loop_busy_pct.steady: 100 x (1 - time under the scheduler loop's wait
spans (``loop_wait``: think-time drain, sleep and error backoff;
``micro_park``: waiting for an arrival; ``micro_coalesce``) / the window's
seconds) (program_span, %)."""

from stages import LOOP_WAITS, window_spans


def read(run):
    spans = window_spans(run)
    waits = [t1 - t0 for name, t0, t1, _, _ in spans or ()
             if name in LOOP_WAITS]
    if not waits:
        return None
    lo, hi = run.window
    return 100.0 * (1.0 - sum(waits) / (hi - lo))
