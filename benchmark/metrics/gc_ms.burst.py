"""gc_ms.burst: time under the program's ``gc`` spans (one per CPython
collection, any thread) inside each burst's run_once start to binds
drained, mean over bursts (program_span, ms)."""

from stages import span_ms_per_burst


def read(run):
    return span_ms_per_burst(run, "gc")
