"""gc_ms.steady: time under the program's ``gc`` spans (one per CPython
collection, any thread) in the window (program_span, ms)."""

from stages import span_ms


def read(run):
    return span_ms(run, "gc")
