"""compile_ms.steady: time under the program's ``compile`` (backend
compile) and ``compile_cache_load`` (persistent-cache load) spans in the
window, from jax.monitoring (program_span, ms)."""

from stages import span_ms


def read(run):
    return span_ms(run, "compile", "compile_cache_load")
