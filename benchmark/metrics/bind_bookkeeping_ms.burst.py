"""bind_bookkeeping_ms.burst: duration of the burst cycle's
``cache_bookkeeping`` spans (bind_batch's mirror update on the side-effect
pool, which snapshot barriers on), summed, mean over bursts (program_span,
ms)."""

from stages import span_ms_per_cycle


def read(run):
    return span_ms_per_cycle(run, "cache_bookkeeping")
