"""bind_cpu_ms.burst: thread CPU of the bind path per burst: ``cpu_s`` of the
burst cycle's ``cache_side_effect`` and ``cache_bookkeeping`` spans, summed,
mean over the window's bursts (program_span, ms)."""

from stages import BIND_SPANS, ms_per_cycle


def read(run):
    return ms_per_cycle(run, BIND_SPANS, lambda args: args.get("cpu_s"))
