"""bind_ingest_cpu_ms.burst: thread CPU of the cache's own watch ingest
on the bind workers (stage ``ingest``, ``cache._on_watch_event``) per burst,
summed over the burst cycle's bind spans (program_span, ms)."""

from stages import BIND_SPANS, ms_per_cycle, stage_cpu


def read(run):
    return ms_per_cycle(run, BIND_SPANS,
                        lambda args: stage_cpu(args, "ingest"))
