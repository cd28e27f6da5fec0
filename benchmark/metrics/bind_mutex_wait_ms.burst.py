"""bind_mutex_wait_ms.burst: wall time the bind path waited for
``cache.mutex`` (stage ``mutex_wait``: watch ingest, bookkeeping staging and
prewarm holds) per burst, summed over the burst cycle's bind spans and the
workers (program_span, ms)."""

from stages import BIND_SPANS, ms_per_cycle


def read(run):
    return ms_per_cycle(run, BIND_SPANS, lambda args: args.get("mutex_wait_s"))
