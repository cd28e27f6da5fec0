"""bind_ledgers_cpu_ms.burst: thread CPU of the bind path's ledgers
(stage ``ledgers``: bind-intent journal, placement-latency ledger, quality
ledger) per burst, summed over the burst cycle's bind spans (program_span,
ms)."""

from stages import BIND_SPANS, ms_per_cycle, stage_cpu


def read(run):
    return ms_per_cycle(run, BIND_SPANS,
                        lambda args: stage_cpu(args, "ledgers"))
