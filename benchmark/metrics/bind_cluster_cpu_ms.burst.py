"""bind_cluster_cpu_ms.burst: thread CPU of the cluster's side of each bind,
per burst: stage ``bind_call`` (the cluster's write and its synchronous
watch fan-out) less stage ``ingest`` (the cache's own handler, inside it),
plus stage ``event`` (``record_event``), summed over the burst cycle's
``cache_side_effect`` spans (program_span, ms)."""

from stages import ms_per_cycle, stage_cpu


def cluster_cpu(args):
    if "bind_call_n" not in args:
        return None
    return (stage_cpu(args, "bind_call") - stage_cpu(args, "ingest")
            + stage_cpu(args, "event"))


def read(run):
    return ms_per_cycle(run, ("cache_side_effect",), cluster_cpu)
