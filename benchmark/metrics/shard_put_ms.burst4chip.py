"""shard_put_ms.burst4chip: the ``shard_put`` span (the sharded sparse
solve's task padding, its inputs' placement on the mesh and the jitted
step's lookup) per burst cycle, mean (program_span, ms). None unless every
such span of the window says ``shard_mode`` flat over 4 shards, so the
reading also witnesses that the cell ran the flat four-chip path."""

from stages import span_ms_per_cycle, window_spans


def read(run):
    spans = window_spans(run)
    if not spans:
        return None
    args = [a for name, _, _, _, a in spans if name == "shard_put"]
    if not args or any(a.get("shard_mode") != "flat" or a.get("shards") != 4
                       for a in args):
        return None
    return span_ms_per_cycle(run, "shard_put")
