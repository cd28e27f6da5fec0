"""bind_echo_inplace_pct.burst: share of the cache's watch ingests on the
bind drain that applied the echo of a placement the cache staged in place
(stage ``echo_inplace`` inside stage ``ingest``, ``cache.update_pod``):
100 × Σ ``echo_inplace_n`` / Σ ``ingest_n`` over the burst cycles'
``cache_side_effect`` spans (program_counter, %). None where no span
carries the stage: a program without the in-place path."""

from stages import _per_cycle


def read(run):
    found = _per_cycle(run, ("cache_side_effect",))
    if found is None:
        return None
    _, picked = found
    echoes = [args["echo_inplace_n"] for _, _, args in picked
              if "echo_inplace_n" in args]
    ingests = sum(args.get("ingest_n", 0) for _, _, args in picked)
    if not echoes or not ingests:
        return None
    return 100.0 * sum(echoes) / ingests
