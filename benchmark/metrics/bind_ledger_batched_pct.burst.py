"""bind_ledger_batched_pct.burst: share of the bind drain's binds whose
applied stamp the drain folded into the placement-latency ledger once per
chunk (each bind's clock read is stage ``ledger_stamp``,
``cache._bind_side_effect``) rather than one ledger call per bind:
100 × Σ ``ledger_stamp_n`` / Σ ``bind_call_n`` over the burst cycles'
``cache_side_effect`` spans (program_counter, %). None where no span
carries the stage: a program without the fold."""

from stages import _per_cycle


def read(run):
    found = _per_cycle(run, ("cache_side_effect",))
    if found is None:
        return None
    _, picked = found
    stamps = [args["ledger_stamp_n"] for _, _, args in picked
              if "ledger_stamp_n" in args]
    binds = sum(args.get("bind_call_n", 0) for _, _, args in picked)
    if not stamps or not binds:
        return None
    return 100.0 * sum(stamps) / binds
