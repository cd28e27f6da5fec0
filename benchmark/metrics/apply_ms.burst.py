"""apply_ms.burst: allocate_tpu last_stats["apply_ms"] per burst cycle,
mean (grouped apply, host clock)."""


def read(run):
    vals = [sum(s.get("apply_ms", 0.0) for s in c["stats"])
            for c in run.cycles]
    return sum(vals) / len(vals) if vals else None
