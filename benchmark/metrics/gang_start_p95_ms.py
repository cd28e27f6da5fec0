"""gang_start_p95_ms: 95th percentile of gang_start_p50_ms's latencies."""

from readers import percentile_ms


def read(run):
    return percentile_ms([g["latency"] for g in run.gangs], 95)
