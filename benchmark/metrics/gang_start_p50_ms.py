"""gang_start_p50_ms: median over the gangs due in the window of due time
to the minMember-th bind seen on the cluster watch; a gang that never
starts counts to the end of the drain; host clock."""

from readers import percentile_ms


def read(run):
    return percentile_ms([g["latency"] for g in run.gangs], 50)
