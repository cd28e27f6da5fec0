"""solve_wait_ms.burst: the solve_dispatch and solve_block spans per burst
cycle (host time launching and waiting on the solve), mean."""

from readers import span_ms_per_cycle


def read(run):
    return span_ms_per_cycle(run, "solve_dispatch", "solve_block")
