"""generator_lag_p99_ms.steady: 99th percentile of how late the open-loop
generator created gangs against their due times, in the window."""

from readers import percentile_ms


def read(run):
    return percentile_ms(run.gen_lag_s, 99)
