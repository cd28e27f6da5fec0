"""The test-only size override: each configuration and mix cut to a size
the host CPU runs in seconds. Kept here, not in the harness."""

import jax

import run as harness

SEED = 2**31 + 12345  # more than 32 signed bits hold, as the driver's do
SECONDS = 3.0


def plan(workload):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell, cfg, mix = harness.cell_plan(bench, workload)
    cfg = dict(cfg, nodes=40, pods_per_group=10, min_member=[1, 10])
    if mix["kind"] == "steady":
        mix = dict(mix, warmup_s=2.0, gang_rate_per_s=3.0, profile_s=1.0,
                   warm_waves=[1, 2])
    else:
        mix = dict(mix, warm_bursts=1)
    return bench, cell, cfg, mix


def measure(workload, trace=0, seed=SEED):
    bench, cell, cfg, mix = plan(workload)
    devices = jax.devices()
    return harness.measure(bench, cell, cfg, mix, seed, SECONDS, trace,
                           devices[0].platform, devices)


def drive(workload, place, seed=SEED):
    """A run with ``place`` in the scheduler's place; returns the Run."""
    import time

    import drivers

    _, _, cfg, mix = plan(workload)
    return drivers.DRIVERS[mix["kind"]](
        cfg, mix, seed, SECONDS, 0, "cpu", time.perf_counter(),
        "/nonexistent", place=place)
