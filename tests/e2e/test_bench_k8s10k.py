"""The four-chip burst cell ``k8s10k.burst.4chip``, rehearsed on the CPU
mesh through the benchmark's own burst driver (``benchmark/drivers.py``),
cut as ``benchmark/tests/small.py`` cuts cells. The cut is far below the
flat-sharding crossover, so the test forces the mode the size policy picks
at the cell's own size (``KBT_SPARSE_SHARD_MODE=flat``). Every correctness
check reads 0, every burst dispatches flat over the whole mesh, and each
pod lands on the node a single-device run of the same seed gives it."""

import os
import time

import pytest

from kube_batch_tpu.solver import sharding

CELL = "k8s10k.burst.4chip"
BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), "benchmark")


@pytest.fixture
def small(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    monkeypatch.syspath_prepend(os.path.join(BENCH, "tests"))
    monkeypatch.setenv("KBT_SOLVER", "jax")
    monkeypatch.setenv("KBT_SOLVER_TOPK", "4")  # the sparse path at 40 nodes
    import small

    return small


def bursts(small, seed, mode, monkeypatch):
    """The run and, per burst, the dispatch of its solve."""
    import drivers

    monkeypatch.setenv("KBT_SPARSE_SHARD_MODE", mode)
    _, _, cfg, mix = small.plan(CELL)
    dispatched = []

    def place(dep):
        dep.sched.run_once()
        dispatched.append(dict(sharding.last_dispatch))

    run = drivers.run_burst(cfg, mix, seed, small.SECONDS, 0, "cpu",
                            time.perf_counter(), "/nonexistent", place=place)
    return run, dispatched


@pytest.mark.parametrize("seed", [2**31 + 12345, 2**33 + 7])
def test_flat_burst_is_correct_and_matches_single_device(small, monkeypatch,
                                                         seed):
    import jax

    run, dispatched = bursts(small, seed, "flat", monkeypatch)
    assert run.cycles and run.attempted > 0
    assert all(v == 0 for v in run.checks.values()), run.checks
    assert dispatched and all(
        d.get("mode") == "flat" and d.get("shards") == jax.device_count()
        for d in dispatched), dispatched

    single, dispatched = bursts(small, seed, "off", monkeypatch)
    assert all(d.get("mode") == "single" for d in dispatched), dispatched
    common = min(len(run.cycles), len(single.cycles))
    for flat_cyc, single_cyc in zip(run.cycles[:common],
                                    single.cycles[:common]):
        assert flat_cyc["stored"] == single_cyc["stored"]
        assert any(node for node in flat_cyc["stored"].values())
