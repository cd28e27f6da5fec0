"""The readers of the four-chip burst cell's sharded solve: the program's
``shard_put`` and ``shard_commit`` spans and the device trace's collective
time, on hand-made spans in the tracer's ring, and which metrics the
harness reports in that cell."""

import pytest

import small
from drivers import Run
from test_stage_readers import _Ring, _window

CELL = "k8s10k.burst.4chip"
SHARD = {"shard_put_ms.burst4chip", "solve_collective_ms.burst4chip",
         "commit_exchange_kb.burst4chip"}
PER_ROUND = 677_376  # (100,352 one-byte codes + 3,136 accept words) x 6
FLAT = {"shard_mode": "flat", "shards": 4}


def spans(put=FLAT, commit=True):
    out = [
        ("cycle", 1.0, 3.0, 1, None),
        ("cycle", 20.0, 22.0, 2, None),
        ("shard_put", 1.5, 1.51, 1, dict(put)),
        ("shard_put", 20.5, 20.53, 2, dict(FLAT)),
        ("cache_side_effect", 3.0, 9.0, 1, {"cpu_s": 0.5}),
    ]
    if commit:
        out += [
            ("shard_commit", 1.6, 2.0, 1,
             {"commit_bytes_per_round": PER_ROUND, "reconcile_rounds": 20}),
            ("shard_commit", 20.6, 21.0, 2,
             {"commit_bytes_per_round": PER_ROUND, "reconcile_rounds": 30}),
        ]
    else:
        out += [("shard_commit", 1.6, 2.0, 1, None)]
    return out


@pytest.fixture
def ring(monkeypatch):
    from kube_batch_tpu.obs import tracer

    def install(recorded):
        monkeypatch.setattr(tracer, "TRACER", _Ring(recorded))

    return install


def burst_run(collective_ns=3e7):
    run = Run("burst", "tpu", 60, 1)
    run.cycles = [{"t0": 1.0, "t2": 11.0}, {"t0": 20.0, "t2": 30.0}]
    run.device = {"collective_ns": collective_ns, "cycles_profiled": 1}
    return _window(run, 0.0, 60.0)


def read(name, run):
    return small.harness.read_metric(name, run)


def test_shard_readers_read_the_cell(ring):
    ring(spans())
    run = burst_run()
    got = {m: read(m, run) for m in SHARD}
    assert got == pytest.approx({
        "shard_put_ms.burst4chip": (0.01 + 0.03) / 2 * 1e3,
        "solve_collective_ms.burst4chip": 30.0,
        "commit_exchange_kb.burst4chip": PER_ROUND * (20 + 30) / 2 / 1024,
    })


@pytest.mark.parametrize("put", [
    {"shard_mode": "single", "shards": 4},
    {"shard_mode": "two-level", "shards": 4},
    {"shard_mode": "flat", "shards": 8},
])
def test_shard_put_witnesses_the_flat_four_chip_path(ring, put):
    ring(spans(put=put))
    assert read("shard_put_ms.burst4chip", burst_run()) is None


def test_shard_put_reads_nothing_without_the_span(ring):
    """The program before the span (the parent of this cell's PR)."""
    ring([s for s in spans() if s[0] not in ("shard_put", "shard_commit")])
    run = burst_run()
    assert read("shard_put_ms.burst4chip", run) is None
    assert read("commit_exchange_kb.burst4chip", run) is None


@pytest.mark.parametrize("device", [0.0, None])
def test_collective_reads_nothing_without_collectives(device):
    run = Run("burst", "tpu", 60, 1)
    if device is not None:
        run.device = {"collective_ns": device, "cycles_profiled": 1}
    assert read("solve_collective_ms.burst4chip", run) is None


def test_commit_exchange_needs_both_counters(ring):
    ring(spans(commit=False))
    assert read("commit_exchange_kb.burst4chip", burst_run()) is None
    half = spans()
    half[-1] = half[-1][:4] + ({"commit_bytes_per_round": PER_ROUND},)
    ring(half[:-2] + [half[-1]])
    assert read("commit_exchange_kb.burst4chip", burst_run()) is None


def test_the_cell_reports_its_metrics_and_no_steady_one():
    bench = small.harness.load_json(small.harness.ROOT, "BENCHMARK.json")
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    reported = {m["name"] for kind in ("end_to_end", "per_layer")
                for m in bench[kind]
                if small.harness.applies(m, CELL, end_to_end)}
    assert reported == {
        "setup_s", "burst_cycle_ms", "tensorize_ms.burst",
        "solve_wait_ms.burst", "solve_device_ms.burst",
        "bind_drain_ms.burst", "device_idle_pct.burst",
        "window_compiles.burst"} | SHARD
    steady = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind]
              if small.harness.applies(m, "k8s5k.steady", end_to_end)}
    assert not (reported - {"setup_s"}) & steady


def test_solve_device_finds_the_sharded_step_module():
    """The sharded step's XLA module is named as a solve, so the cell's
    ``solve_device_ms.burst`` reads it (``jit_run`` it would not)."""
    run = Run("burst", "tpu", 60, 1)
    run.device = {"modules_ns": {"jit_solve_sparse_sharded": 5e8,
                                 "jit_run": 1e8}, "cycles_profiled": 1}
    assert read("solve_device_ms.burst", run) == pytest.approx(500.0)
