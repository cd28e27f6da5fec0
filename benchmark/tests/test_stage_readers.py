"""The readers of the program's stage counters, GC, compile and loop-wait
spans (``stages.py`` and the metrics that use it): on hand-made spans in
the tracer's ring, on a ring without stage counters (the program before
them), and in the CPU rehearsal of each cell at trace 1."""

import collections

import pytest

import small
from drivers import Run, _spans_in

NEW = {
    "k8s5k.burst": {
        "bind_cpu_ms.burst", "bind_ingest_cpu_ms.burst",
        "bind_ledgers_cpu_ms.burst", "bind_cluster_cpu_ms.burst",
        "bind_mutex_wait_ms.burst", "bind_bookkeeping_ms.burst",
        "gc_ms.burst"},
    "k8s5k.steady": {
        "loop_busy_pct.steady", "micro_coalesce_ms.steady",
        "snapshot_wait_ms.steady", "compile_ms.steady", "gc_ms.steady"},
}


class _Ring:
    """What the readers use of the program's tracer."""

    def __init__(self, spans, dropped=0):
        self._events = collections.deque(
            (name, t0, t1, 0, 0, 0, cycle, args, None)
            for name, t0, t1, cycle, args in spans)
        self.dropped = dropped


@pytest.fixture
def ring(monkeypatch):
    from kube_batch_tpu.obs import tracer

    def install(spans, dropped=0):
        monkeypatch.setattr(tracer, "TRACER", _Ring(spans, dropped))

    return install


def _read(name, run):
    return small.harness.read_metric(name, run)


def _window(run, lo, hi):
    """The window and the harness's own snapshot of its spans."""
    run.window = (lo, hi)
    run.spans = _spans_in(run.window)
    return run


def _burst_run():
    run = Run("burst", "cpu", 60, 1)
    run.cycles = [{"t0": 1.0, "t2": 11.0}, {"t0": 20.0, "t2": 30.0}]
    return _window(run, 0.0, 60.0)


# Stage CPU is read on one entry in eight: 0.025 s over 100 of 800
# entries scales to 0.2 s.
BIND = {"cpu_s": 0.5, "bind_call_n": 800, "bind_call_cpu_n": 100,
        "bind_call_cpu_s": 0.04, "ingest_n": 800, "ingest_cpu_n": 100,
        "ingest_cpu_s": 0.025, "event_n": 800, "event_cpu_n": 100,
        "event_cpu_s": 0.005, "ledgers_n": 8, "ledgers_cpu_n": 1,
        "ledgers_cpu_s": 0.0125, "mutex_wait_s": 0.4}
BURST_SPANS = [
    ("cycle", 1.0, 3.0, 1, None),
    ("cycle", 20.0, 22.0, 2, None),
    ("cache_side_effect", 3.0, 9.0, 1, BIND),
    ("cache_side_effect", 22.0, 29.0, 2, BIND),
    ("cache_bookkeeping", 3.0, 3.25, 1, {
        "cpu_s": 0.2, "ledgers_n": 2, "ledgers_cpu_n": 1,
        "ledgers_cpu_s": 0.05, "mutex_wait_s": 0.1}),
    ("gc", 5.0, 5.5, None, {"generation": 2}),
    ("gc", 29.5, 30.5, None, {"generation": 0}),   # half inside burst 2
    ("gc", 40.0, 41.0, None, {"generation": 2}),   # between bursts
    ("cache_side_effect", 59.0, 61.0, 3, BIND),    # straddles the end
]


def test_burst_readers_sum_per_burst(ring):
    ring(BURST_SPANS)
    run = _burst_run()
    got = {m: _read(m, run) for m in NEW["k8s5k.burst"]}
    assert got == pytest.approx({
        "bind_cpu_ms.burst": (0.5 + 0.5 + 0.2) / 2 * 1e3,
        "bind_ingest_cpu_ms.burst": 0.2 * 1e3,
        "bind_ledgers_cpu_ms.burst": (0.1 + 0.1 + 0.1) / 2 * 1e3,
        "bind_cluster_cpu_ms.burst": (0.32 - 0.2 + 0.04) * 1e3,
        "bind_mutex_wait_ms.burst": (0.4 + 0.4 + 0.1) / 2 * 1e3,
        "bind_bookkeeping_ms.burst": 0.25 / 2 * 1e3,
        "gc_ms.burst": (0.5 + 0.5) / 2 * 1e3,
    })
    parts = sum(got[m] for m in ("bind_ingest_cpu_ms.burst",
                                 "bind_ledgers_cpu_ms.burst",
                                 "bind_cluster_cpu_ms.burst"))
    assert parts <= got["bind_cpu_ms.burst"]


def test_steady_readers(ring):
    ring([
        ("cycle", 0.5, 0.6, 1, None),
        ("cycle", 2.5, 2.6, 2, None),
        ("snapshot", 0.5, 0.55, 1, {"bookkeeping_wait_s": 0.02,
                                    "mutex_wait_s": 0.01}),
        ("snapshot", 2.5, 2.52, 2, {"bookkeeping_wait_s": 0.0,
                                    "mutex_wait_s": 0.01}),
        ("cache_side_effect", 0.7, 0.8, 1, {"cpu_s": 0.05}),
        ("loop_wait", 1.0, 3.0, 1, {"phase": "sleep"}),
        ("micro_park", 3.0, 6.0, 2, None),
        ("micro_coalesce", 6.0, 6.02, 2, {"window_s": 0.02}),
        ("micro_coalesce", 7.0, 7.04, 2, {"window_s": 0.04}),
        ("compile", 8.0, 9.5, 2, {"fun": "jit_solve"}),
        ("compile_cache_load", 9.5, 10.0, 2, None),
        ("gc", 4.0, 4.1, None, {"generation": 1}),
        ("micro_park", 9.9, 10.2, 2, None),            # straddles the end
    ])
    run = _window(Run("steady", "cpu", 10, 1), 0.0, 10.0)
    got = {m: _read(m, run) for m in NEW["k8s5k.steady"]}
    assert got == pytest.approx({
        "loop_busy_pct.steady": 100.0 * (1 - (2.0 + 3.0 + 0.06) / 10.0),
        "micro_coalesce_ms.steady": 30.0,
        "snapshot_wait_ms.steady": (0.03 + 0.01) / 2 * 1e3,
        "compile_ms.steady": 2.0 * 1e3,
        "gc_ms.steady": 0.1 * 1e3,
    })


def test_readers_find_nothing_without_stage_counters(ring):
    """The program before stage counters: spans carry no ``cpu_s``, and
    there are no GC, compile or loop-wait spans to read."""
    ring([(n, t0, t1, c, None) for n, t0, t1, c, _ in BURST_SPANS
          if n != "gc"])
    run = _burst_run()
    assert all(_read(m, run) is None for m in NEW["k8s5k.burst"])
    run = _window(Run("steady", "cpu", 10, 1), 0.0, 60.0)
    assert all(_read(m, run) is None for m in NEW["k8s5k.steady"])


def test_readers_refuse_a_ring_that_dropped_spans(ring):
    ring(BURST_SPANS, dropped=1)
    assert all(_read(m, _burst_run()) is None for m in NEW["k8s5k.burst"])


def test_readers_take_the_spans_of_the_window_snapshot(ring):
    """The window is what the harness took at its end: a span the ring
    gains after it (closing the deployment, judging) is not read."""
    ring(BURST_SPANS)
    run = _burst_run()
    before = _read("gc_ms.burst", run)
    ring(BURST_SPANS + [("gc", 6.0, 7.0, None, {"generation": 2})])
    assert _read("gc_ms.burst", run) == before
    del run._window_spans
    assert _read("gc_ms.burst", run) == before


def test_cluster_cpu_is_measured_not_a_remainder(ring):
    """``bind_cluster_cpu_ms.burst`` reads its own stages, so the parts can
    exceed ``bind_cpu_ms.burst`` when the stage counters are wrong."""
    heavy = dict(BIND, bind_call_cpu_s=0.1)
    ring([(n, t0, t1, c, heavy if a is BIND else a)
          for n, t0, t1, c, a in BURST_SPANS])
    run = _burst_run()
    parts = sum(_read(m, run) for m in (
        "bind_ingest_cpu_ms.burst", "bind_ledgers_cpu_ms.burst",
        "bind_cluster_cpu_ms.burst"))
    assert parts > _read("bind_cpu_ms.burst", run)


@pytest.mark.parametrize("workload", sorted(NEW))
def test_rehearsal_reports_the_stage_metrics(workload):
    result, _ = small.measure(workload, 1)
    assert result["correct"] is True, result["checks"]
    metrics = result["metrics"]
    assert NEW[workload] <= set(metrics), set(metrics)
    assert all(metrics[m]["value"] >= 0 for m in NEW[workload])
    if workload == "k8s5k.burst":
        parts = sum(metrics[m]["value"] for m in (
            "bind_ingest_cpu_ms.burst", "bind_ledgers_cpu_ms.burst",
            "bind_cluster_cpu_ms.burst"))
        assert parts <= metrics["bind_cpu_ms.burst"]["value"]
