"""The reader of ``bind_echo_inplace_pct.burst``: the share of the bind
drain's watch ingests that took the cache's in-place echo path, on
hand-made spans in the tracer's ring."""

import pytest

import small
from test_stage_readers import _burst_run, _Ring

METRIC = "bind_echo_inplace_pct.burst"


def spans(first, second):
    """Two burst cycles, each with its drain span's args."""
    return [
        ("cycle", 1.0, 3.0, 1, None),
        ("cycle", 20.0, 22.0, 2, None),
        ("cache_side_effect", 3.0, 9.0, 1, first),
        ("cache_side_effect", 22.0, 29.0, 2, second),
        ("cache_bookkeeping", 3.0, 3.25, 1, {"cpu_s": 0.2}),
        # straddles the window's end: not read
        ("cache_side_effect", 59.0, 61.0, 3,
         {"cpu_s": 0.1, "ingest_n": 50, "echo_inplace_n": 0}),
    ]


@pytest.fixture
def ring(monkeypatch):
    from kube_batch_tpu.obs import tracer

    def install(recorded, dropped=0):
        monkeypatch.setattr(tracer, "TRACER", _Ring(recorded, dropped))

    return install


def read(run):
    return small.harness.read_metric(METRIC, run)


def test_reads_the_share_over_the_burst_cycles(ring):
    ring(spans({"cpu_s": 0.5, "ingest_n": 800, "echo_inplace_n": 800},
               {"cpu_s": 0.5, "ingest_n": 800, "echo_inplace_n": 792}))
    assert read(_burst_run()) == pytest.approx(100.0 * 1592 / 1600)


def test_a_drain_without_echoes_counts_its_ingests(ring):
    ring(spans({"cpu_s": 0.5, "ingest_n": 800, "echo_inplace_n": 800},
               {"cpu_s": 0.5, "ingest_n": 200}))
    assert read(_burst_run()) == pytest.approx(80.0)


def test_reads_nothing_without_the_stage(ring):
    """The program before the in-place path: ingests, no echo stage."""
    ring(spans({"cpu_s": 0.5, "ingest_n": 800},
               {"cpu_s": 0.5, "ingest_n": 800}))
    assert read(_burst_run()) is None


def test_reads_nothing_from_a_ring_that_dropped_spans(ring):
    ring(spans({"cpu_s": 0.5, "ingest_n": 800, "echo_inplace_n": 800},
               {"cpu_s": 0.5, "ingest_n": 800, "echo_inplace_n": 800}),
         dropped=1)
    assert read(_burst_run()) is None
