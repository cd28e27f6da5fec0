"""The reader of ``bind_ledger_batched_pct.burst``: the share of the bind
drain's binds whose applied stamp was folded into the latency ledger per
chunk, on hand-made spans in the tracer's ring."""

import pytest

import small
from test_stage_readers import _burst_run, _Ring

METRIC = "bind_ledger_batched_pct.burst"


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
         {"cpu_s": 0.1, "bind_call_n": 50, "ledger_stamp_n": 0}),
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
    ring(spans({"cpu_s": 0.5, "bind_call_n": 800, "ledger_stamp_n": 800},
               {"cpu_s": 0.5, "bind_call_n": 800, "ledger_stamp_n": 799}))
    assert read(_burst_run()) == pytest.approx(100.0 * 1599 / 1600)


def test_a_drain_without_stamps_counts_its_binds(ring):
    """A drain span whose binds all resolved per bind (serving pods)."""
    ring(spans({"cpu_s": 0.5, "bind_call_n": 800, "ledger_stamp_n": 800},
               {"cpu_s": 0.5, "bind_call_n": 200}))
    assert read(_burst_run()) == pytest.approx(80.0)


def test_reads_nothing_without_the_stage(ring):
    """The program before the fold: binds, no stamp stage."""
    ring(spans({"cpu_s": 0.5, "bind_call_n": 800},
               {"cpu_s": 0.5, "bind_call_n": 800}))
    assert read(_burst_run()) is None


def test_reads_nothing_with_no_binds(ring):
    ring(spans({"cpu_s": 0.5, "ledger_stamp_n": 0},
               {"cpu_s": 0.5, "ledger_stamp_n": 0}))
    assert read(_burst_run()) is None
