"""Long-horizon telemetry: rollup math, sketch bounds, ring wraparound,
the scheduler feed, and the HTTP surface (doc/design/observability.md
§4)."""

import json
import math
import random
import urllib.request

import pytest

from kube_batch_tpu import metrics
from kube_batch_tpu.obs.telemetry import (
    TELEMETRY,
    QuantileSketch,
    Telemetry,
    collect_fairness,
    collect_watermarks,
)


# -- quantile sketch ---------------------------------------------------------

def test_sketch_relative_error_bound():
    """The DDSketch contract: any quantile estimate is within alpha
    relative error of the true order statistic."""
    rng = random.Random(7)
    sketch = QuantileSketch(alpha=0.05)
    values = [rng.uniform(0.01, 500.0) for _ in range(20_000)]
    for v in values:
        sketch.add(v)
    values.sort()
    for q in (0.01, 0.25, 0.5, 0.9, 0.99, 0.999):
        true = values[int(q * (len(values) - 1))]
        est = sketch.quantile(q)
        assert abs(est - true) / true <= 0.0501, (q, est, true)


def test_sketch_wide_dynamic_range():
    """Log buckets keep the bound across 9 orders of magnitude (bytes
    watermarks vs ms phases share the implementation)."""
    rng = random.Random(3)
    sketch = QuantileSketch(alpha=0.05)
    values = [10 ** rng.uniform(-3, 9) for _ in range(5_000)]
    for v in values:
        sketch.add(v)
    values.sort()
    for q in (0.1, 0.5, 0.95):
        true = values[int(q * (len(values) - 1))]
        assert abs(sketch.quantile(q) - true) / true <= 0.0501


def test_sketch_zero_and_negative():
    """Non-positive values (idle phase ms, signed drift) are tracked
    exactly at their min, not log-bucketed into garbage."""
    sketch = QuantileSketch()
    for v in (-0.5, 0.0, 0.0):
        sketch.add(v)
    sketch.add(10.0)
    assert sketch.count == 4
    assert sketch.quantile(0.0) == -0.5
    assert abs(sketch.quantile(1.0) - 10.0) / 10.0 <= 0.051


def test_sketch_empty_and_single():
    sketch = QuantileSketch()
    assert sketch.quantile(0.5) == 0.0
    sketch.add(42.0)
    assert abs(sketch.quantile(0.5) - 42.0) / 42.0 <= 0.051


def test_sketch_bucket_collapse_bounded():
    """Past max_buckets the lowest buckets merge; memory stays bounded
    and the tail keeps its error bound."""
    sketch = QuantileSketch(alpha=0.05, max_buckets=32)
    rng = random.Random(1)
    values = [10 ** rng.uniform(-6, 6) for _ in range(3_000)]
    for v in values:
        sketch.add(v)
    assert len(sketch.buckets) <= 32
    values.sort()
    true99 = values[int(0.99 * (len(values) - 1))]
    assert abs(sketch.quantile(0.99) - true99) / true99 <= 0.0501



def _one_at_a_time(sketch, value):
    """The sketch's add, one value at a time, written out: log bucket,
    exact non-positive min, lowest two buckets merged past the cap."""
    sketch.count += 1
    if value <= 0.0:
        if sketch.low_count == 0 or value < sketch.low_min:
            sketch.low_min = value
        sketch.low_count += 1
        return
    idx = math.ceil(math.log(value) / sketch._log_gamma)
    sketch.buckets[idx] = sketch.buckets.get(idx, 0) + 1
    if len(sketch.buckets) > sketch.max_buckets:
        low, next_low = sorted(sketch.buckets)[:2]
        sketch.buckets[next_low] += sketch.buckets.pop(low)


@pytest.mark.parametrize("max_buckets", [4, 32, 512])
def test_sketch_add_many_equals_one_at_a_time(max_buckets):
    """A batch leaves the buckets, count, low count and min exactly as
    the same values added one at a time in order, collapses included."""
    rng = random.Random(max_buckets)
    values = [10 ** rng.uniform(-6, 6) for _ in range(2_000)]
    for i in range(0, len(values), 97):
        values[i] = (0.0, -1.5, -0.25)[i % 3]
    batched = QuantileSketch(max_buckets=max_buckets)
    for start in range(0, len(values), 300):
        batched.add_many(values[start:start + 300])
    single = QuantileSketch(max_buckets=max_buckets)
    for v in values:
        _one_at_a_time(single, v)
    assert batched.buckets == single.buckets
    assert (batched.count, batched.low_count, batched.low_min) == \
        (single.count, single.low_count, single.low_min)
    if max_buckets < 512:
        assert len(batched.buckets) == max_buckets


def test_histogram_observe_many_equals_observe():
    """Batched observations: the same cumulative bucket counts, count
    and sum (float adds in order) as one observe per value."""
    from kube_batch_tpu.metrics.metrics import LATENCY_BUCKETS, Histogram

    rng = random.Random(5)
    values = [rng.expovariate(1 / 3.0) for _ in range(5_000)]
    values += [0.0, 0.001, 10.0, 400.0]
    one, many = (Histogram("t_one", buckets=LATENCY_BUCKETS),
                 Histogram("t_many", buckets=LATENCY_BUCKETS))
    for v in values:
        one.observe(v, ("q",))
    for start in range(0, len(values), 999):
        many.observe_many(values[start:start + 999], ("q",))
    many.observe_many([], ("q",))
    many.observe_many([], ("empty",))
    assert many._counts == one._counts
    assert many._sums == one._sums
    assert many._totals == one._totals


# -- window rollup -----------------------------------------------------------

def test_window_boundaries_and_stats():
    t = Telemetry(window_cycles=4, max_windows=16, raw_capacity=32)
    for c in range(10):
        t.observe_values({"x": float(c)}, cycle=c)
    ws = t.windows()
    assert len(ws) == 2 and t.windows_rolled == 2
    w0, w1 = ws
    assert (w0["start_cycle"], w0["end_cycle"], w0["cycles"]) == (0, 3, 4)
    assert (w1["start_cycle"], w1["end_cycle"], w1["cycles"]) == (4, 7, 4)
    k = w0["keys"]["x"]
    assert k["count"] == 4 and k["min"] == 0.0 and k["max"] == 3.0
    assert k["sum"] == 6.0 and k["mean"] == 1.5
    # Cycles 8, 9 sit in the open window.
    assert t.cycles_observed == 10
    assert "x" in t.snapshot()["open_window_keys"]


def test_window_ring_wraparound_counts_drops():
    t = Telemetry(window_cycles=2, max_windows=4, raw_capacity=8)
    for c in range(20):
        t.observe_values({"x": 1.0}, cycle=c)
    t.flush()  # rolls are deferred one sample; close the final window
    assert t.windows_rolled == 10
    assert len(t.windows()) == 4
    assert t.windows_dropped == 6
    # Oldest surviving window reflects the drop.
    assert t.windows()[0]["start_cycle"] == 12
    # Raw ring keeps only the newest raw_capacity samples.
    raw = t.raw()
    assert len(raw) == 8 and raw[0]["cycle"] == 12


def test_sparse_keys_roll_independently():
    """A key absent from some cycles still rolls with its own count."""
    t = Telemetry(window_cycles=4, max_windows=8)
    for c in range(4):
        values = {"always": 1.0}
        if c % 2 == 0:
            values["sometimes"] = float(c)
        t.observe_values(values, cycle=c)
    t.flush()
    w = t.windows()[0]["keys"]
    assert w["always"]["count"] == 4
    assert w["sometimes"]["count"] == 2


def test_annotate_cycle_merges_into_open_window():
    t = Telemetry(window_cycles=2, max_windows=8)
    t.observe_values({"x": 1.0}, cycle=0)
    t.annotate_cycle({"extra": 5.0})
    t.observe_values({"x": 2.0}, cycle=1)
    # Cycle 1 fills the window, but its post-cycle annotation must
    # still land in it — the roll is deferred to the next sample.
    t.annotate_cycle({"boundary": 7.0})
    t.observe_values({"x": 3.0}, cycle=2)
    w = t.windows()[0]["keys"]
    assert w["extra"]["count"] == 1 and w["extra"]["max"] == 5.0
    assert w["boundary"]["count"] == 1 and w["boundary"]["max"] == 7.0
    assert t.raw()[0]["extra"] == 5.0


def test_flush_keeps_final_boundary_annotations():
    """Run length a multiple of the window size: the final cycle's
    annotations sit past the full window and must still be flushed to
    the detectors, not dropped."""
    t = Telemetry(window_cycles=2, max_windows=8)
    for c in range(4):
        t.observe_values({"x": 1.0}, cycle=c)
    t.annotate_cycle({"violation": 1.0})
    t.flush()
    ws = t.windows()
    assert len(ws) == 2
    assert ws[1]["keys"]["violation"]["count"] == 1


def test_annotation_only_window_has_numeric_start():
    """A window that only ever saw annotate_cycle content (every cycle
    in it errored before the observe feed) still rolls with a numeric
    start_cycle — detector midpoint arithmetic must never meet None."""
    t = Telemetry(window_cycles=2, max_windows=8)
    t.annotate_cycle({"sim_cycle_errors": 1.0})
    t.flush()
    ws = t.windows()
    assert len(ws) == 1
    assert isinstance(ws[0]["start_cycle"], int)
    assert ws[0]["keys"]["sim_cycle_errors"]["count"] == 1


def test_flush_closes_tail_window():
    t = Telemetry(window_cycles=100, max_windows=8)
    for c in range(5):
        t.observe_values({"x": float(c)}, cycle=c)
    assert not t.windows()
    t.flush()
    ws = t.windows()
    assert len(ws) == 1 and ws[0]["cycles"] == 5
    assert ws[0]["end_cycle"] == 4


# -- probes ------------------------------------------------------------------

def test_watermarks_present_and_numeric():
    values = collect_watermarks()
    for key in ("alloc_blocks", "tracer_ring", "flight_ring",
                "metrics_series", "explain_verdicts"):
        assert key in values, key
    assert all(
        isinstance(v, float) and not math.isnan(v)
        for v in values.values()
    )
    assert values["alloc_blocks"] > 0


def test_fairness_probe_two_queues():
    from kube_batch_tpu.api import PodPhase, build_resource_list
    from kube_batch_tpu.cache import SchedulerCache
    from kube_batch_tpu.utils.test_utils import (
        build_node,
        build_pod,
        build_pod_group,
        build_queue,
    )

    cache = SchedulerCache()
    cache.add_queue(build_queue("q0", weight=1))
    cache.add_queue(build_queue("q1", weight=2))
    for i in range(2):
        cache.add_node(build_node(
            f"n{i}", build_resource_list(cpu="8", memory="16Gi", pods=110)
        ))
    cache.add_pod_group(build_pod_group(
        "pg0", namespace="t", min_member=1, queue="q0"
    ))
    cache.add_pod(build_pod(
        "t", "p0", "n0", PodPhase.RUNNING,
        build_resource_list(cpu="2", memory="1Gi"), group_name="pg0",
    ))
    state = {}
    drift = collect_fairness(cache, state)
    assert set(drift) == {"fairness_drift:q0", "fairness_drift:q1"}
    # q0 holds 2 of 16 CPU, weight 1 of 3 -> under its ~5.3 CPU
    # water-filled share; q1 holds nothing. Under-service = negative
    # drift (benign: the soak detector bounds the POSITIVE side).
    assert drift["fairness_drift:q0"] <= 0.0
    assert drift["fairness_drift:q1"] <= 0.0
    # Node-total memo primed.
    assert state["n_nodes"] == 2

    # Over-serve q0 past its deserved share: 12 of 16 CPU against a
    # ~5.3 CPU share -> clearly positive drift.
    for i in range(1, 6):
        cache.add_pod(build_pod(
            "t", f"p{i}", f"n{i % 2}", PodPhase.RUNNING,
            build_resource_list(cpu="2", memory="1Gi"),
            group_name="pg0",
        ))
    drift = collect_fairness(cache, state)
    assert drift["fairness_drift:q0"] > 0.2, drift
    cache.shutdown()


def test_fairness_single_queue_skipped():
    from kube_batch_tpu.cache import SchedulerCache
    from kube_batch_tpu.utils.test_utils import build_queue

    cache = SchedulerCache()
    cache.add_queue(build_queue("only", weight=1))
    assert collect_fairness(cache, {}) == {}
    cache.shutdown()


# -- the scheduler feed ------------------------------------------------------

def test_observe_scheduler_cycle_extracts_record_and_updates_gauges():
    t = Telemetry(window_cycles=4, max_windows=8)
    rec = {
        "e2e_ms": 12.5,
        "phases_ms": {"open_session": 1.5, "action:allocate_tpu": 9.0},
        "solver": {"placed": 10, "tasks": 12, "rounds": 2},
    }
    values = t.observe_scheduler_cycle(rec)
    assert values["e2e_ms"] == 12.5
    assert values["phase_ms:open_session"] == 1.5
    assert values["solver:placed"] == 10.0
    assert "alloc_blocks" in values
    from kube_batch_tpu.metrics.metrics import (
        process_rss_bytes,
        telemetry_ring_occupancy,
    )

    assert telemetry_ring_occupancy.get() >= 1.0
    if "rss_bytes" in values:
        assert process_rss_bytes.get() == values["rss_bytes"]


def test_scheduler_run_once_feeds_global_telemetry():
    """The production wiring: one run_once = one telemetry cycle."""
    from kube_batch_tpu.cache import SchedulerCache
    from kube_batch_tpu.scheduler import Scheduler
    from kube_batch_tpu.utils.test_utils import build_queue

    TELEMETRY.configure(window_cycles=2, max_windows=8, raw_capacity=16)
    cache = SchedulerCache()
    cache.add_queue(build_queue("default", weight=1))
    sched = Scheduler(cache, schedule_period=0.01)
    before = TELEMETRY.cycles_observed
    assert sched.run_once_guarded()
    assert sched.run_once_guarded()
    assert TELEMETRY.cycles_observed == before + 2
    assert "e2e_ms" in TELEMETRY.raw()[-1]
    # The heap-proportional probes run on the every-64th "expensive"
    # cadence — cycle 0 carries them, cycle 1 does not.
    assert "alloc_blocks" in TELEMETRY.raw()[0]
    assert "alloc_blocks" not in TELEMETRY.raw()[-1]
    cache.shutdown()
    TELEMETRY.reset()


def test_telemetry_env_kill_switch(monkeypatch):
    from kube_batch_tpu.cache import SchedulerCache
    from kube_batch_tpu.scheduler import Scheduler

    monkeypatch.setenv("KBT_TELEMETRY", "0")
    TELEMETRY.configure(window_cycles=2, max_windows=8)
    cache = SchedulerCache()
    sched = Scheduler(cache, schedule_period=0.01)
    assert sched.run_once_guarded()
    assert TELEMETRY.cycles_observed == 0
    cache.shutdown()
    TELEMETRY.reset()


# -- flight dump + HTTP surface ----------------------------------------------

def test_flight_dump_embeds_telemetry():
    from kube_batch_tpu.obs import RECORDER

    TELEMETRY.configure(window_cycles=2, max_windows=8)
    for c in range(4):
        TELEMETRY.observe_values({"x": float(c)}, cycle=c)
    TELEMETRY.flush()
    dump = RECORDER.dump(reason="test")
    telem = dump["telemetry"]
    assert telem["cycles_observed"] == 4
    assert len(telem["windows"]) == 2
    json.dumps(dump, sort_keys=True)  # canonical-JSON safe
    TELEMETRY.reset()


def test_debug_timeseries_and_vars_endpoints():
    from kube_batch_tpu.cli.server import start_metrics_server

    TELEMETRY.configure(window_cycles=2, max_windows=8)
    TELEMETRY.observe_values({"e2e_ms": 5.0}, cycle=0)
    TELEMETRY.observe_values({"e2e_ms": 7.0}, cycle=1)
    TELEMETRY.flush()
    server, _thread = start_metrics_server("127.0.0.1:0")
    port = server.server_address[1]
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/timeseries"
        ) as resp:
            ts = json.loads(resp.read())
        assert ts["cycles_observed"] == 2
        assert ts["windows"][0]["keys"]["e2e_ms"]["count"] == 2
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/vars"
        ) as resp:
            dv = json.loads(resp.read())
        assert dv["telemetry"]["cycles_observed"] == 2
        assert "alloc_blocks" in dv["watermarks"]
    finally:
        server.shutdown()
        TELEMETRY.reset()


def test_ms_buckets_resolution():
    """The cycle-shaped histograms carry ms-scale buckets: a 50 ms and
    a 150 ms cycle must land in different buckets (with DefBuckets both
    straddled the same 0.1/0.25 span as everything else)."""
    from bisect import bisect_left

    from kube_batch_tpu.metrics.metrics import (
        action_scheduling_latency,
        e2e_scheduling_latency,
    )

    h = e2e_scheduling_latency
    in_range = [b for b in h.buckets if 0.005 <= b <= 0.5]
    assert len(in_range) >= 10, h.buckets
    assert bisect_left(h.buckets, 0.05) != bisect_left(h.buckets, 0.15)
    assert action_scheduling_latency.buckets == h.buckets
