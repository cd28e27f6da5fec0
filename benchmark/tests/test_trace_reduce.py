"""The trace reduction, on hand-made events and on traces recorded on a
TPU v5e in PR 22 (``fixtures/*.json.gz``: the device plane's op and module
lines from 150 ms starting at the first solve module of a burst profile
and of a steady profile, with the harness's window marker, as
:func:`trace_reduce.load` reads them)."""

import gzip
import json
import os

import numpy as np
import pytest

import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
RECORDED = ["burst_trace.json.gz", "steady_trace.json.gz"]
DEV = "/device:TPU:0"


def _trace(ops, modules=(), host=()):
    return {
        DEV: {tr.OPS_LINE: list(ops), tr.MODULES_LINE: list(modules)},
        "/host:CPU": {"python": list(host)},
    }


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_busy_is_the_clipped_union():
    t = _trace([("a", 0, 10), ("b", 5, 10), ("c", 30, 10), ("d", 100, 5)])
    # window [8, 35): a and b cover 8..15, c covers 30..35.
    assert tr.busy_ns(t, DEV, [(8, 35)]) == 12
    assert tr.busy_ns(t, DEV, [(0, 20), (100, 200)]) == 15 + 5


def test_op_and_module_times():
    t = _trace([("all-reduce.1", 0, 4), ("fusion.2", 4, 6),
                ("all-reduce.1", 20, 2)],
               modules=[("jit_solve_sparse", 0, 10), ("jit_topk", 20, 2)])
    assert tr.op_time_ns(t, DEV, [(0, 100)]) == {"all-reduce.1": 6,
                                                 "fusion.2": 6}
    assert tr.op_time_ns(t, DEV, [(0, 100)], match=tr.COLLECTIVE) == {
        "all-reduce.1": 6}
    assert tr.op_time_ns(t, DEV, [(0, 5)], line=tr.MODULES_LINE) == {
        "jit_solve_sparse": 5}


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    t = _trace([("a", 10, 10), ("b", 50, 10)])
    spans = [("cycle", 0, 100), ("tensorize", 20, 50)]
    gaps = tr.idle_gaps(t, DEV, (0, 100), spans)
    # holes: 0-10 (cycle), 20-50 (tensorize), 60-100 (cycle), longest first
    assert gaps == [("cycle", 40), ("tensorize", 30), ("cycle", 10)]
    assert tr.idle_gaps(t, DEV, (0, 100), [])[0] == ("no host span", 40)


def test_device_planes_in_device_order_and_clock_offset():
    t = {"/device:TPU:10": {}, "/device:TPU:2": {}, "/host:CPU": {
        "python": [("kbt_bench_window", 5_000, 10)]}}
    assert tr.device_planes(t) == ["/device:TPU:2", "/device:TPU:10"]
    assert tr.clock_offset(t, "kbt_bench_window", 2.0) == 5_000 - 2e9
    assert tr.clock_offset(t, "missing", 2.0) is None


def _bitmap_busy(ops, lo, hi):
    """A plain reading of busy time: mark every covered nanosecond."""
    covered = np.zeros(int(hi - lo), dtype=bool)
    for _, start, dur in ops:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            covered[int(round(s - lo)):int(round(e - lo))] = True
    return int(covered.sum())


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_trace(name):
    with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
        t = json.load(f)
    planes = tr.device_planes(t)
    assert planes and planes[0] == DEV
    mark = tr.find_host_event(t, "kbt_bench_window")
    assert mark is not None
    ops = t[DEV][tr.OPS_LINE]
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    assert mark[0] < lo < hi < mark[1]  # the profiled burst holds the solve
    busy = tr.busy_ns(t, DEV, [(lo, hi)])
    assert 0 < busy < hi - lo
    assert abs(busy - _bitmap_busy(ops, lo, hi)) <= len(ops)  # rounding
    per_op = tr.op_time_ns(t, DEV, [(lo, hi)])
    assert sum(per_op.values()) >= busy * (1 - 1e-9)
    gaps = tr.idle_gaps(t, DEV, (lo, hi), [], top=10**6)
    assert sum(g for _, g in gaps) == pytest.approx(hi - lo - busy)
    # One chip: no collective ran.
    assert tr.op_time_ns(t, DEV, [(lo, hi)], match=tr.COLLECTIVE) == {}


def test_load_reads_an_xplane(tmp_path):
    """load() on a real .xplane.pb, recorded here on the host CPU."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("kbt_bench_window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    paths = list(tmp_path.glob("**/*.xplane.pb"))
    assert len(paths) == 1
    t = tr.load(str(paths[0]))
    assert "/host:CPU" in t
    start, end = tr.find_host_event(t, "kbt_bench_window")
    assert end > start
