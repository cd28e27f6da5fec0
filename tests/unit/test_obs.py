"""Observability layer tests: span tracer (thread safety, cross-thread
nesting, export), flight-recorder ring (wraparound, error capture,
SIGUSR1 dump roundtrip), and the /debug HTTP surface.
"""

import gc
import json
import os
import signal
import threading
import time
import urllib.request
from urllib.error import HTTPError

import pytest

from kube_batch_tpu.obs.flightrecorder import FlightRecorder, install_sigusr1
from kube_batch_tpu.obs import tracer as tracer_mod
from kube_batch_tpu.obs.tracer import _NULL, Tracer


# ------------------------------------------------------------------ tracer


def test_disabled_span_records_nothing():
    t = Tracer()
    with t.span("a"):
        with t.span("b"):
            pass
    assert t.events() == []
    assert t.spans_recorded == 0


def test_span_nesting_and_args():
    t = Tracer()
    t.enable()
    t.begin_cycle(7)
    with t.span("outer"):
        with t.span("inner", k=64):
            pass
    events = {e["name"]: e for e in t.events()}
    assert set(events) == {"outer", "inner"}
    outer, inner = events["outer"], events["inner"]
    assert inner["args"]["parent"] == outer["args"]["sid"]
    assert outer["args"]["parent"] == 0
    assert inner["args"]["cycle"] == 7
    assert inner["args"]["k"] == 64
    assert inner["ph"] == "X"
    assert inner["dur"] >= 0


def test_complete_records_retroactive_span():
    t = Tracer()
    t.enable()
    t0 = time.perf_counter()
    time.sleep(0.001)
    with t.span("parent"):
        t.complete("apply", t0)
    by_name = {e["name"]: e for e in t.events()}
    assert by_name["apply"]["args"]["parent"] == (
        by_name["parent"]["args"]["sid"]
    )
    assert by_name["apply"]["dur"] >= 1000  # >= 1ms in us


def test_worker_spans_nest_under_the_right_cycle():
    """Spans opened on worker threads (the overlapped solve/apply
    pattern) adopt the submitting span's id and the cycle stamp."""
    t = Tracer()
    t.enable()
    t.begin_cycle(3)
    results = []

    barrier = threading.Barrier(4)

    with t.span("cycle_span"):
        token = t.capture()

        def worker(i):
            # Barrier: all four workers are alive at once, so their
            # thread idents are guaranteed distinct (idents can be
            # reused once a thread exits).
            barrier.wait(timeout=10)
            with t.adopt(token), t.span(f"worker-{i}"):
                time.sleep(0.002)
            results.append(i)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(4)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()

    events = {e["name"]: e for e in t.events()}
    cycle_sid = events["cycle_span"]["args"]["sid"]
    tids = set()
    for i in range(4):
        ev = events[f"worker-{i}"]
        assert ev["args"]["parent"] == cycle_sid
        assert ev["args"]["cycle"] == 3
        tids.add(ev["tid"])
    assert len(tids) == 4  # genuinely distinct tracks
    assert sorted(results) == [0, 1, 2, 3]


def test_adopted_spans_keep_the_capturing_cycle():
    """Async side effects drain in the NEXT cycle's overlap window by
    design — their spans must still stamp the cycle that queued them,
    not whatever the scheduler thread advanced the counter to."""
    t = Tracer()
    t.enable()
    t.begin_cycle(5)
    with t.span("submitter"):
        token = t.capture()
    t.begin_cycle(6)  # scheduler moved on before the worker drained

    def worker():
        with t.adopt(token), t.span("late-side-effect"):
            with t.span("nested"):
                pass

    th = threading.Thread(target=worker)
    th.start()
    th.join()
    events = {e["name"]: e for e in t.events()}
    assert events["submitter"]["args"]["cycle"] == 5
    assert events["late-side-effect"]["args"]["cycle"] == 5
    assert events["nested"]["args"]["cycle"] == 5
    # A fresh span on the main thread sees the advanced cycle.
    with t.span("current"):
        pass
    assert {e["name"]: e for e in t.events()}["current"]["args"][
        "cycle"
    ] == 6


def test_tracer_thread_safety_under_contention():
    """Many threads spanning concurrently: every span is recorded, no
    event is torn/corrupt."""
    t = Tracer(capacity=100_000)
    t.enable()
    n_threads, per_thread = 8, 200

    def hammer(k):
        for i in range(per_thread):
            with t.span("s", thread=k, i=i):
                pass

    threads = [
        threading.Thread(target=hammer, args=(k,))
        for k in range(n_threads)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    # Collections the hammering triggers add their own ``gc`` spans.
    events = [e for e in t.events() if e["name"] == "s"]
    assert len(events) == n_threads * per_thread
    assert t.spans_recorded == len(t.events())
    sids = [e["args"]["sid"] for e in events]
    assert len(set(sids)) == len(sids)  # unique span ids


@pytest.fixture
def no_auto_gc():
    """No automatic collection mid-test: an enabled tracer records each
    as a ``gc`` span, which would break exact span counts."""
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


def test_event_ring_caps_memory(no_auto_gc):
    t = Tracer(capacity=10)
    t.enable()
    for i in range(25):
        with t.span(f"s{i}"):
            pass
    assert len(t.events()) == 10
    assert t.spans_recorded == 25
    assert t.dropped == 15
    # The ring keeps the NEWEST spans.
    assert t.events()[-1]["name"] == "s24"


def test_export_chrome_trace(tmp_path, no_auto_gc):
    t = Tracer()
    t.enable()
    with t.span("a"):
        with t.span("b"):
            pass
    path = t.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    metas = [e for e in events if e.get("ph") == "M"]
    assert {e["name"] for e in xs} == {"a", "b"}
    assert metas and metas[0]["name"] == "thread_name"


def _args(t, name):
    return [e["args"] for e in t.events() if e["name"] == name]


def test_stage_sums_nest_and_stay_per_thread():
    t = Tracer()
    t.enable()
    with t.span("outer"):
        for _ in range(2):
            with t.stage("call"):
                with t.stage("ingest"):
                    with t.stage("wait"):
                        time.sleep(0.002)
                    time.sleep(0.001)

        def other():
            with t.span("other"):
                with t.stage("ingest"):
                    pass

        th = threading.Thread(target=other)
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
    (outer,), (other,) = _args(t, "outer"), _args(t, "other")
    assert outer["call_n"] == outer["ingest_n"] == 2
    assert outer["wait_n"] == 2
    assert outer["call_s"] >= outer["ingest_s"] >= outer["wait_s"]
    assert outer["wait_s"] >= 0.004
    for stage in ("call", "ingest", "wait"):
        # The first entry of a stage reads the CPU clock, the second not.
        assert outer[f"{stage}_cpu_n"] == 1
        # less the clock reads' own cost, so a near-idle stage can dip
        assert -1e-4 <= outer[f"{stage}_cpu_s"] <= outer[f"{stage}_s"]
    # The other thread's stage went to its own span only.
    assert other["ingest_n"] == 1 and "call_n" not in other


def test_stage_reads_the_cpu_clock_on_every_nth_entry():
    t = Tracer()
    t.enable()
    n = 3 * tracer_mod.CPU_EVERY + 1
    with t.span("s"):
        for _ in range(n):
            with t.stage("x"):
                pass
    (args,) = _args(t, "s")
    assert args["x_n"] == n and args["x_cpu_n"] == 4
    assert "mutex_wait_cpu_n" not in args


def test_stage_goes_to_the_innermost_span():
    t = Tracer()
    t.enable()
    with t.span("outer"):
        with t.span("inner"):
            with t.stage("ledgers"):
                pass
    assert "ledgers_n" not in _args(t, "outer")[0]
    assert _args(t, "inner")[0]["ledgers_n"] == 1


def test_disabled_tracer_records_nothing_and_installs_no_gc_hook():
    t = Tracer()
    assert t.stage("ingest") is _NULL
    assert t.acquire(threading.Lock()) is _NULL
    with t.span("a", cpu=True), t.stage("ingest"):
        pass
    gc.collect()
    assert t.events() == [] and t.spans_recorded == 0
    assert t._on_gc not in gc.callbacks
    t.enable()
    assert t._on_gc in gc.callbacks
    t.disable()
    assert t._on_gc not in gc.callbacks


def test_disabled_sites_test_one_flag_and_read_no_clock(monkeypatch):
    """Tracing off, each instrumented site costs one ``enabled`` test: no
    clock read, no allocation, the shared no-op returned."""
    reads = []

    class Counting(Tracer):
        @property
        def enabled(self):
            reads.append(1)
            return False

        @enabled.setter
        def enabled(self, value):
            pass

    t = Counting()

    def no_clock():
        raise AssertionError("clock read on the disabled path")

    monkeypatch.setattr(tracer_mod, "_perf", no_clock)
    monkeypatch.setattr(tracer_mod, "_cpu", no_clock)
    monkeypatch.setattr(time, "perf_counter", no_clock)
    for site in (lambda: t.span("s", cpu=True), lambda: t.stage("ingest"),
                 lambda: t.acquire(threading.Lock())):
        reads.clear()
        with site() as cm:
            assert cm is _NULL
        assert len(reads) == 1


def test_stage_outside_any_span_records_nothing():
    t = Tracer()
    t.enable()
    assert t.stage("ingest") is _NULL
    lock = threading.RLock()
    with t.acquire(lock), lock:
        pass
    assert [e for e in t.events() if e["name"] != "gc"] == []


def test_acquire_times_the_wait_and_holds_the_lock():
    t = Tracer()
    t.enable()
    lock = threading.RLock()
    held = threading.Event()

    def other_holder():
        with lock:
            held.set()
            time.sleep(0.02)

    th = threading.Thread(target=other_holder)
    th.start()
    held.wait(timeout=5)
    with t.span("waiter"):
        with t.acquire(lock), lock:
            # Held here, and by this thread: another cannot take it.
            assert not _try_from_other_thread(lock)
    th.join(timeout=10)
    assert not th.is_alive()
    assert _try_from_other_thread(lock)
    (args,) = _args(t, "waiter")
    assert args["mutex_wait_n"] == 1 and args["mutex_wait_s"] >= 0.015
    assert "mutex_wait_cpu_s" not in args  # a blocked wait burns no CPU


def _try_from_other_thread(lock):
    got = []

    def attempt():
        if lock.acquire(blocking=False):
            lock.release()
            got.append(True)

    th = threading.Thread(target=attempt)
    th.start()
    th.join(timeout=10)
    assert not th.is_alive()
    return bool(got)


def test_cpu_span_records_its_thread_cpu():
    t = Tracer()
    t.enable()
    with t.span("busy", cpu=True, k=1):
        deadline = time.perf_counter() + 0.02
        while time.perf_counter() < deadline:
            pass
    with t.span("plain"):
        pass
    (busy,), (plain,) = _args(t, "busy"), _args(t, "plain")
    assert busy["k"] == 1
    assert 0.005 <= busy["cpu_s"] <= 0.2
    assert "cpu_s" not in plain


def test_gc_collect_yields_a_gc_span():
    t = Tracer()
    t.enable()
    with t.span("around"):
        gc.collect()
    t.disable()
    spans = [e for e in t.events() if e["name"] == "gc"]
    full = [e for e in spans if e["args"]["generation"] == 2]
    assert full and full[-1]["args"]["collected"] >= 0
    around = _args(t, "around")[0]
    assert full[-1]["args"]["parent"] == around["sid"]


def test_stage_cpu_leaves_out_collections():
    """A collection inside a stage's sampled entry is the ``gc`` span's,
    not the stage's: scaled by CPU_EVERY it would swamp the stage."""
    t = Tracer()
    t.enable()
    keep = [[i] for i in range(300_000)]  # a full collection walks these
    with t.span("s"):
        with t.stage("x"):
            gc.collect()
    t.disable()
    del keep
    (args,) = _args(t, "s")
    gc_s = sum(e["dur"] for e in t.events() if e["name"] == "gc"
               and e["args"]["parent"] == args["sid"]) / 1e6
    assert gc_s > 0.005
    assert args["x_cpu_n"] == 1 and args["x_s"] >= gc_s
    assert abs(args["x_cpu_s"]) < 0.5 * gc_s


def test_stage_cpu_leaves_out_its_clock_reads(monkeypatch, no_auto_gc):
    """On fake clocks where a CPU read costs 5 µs: a read entry holds about
    one read of its own and both reads of a nested read entry, and scaled
    by CPU_EVERY they would count as the stage's work."""
    now = [0.0]  # one busy thread: CPU and wall advance together

    def cpu():
        now[0] += 2.5e-6
        value = now[0]
        now[0] += 2.5e-6
        return value

    monkeypatch.setattr(tracer_mod, "_cpu", cpu)
    monkeypatch.setattr(tracer_mod, "_perf", lambda: now[0])
    t = Tracer()
    t.enable()
    with t.span("s"):
        for _ in range(2 * tracer_mod.CPU_EVERY):
            with t.stage("outer"):
                now[0] += 30e-6
                with t.stage("inner"):
                    now[0] += 20e-6
    t.disable()
    (args,) = _args(t, "s")
    assert args["outer_cpu_n"] == args["inner_cpu_n"] == 2
    assert args["inner_cpu_s"] == pytest.approx(2 * 20e-6)
    assert args["outer_cpu_s"] == pytest.approx(2 * 50e-6)


def test_jit_compile_yields_a_compile_span():
    import jax
    import jax.numpy as jnp

    t = Tracer()
    t.enable()

    def kbt_traced_probe(x):
        return x * 3 + 1

    t0 = time.perf_counter()
    jax.jit(kbt_traced_probe)(jnp.arange(7)).block_until_ready()
    t1 = time.perf_counter()
    t.disable()
    compiles = [e for e in t.events() if e["name"] == "compile"
                and "kbt_traced_probe" in (e["args"]["fun"] or "")]
    assert compiles
    # On the tracer's own clock, inside the call that compiled.
    start = (t0 - t._epoch) * 1e6
    end = (t1 - t._epoch) * 1e6
    ev = compiles[0]
    assert start - 1e3 <= ev["ts"] and ev["ts"] + ev["dur"] <= end + 1e3


def test_scheduler_loop_waits_are_spans():
    """The production loop's waits carry spans: the think-time drain and
    sleep (``loop_wait``), the arrival park (``micro_park``) and the
    coalescing window before a micro cycle (``micro_coalesce``); so does
    the work after each cycle (``observe_cycle``)."""
    from kube_batch_tpu.api import PodPhase, build_resource_list
    from kube_batch_tpu.cache import SchedulerCache
    from kube_batch_tpu.cluster import InProcessCluster
    from kube_batch_tpu.obs.tracer import TRACER
    from kube_batch_tpu.scheduler import Scheduler
    from kube_batch_tpu.utils.test_utils import (
        build_node, build_pod, build_pod_group, build_queue)

    cluster = InProcessCluster(simulate_kubelet=True)
    cluster.create_queue(build_queue("default", 1))
    cluster.create_node(
        build_node("n1", build_resource_list(cpu="8", memory="16Gi")))
    sched = Scheduler(SchedulerCache(cluster=cluster), schedule_period=0.5)
    assert sched.micro_enabled
    stop = threading.Event()
    loop = threading.Thread(target=sched.run, args=(stop,), daemon=True)
    TRACER.reset()
    TRACER.enable()
    try:
        loop.start()
        time.sleep(0.7)  # past the first cycle, into its think time
        cluster.create_pod_group(
            build_pod_group("pg1", namespace="ns", min_member=1))
        cluster.create_pod(build_pod(
            "ns", "p0", "", PodPhase.PENDING,
            build_resource_list(cpu="500m", memory="256Mi"),
            group_name="pg1"))
        deadline = time.time() + 10
        while time.time() < deadline and not sched.micro_cycles_run:
            time.sleep(0.02)
        time.sleep(0.6)
    finally:
        stop.set()
        loop.join(timeout=10)
        TRACER.disable()
    assert not loop.is_alive()
    events = TRACER.events()
    TRACER.reset()
    names = {e["name"] for e in events}
    assert {"loop_wait", "micro_park", "micro_coalesce",
            "observe_cycle"} <= names, names
    phases = {e["args"]["phase"] for e in events if e["name"] == "loop_wait"}
    assert "sleep" in phases
    coalesce = [e for e in events if e["name"] == "micro_coalesce"]
    assert all(e["args"]["window_s"] > 0 for e in coalesce)


# --------------------------------------------------------- flight recorder


def test_ring_buffer_wraparound():
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.begin_cycle(i)
        fr.phase("open_session")
        fr.phase_done("open_session", 1.0)
        fr.end_cycle(e2e_ms=float(i))
    records = fr.snapshot()
    assert len(records) == 4
    assert [r["cycle"] for r in records] == [6, 7, 8, 9]
    assert all(r["ok"] for r in records)
    # seq keeps counting monotonically across wraps.
    assert [r["seq"] for r in records] == [7, 8, 9, 10]


def test_error_capture_pins_failing_phase():
    fr = FlightRecorder(capacity=8)
    fr.begin_cycle(0)
    fr.phase("action:allocate_tpu")
    try:
        raise RuntimeError("kaboom")
    except RuntimeError as exc:
        # Scheduler's finally moves the phase on; the pinned
        # failed phase must win in the committed record.
        fr.mark_failed_phase()
        fr.phase("close_session")
        fr.record_error(exc)
    last = fr.snapshot()[-1]
    assert last["ok"] is False
    assert last["phase"] == "action:allocate_tpu"
    assert "RuntimeError: kaboom" in last["error"]
    assert any("kaboom" in line for line in last["traceback"])
    assert fr.error_count == 1


def test_annotate_and_open_record_in_dump():
    fr = FlightRecorder(capacity=4)
    fr.begin_cycle(0)
    fr.annotate("solver", {"backend": "native", "placed": 10})
    dump = json.loads(fr.dump_json("test"))
    assert dump["type"] == "flightrecorder"
    assert dump["records"][-1]["in_flight"] is True
    assert dump["records"][-1]["solver"]["backend"] == "native"
    # Canonical: dumps twice byte-identically (modulo dumped_at).
    fr.end_cycle()


def test_annotate_coerces_unserializable_values():
    import numpy as np

    fr = FlightRecorder(capacity=2)
    fr.begin_cycle(0)
    fr.annotate("solver", {
        "placed": np.int64(5), "frac": np.float32(0.5),
        "obj": object(),
    })
    fr.end_cycle()
    dump = json.loads(fr.dump_json("test"))
    solver = dump["records"][-1]["solver"]
    assert solver["placed"] == 5
    assert isinstance(solver["obj"], str)


def test_sigusr1_dump_roundtrip(tmp_path):
    fr_dir = str(tmp_path)
    from kube_batch_tpu.obs.flightrecorder import RECORDER

    RECORDER.begin_cycle(0)
    RECORDER.phase("action:allocate_tpu")
    RECORDER.end_cycle(e2e_ms=1.0)
    installed = install_sigusr1(fr_dir)
    if not installed:
        pytest.skip("cannot install SIGUSR1 handler on this platform")
    try:
        os.kill(os.getpid(), signal.SIGUSR1)
        deadline = time.time() + 5.0
        dumps = []
        while time.time() < deadline:
            dumps = [
                f for f in os.listdir(fr_dir) if "sigusr1" in f
            ]
            if dumps:
                break
            time.sleep(0.02)
        assert dumps, "SIGUSR1 produced no dump file"
        with open(os.path.join(fr_dir, dumps[0])) as f:
            doc = json.load(f)
        assert doc["reason"] == "sigusr1"
        assert doc["records"], "dump carries no records"
        assert doc["records"][-1]["phases_ms"] is not None
    finally:
        signal.signal(signal.SIGUSR1, signal.SIG_DFL)


# ------------------------------------------------------------ HTTP surface


@pytest.fixture
def debug_server():
    from kube_batch_tpu.cli import start_metrics_server

    server, _thread = start_metrics_server("127.0.0.1:0")
    port = server.server_address[1]
    yield f"http://127.0.0.1:{port}"
    server.shutdown()


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.status, resp.read().decode()


def test_healthz_and_debug_vars(debug_server):
    status, body = _get(f"{debug_server}/healthz")
    assert status == 200 and body == "ok\n"
    status, body = _get(f"{debug_server}/debug/vars")
    assert status == 200
    doc = json.loads(body)
    assert doc["version"]
    assert doc["uptime_seconds"] >= 0
    assert "cycle_errors" in doc
    assert "last_cycle_age_seconds" in doc


def test_debug_flightrecorder_endpoint(debug_server):
    from kube_batch_tpu.obs.flightrecorder import RECORDER

    RECORDER.begin_cycle(0)
    RECORDER.end_cycle()
    status, body = _get(f"{debug_server}/debug/flightrecorder")
    assert status == 200
    doc = json.loads(body)
    assert doc["type"] == "flightrecorder"
    assert doc["records"]


def test_unknown_path_gets_404_with_body(debug_server):
    with pytest.raises(HTTPError) as err:
        _get(f"{debug_server}/nope/nothing")
    assert err.value.code == 404
    body = err.value.read().decode()
    assert "/nope/nothing" in body  # NOT a silent empty 404


def test_debug_jobs_unknown_job_404(debug_server):
    with pytest.raises(HTTPError) as err:
        _get(f"{debug_server}/debug/jobs/ns/ghost")
    assert err.value.code == 404
    assert "ns/ghost" in err.value.read().decode()
