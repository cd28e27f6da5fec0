"""Placement-latency ledger + decision-audit-log tests
(kube_batch_tpu/obs/latency.py, doc/design/observability.md §5):
arrival→bind stage stamping through the REAL cache/action pipeline,
gang last-member semantics, bind-failure and evict requeues restarting
the clock, ledger GC with the pod/job (the metrics-GC pattern — no
per-pod leak), explain verdicts carrying cycles-waited, the audit
ring's bounds + deterministic dump, and the HTTP surfaces."""

import json
import time
import urllib.request

import pytest

from kube_batch_tpu import metrics
from kube_batch_tpu.api import PodPhase, TaskStatus, build_resource_list
from kube_batch_tpu.cache import SchedulerCache
from kube_batch_tpu.framework import close_session, get_action, open_session
from kube_batch_tpu.obs import explain
from kube_batch_tpu.obs.latency import (
    AUDIT,
    LEDGER,
    AuditLog,
    PlacementLedger,
)
from kube_batch_tpu.utils.test_utils import (
    FakeBinder,
    FakeEvictor,
    FakeStatusUpdater,
    FakeVolumeBinder,
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
)
from tests.actions.test_actions import make_tiers

TIERS_ARGS = (
    ["priority", "gang", "conformance"],
    ["drf", "predicates", "proportion", "nodeorder"],
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def now(self):
        return self.t

    def tick(self, dt):
        self.t += dt


@pytest.fixture(autouse=True)
def _clean_state():
    LEDGER.reset()
    AUDIT.reset()
    explain.clear()
    yield
    LEDGER.reset()
    AUDIT.reset()
    LEDGER.configure(clock=time.monotonic)
    explain.clear()


def _ledger_with_clock():
    ledger = PlacementLedger()
    clock = FakeClock()
    ledger.configure(clock=clock.now)
    return ledger, clock


def _cache(**kwargs):
    return SchedulerCache(
        binder=kwargs.pop("binder", FakeBinder()),
        evictor=FakeEvictor(),
        status_updater=FakeStatusUpdater(),
        volume_binder=FakeVolumeBinder(),
        **kwargs,
    )


def _run_allocate_tpu(cache):
    ssn = open_session(cache, make_tiers(*TIERS_ARGS))
    action, _ = get_action("allocate_tpu")
    action.execute(ssn)
    return ssn


# -- ledger unit: stage math -------------------------------------------------


def test_pod_lifecycle_stage_decomposition():
    ledger, clock = _ledger_with_clock()
    ledger.note_arrival("u1", "t/p1", "t/job")
    clock.tick(5.0)
    ledger.note_placed(
        (("u1", "t/job"),), {"t/job": "q0"}, kind="periodic", solve_s=1.0
    )
    ledger.note_dispatched(("u1",))
    clock.tick(2.0)
    ledger.note_applied("u1")
    assert ledger.applied == 1
    assert ledger.entry_count() == 0  # entry dropped at applied
    stages = ledger.percentiles()["q0"]["periodic"]
    assert abs(stages["queue_wait"]["p50_s"] - 4.0) < 0.25
    assert abs(stages["solve"]["p50_s"] - 1.0) < 0.1
    assert abs(stages["bind"]["p50_s"] - 2.0) < 0.15
    assert abs(stages["total"]["p50_s"] - 7.0) < 0.4
    assert stages["dispatch"]["p50_s"] == 0.0


def test_micro_cycle_kind_keys_series():
    ledger, clock = _ledger_with_clock()
    ledger.note_arrival("u1", "t/p1", "t/job")
    clock.tick(1.0)
    ledger.note_placed((("u1", "t/job"),), {"t/job": "q0"}, kind="micro")
    ledger.note_dispatched(("u1",))
    ledger.note_applied("u1")
    assert "micro" in ledger.percentiles()["q0"]


def test_gang_latency_is_last_members_applied():
    ledger, clock = _ledger_with_clock()
    ledger.note_arrival("u1", "t/p1", "t/gang")
    clock.tick(1.0)
    ledger.note_arrival("u2", "t/p2", "t/gang")
    clock.tick(4.0)
    ledger.note_placed(
        (("u1", "t/gang"), ("u2", "t/gang")), {"t/gang": "q0"}
    )
    ledger.note_dispatched(("u1", "u2"))
    ledger.note_applied("u1")
    assert ledger.gang_samples == 0  # one member still pending
    clock.tick(4.0)
    ledger.note_applied("u2")
    assert ledger.gang_samples == 1
    gang = ledger.percentiles()["q0"]["periodic"]["gang_total"]
    # Last member applied at t=9, first arrival at t=0.
    assert abs(gang["p50_s"] - 9.0) < 0.5
    assert gang["count"] == 1
    # Per-member series kept alongside: two total samples.
    assert ledger.percentiles()["q0"]["periodic"]["total"]["count"] == 2


def test_bind_failure_restarts_clock():
    ledger, clock = _ledger_with_clock()
    ledger.note_arrival("u1", "t/p1", "t/job")
    clock.tick(3.0)
    ledger.note_placed((("u1", "t/job"),), {"t/job": "q0"})
    ledger.note_dispatched(("u1",))
    ledger.note_bind_failed("u1")
    assert ledger.bind_failures == 1 and ledger.requeues == 1
    clock.tick(7.0)
    ledger.note_placed((("u1", "t/job"),), {"t/job": "q0"})
    ledger.note_dispatched(("u1",))
    ledger.note_applied("u1")
    total = ledger.percentiles()["q0"]["periodic"]["total"]
    # Measured from the requeue (t=3), not the first arrival.
    assert abs(total["p50_s"] - 7.0) < 0.4


def test_ledger_gc_with_pod_and_job_no_leak():
    ledger, _clock = _ledger_with_clock()
    for j in range(4):
        for i in range(8):
            ledger.note_arrival(f"u{j}-{i}", f"t/p{j}-{i}", f"t/job{j}")
    assert ledger.entry_count() == 32
    ledger.forget_pod("u0-0")
    assert ledger.entry_count() == 31
    for j in range(4):
        ledger.forget_job(f"t/job{j}")
    assert ledger.entry_count() == 0
    assert ledger.job_wait_info("t/job0") is None


def test_sketch_merge_matches_direct_adds():
    """stage_percentiles merges per-key sketches via
    QuantileSketch.merge — merged quantiles must match a sketch that
    saw every value directly (DDSketch mergeability)."""
    from kube_batch_tpu.obs.telemetry import QuantileSketch

    direct = QuantileSketch()
    a, b = QuantileSketch(), QuantileSketch()
    for i in range(200):
        v = 0.001 * (i + 1)
        direct.add(v)
        (a if i % 2 else b).add(v)
    a.merge(b)
    for q in (0.5, 0.95, 0.99):
        assert a.quantile(q) == direct.quantile(q)
    assert a.count == direct.count


def test_requeue_recreated_entry_keeps_job_attribution():
    """An applied pod's entry is gone; a later evict re-creates it
    UNDER ITS JOB so the re-placement's gang accounting and per-queue
    series stay attributed (a job-less orphan would fall out of both)."""
    ledger, clock = _ledger_with_clock()
    ledger.note_arrival("u1", "t/p1", "t/gang")
    ledger.note_placed((("u1", "t/gang"),), {"t/gang": "q0"})
    ledger.note_dispatched(("u1",))
    ledger.note_applied("u1")
    assert ledger.entry_count() == 0
    clock.tick(2.0)
    ledger.note_requeued("u1", "evicted", job="t/gang")
    assert ledger.entry_count() == 1
    assert ledger.job_wait_info("t/gang") is not None
    clock.tick(3.0)
    ledger.note_placed((("u1", "t/gang"),), {"t/gang": "q0"})
    ledger.note_dispatched(("u1",))
    ledger.note_applied("u1")
    # Second wave closed under the job: a second gang-member sample
    # (and no orphan entry left behind).
    assert ledger.entry_count() == 0
    total = ledger.percentiles()["q0"]["periodic"]["total"]
    assert total["count"] == 2


def test_disabled_ledger_is_inert():
    ledger, _clock = _ledger_with_clock()
    ledger.configure(enabled=False)
    ledger.note_arrival("u1", "t/p1", "t/job")
    ledger.note_placed((("u1", "t/job"),), {})
    ledger.note_applied("u1")
    assert ledger.entry_count() == 0 and ledger.stamped == 0


# -- applied stamps folded per chunk ------------------------------------------


def _fold_scenario(ledger, clock):
    """Drive a ledger to the eve of a bind drain and return its applied
    stamps ``(uid, ts)`` in bind order: four queues, periodic and micro
    placements, gangs of 60-90 pods, a gang whose wave closes mid-list
    followed by other gangs' binds, a second wave of that gang, an
    SLO-targeted job that meets its target on some pods and misses it on
    others, a requeued pod, and a uid the ledger never saw."""
    import random

    rng = random.Random(29)
    jobs = {}
    for g in range(6):
        job = f"t/g{g}"
        jobs[job] = [f"{job}-{i}" for i in range(60 + 6 * g)]
    jobs["t/short"] = [f"t/short-{i}" for i in range(3)]
    for job, uids in jobs.items():
        for uid in uids:
            clock.tick(rng.uniform(0.0, 0.02))
            ledger.note_arrival(uid, uid, job)
    slo = [f"t/srv-{i}" for i in range(8)]
    for uid in slo:
        ledger.note_arrival(uid, uid, "t/srv", workload_class="serving",
                            slo_target=7.0)
    queues = {job: f"q{n % 4}" for n, job in enumerate(jobs)}
    queues["t/srv"] = "q1"
    clock.tick(1.0)
    for n, (job, uids) in enumerate(jobs.items()):
        ledger.note_placed(((u, job) for u in uids), queues,
                           kind="micro" if n % 3 == 2 else "periodic",
                           solve_s=rng.uniform(0.05, 0.5))
        clock.tick(0.1)
    ledger.note_placed(((u, "t/srv") for u in slo), queues, solve_s=0.2)
    ledger.note_dispatched([u for uids in jobs.values() for u in uids])
    ledger.note_dispatched(slo)
    ledger.note_bind_failed("t/g0-1")  # requeued, then placed again
    clock.tick(0.5)
    ledger.note_placed((("t/g0-1", "t/g0"),), queues, solve_s=0.1)
    ledger.note_dispatched(("t/g0-1",))
    order = [u for job in ("t/g0", "t/g1") for u in jobs[job]]
    order += jobs["t/short"]  # the wave closes here, mid-list
    order += slo[:4] + ["t/never-seen"]
    rest = [u for job in ("t/g2", "t/g3", "t/g4", "t/g5")
            for u in jobs[job]]
    rng.shuffle(rest)
    order += rest[:200] + slo[4:] + rest[200:]
    stamps = []
    for n, uid in enumerate(order):
        clock.tick(rng.uniform(0.0, 0.004) if n % 50 else 1.0)
        stamps.append((uid, clock.t))
        if uid == "t/short-2":  # a new wave of the short gang opens
            ledger.note_arrival("t/short-3", "t/short-3", "t/short")
    clock.tick(0.3)
    ledger.note_placed((("t/short-3", "t/short"),), queues)
    ledger.note_dispatched(("t/short-3",))
    clock.tick(0.2)
    stamps.append(("t/short-3", clock.t))
    return stamps


def _ledger_state(ledger, hist, slo_counter):
    return {
        "sketch_keys": list(ledger._sketches),
        "sketches": {
            key: (s.count, s.sum, s.sketch.count, dict(s.sketch.buckets),
                  s.sketch.low_count, s.sketch.low_min)
            for key, s in ledger._sketches.items()
        },
        "percentiles": ledger.percentiles(),
        "stage_percentiles": ledger.stage_percentiles(),
        "counters": (ledger.stamped, ledger.applied, ledger.gang_samples,
                     ledger.bind_failures, ledger.requeues),
        "slo": (ledger.serving_summary(), dict(ledger._slo_counts),
                dict(ledger._job_slo_applied),
                dict(ledger._job_slo_missed)),
        "jobs": {
            job: (jw.applied, jw.arrivals, jw.first_arrival_ts,
                  jw.cycles_waited, jw.queue)
            for job, jw in ledger._jobs.items()
        },
        "entries": sorted(ledger._entries),
        "done": list(ledger._done),
        "histogram": (dict(hist._counts), dict(hist._sums),
                      dict(hist._totals)),
        "slo_placements": dict(slo_counter._values),
    }


@pytest.mark.parametrize("chunk", [1, 7, 1024])
def test_folded_stamps_equal_per_bind_applied(chunk, monkeypatch):
    """note_applied_many over a drain's ``(uid, ts)`` stamps leaves the
    ledger, its histogram and its SLO counts exactly as one note_applied
    per uid at those clock readings does, chunk by chunk in any size."""
    from kube_batch_tpu.metrics import metrics as metrics_mod

    def run(fold):
        hist = metrics.Histogram(
            "t_placement", buckets=metrics_mod.LATENCY_BUCKETS)
        slo_counter = metrics.Counter("t_slo")
        monkeypatch.setattr(metrics_mod, "pod_placement_latency", hist)
        monkeypatch.setattr(metrics, "pod_slo_placements", slo_counter)
        ledger, clock = _ledger_with_clock()
        stamps = _fold_scenario(ledger, clock)
        if fold:
            for start in range(0, len(stamps), chunk):
                ledger.note_applied_many(stamps[start:start + chunk])
        else:
            for uid, ts in stamps:
                clock.t = ts
                ledger.note_applied(uid)
        return _ledger_state(ledger, hist, slo_counter)

    per_bind, folded = run(False), run(True)
    assert folded == per_bind
    # The scenario reaches what it is meant to.
    assert per_bind["entries"] == []
    assert per_bind["counters"][1] == 462  # every pod stamped applied
    assert len(per_bind["done"]) == 256
    assert per_bind["counters"][2] == 8  # gangs g0-g5, t/srv, t/short
    placed, met, missed = per_bind["slo"][1]["serving"]
    assert placed == 8 and met and missed
    assert per_bind["slo_placements"]
    assert {kind for q in per_bind["percentiles"].values()
            for kind in q} == {"periodic", "micro"}


def test_fold_of_unknown_or_no_stamps_changes_nothing():
    ledger, clock = _ledger_with_clock()
    ledger.note_arrival("u1", "t/p1", "t/job")
    ledger.note_applied_many([])
    ledger.note_applied_many([("u-unknown", 1.0)])
    assert ledger.applied == 0 and ledger.entry_count() == 1
    assert ledger.percentiles() == {}


# -- cache/action integration ------------------------------------------------


def _gang_cache(n=2, cpu="1000m"):
    cache = _cache()
    cache.add_queue(build_queue("default", weight=1))
    cache.add_node(build_node(
        "n1", build_resource_list(cpu="8", memory="16Gi", pods=110)
    ))
    cache.add_pod_group(build_pod_group(
        "g", namespace="t", min_member=n, queue="default"
    ))
    for i in range(n):
        cache.add_pod(build_pod(
            "t", f"p{i}", "", PodPhase.PENDING,
            build_resource_list(cpu=cpu, memory="1Gi"),
            group_name="g",
        ))
    return cache


def test_arrival_to_bind_through_real_pipeline():
    cache = _gang_cache()
    assert LEDGER.stamped == 2  # add_pod stamped both arrivals
    before = metrics.pod_placement_latency.count(
        ("total", "default", "periodic")
    )
    ssn = _run_allocate_tpu(cache)
    try:
        assert cache.wait_for_side_effects(timeout=30.0)
        assert LEDGER.applied == 2
        assert LEDGER.entry_count() == 0
        stages = LEDGER.percentiles()["default"]["periodic"]
        for stage in ("queue_wait", "solve", "dispatch", "bind",
                      "total", "gang_total"):
            assert stage in stages, stage
        assert LEDGER.gang_samples == 1
        # Prometheus histogram observed at the applied seam.
        after = metrics.pod_placement_latency.count(
            ("total", "default", "periodic")
        )
        assert after - before == 2
        # Audit: one placed record for the job.
        placed = [r for r in AUDIT.records() if r["action"] == "placed"]
        assert placed and placed[-1]["job"] == "t/g"
        assert placed[-1]["count"] == 2
    finally:
        close_session(ssn)
        cache.shutdown()


class FailingBinder:
    def bind(self, pod, hostname):
        raise RuntimeError("injected bind failure")


def test_bind_failure_requeues_through_cache():
    cache = _gang_cache()
    cache.binder = FailingBinder()
    ssn = _run_allocate_tpu(cache)
    try:
        assert cache.wait_for_side_effects(timeout=30.0)
        assert LEDGER.applied == 0
        assert LEDGER.bind_failures == 2
        assert LEDGER.entry_count() == 2  # entries survive, requeued
    finally:
        close_session(ssn)
        cache.shutdown()


def test_evict_restarts_clock_through_cache():
    cache = _gang_cache()
    ssn = _run_allocate_tpu(cache)
    try:
        assert cache.wait_for_side_effects(timeout=30.0)
        requeues_before = LEDGER.requeues
        job = cache.jobs["t/g"]
        task = next(iter(
            job.task_status_index[TaskStatus.BINDING].values()
        ))
        cache.evict(task, "test preemption")
        assert cache.wait_for_side_effects(timeout=30.0)
        assert LEDGER.requeues == requeues_before + 1
    finally:
        close_session(ssn)
        cache.shutdown()


def test_job_cleanup_gcs_ledger_entries():
    cache = _gang_cache()
    try:
        assert LEDGER.entry_count() == 2
        for i in range(2):
            cache.delete_pod(cache.jobs["t/g"].tasks[f"t-p{i}"].pod)
        assert LEDGER.entry_count() == 0
    finally:
        cache.shutdown()


# -- explain wiring ----------------------------------------------------------


def test_verdict_carries_cycles_waited():
    cache = _cache()
    cache.add_queue(build_queue("default", weight=1))
    cache.add_node(build_node(
        "n1", build_resource_list(cpu="8", memory="16Gi", pods=110),
        labels={"zone": "a"},
    ))
    cache.add_pod_group(build_pod_group(
        "blocked", namespace="t", min_member=1, queue="default"
    ))
    cache.add_pod(build_pod(
        "t", "b0", "", PodPhase.PENDING,
        build_resource_list(cpu="1000m", memory="1Gi"),
        group_name="blocked", selector={"zone": "nowhere"},
    ))
    ssn = _run_allocate_tpu(cache)
    close_session(ssn)
    # Churn a node so the second cycle actually SOLVES (an unchanged
    # cycle takes the warm no-op path, which re-derives no verdicts —
    # cycles_waited counts solving cycles by design).
    cache.add_node(build_node(
        "n2", build_resource_list(cpu="8", memory="16Gi", pods=110),
        labels={"zone": "b"},
    ))
    ssn = _run_allocate_tpu(cache)
    try:
        verdict = explain.get_verdict("t/blocked")
        assert verdict is not None
        assert verdict.detail["cycles_waited"] == 2
        assert "waiting_since" in verdict.detail
        assert "waiting_seconds" in verdict.detail
        # The diagnosis prose answers "how long and why" in one query.
        diag = explain.diagnose_job(ssn, ssn.jobs["t/blocked"])
        assert "waiting 2 solve cycle(s)" in explain.format_diagnosis(
            diag
        )
        # One unassigned audit record per touched cycle.
        unassigned = [
            r for r in AUDIT.records()
            if r["action"] == "unassigned" and r["job"] == "t/blocked"
        ]
        assert len(unassigned) == 2
        assert unassigned[-1]["reason"] == explain.REASON_PREDICATE
        assert unassigned[-1]["waited_cycles"] == 2
    finally:
        close_session(ssn)
        cache.shutdown()


# -- audit log ---------------------------------------------------------------


def test_audit_ring_bounds_and_deterministic_dump(tmp_path):
    audit = AuditLog(capacity=16)
    for i in range(40):
        audit.append({
            "action": "placed", "job": f"t/j{i}", "queue": "q",
            "count": 1,
        })
    meta = audit.meta()
    assert meta["records"] == 16
    assert meta["dropped"] == 24
    assert meta["seq"] == 40
    lines = audit.dump_lines()
    assert lines == audit.dump_lines()  # deterministic re-dump
    parsed = [json.loads(line) for line in lines]
    assert parsed[0]["seq"] == 25 and parsed[-1]["seq"] == 40
    path = audit.dump_jsonl(str(tmp_path / "audit.jsonl"))
    assert open(path).read().splitlines() == lines


def test_micro_defer_restamps_requeued_and_splits_stages():
    """A deferred micro cycle placed nothing: its arrival batch must be
    re-stamped ``requeued`` (reason ``micro-defer:<outcome>``) so the
    wait until the periodic pickup is attributed to the defer — the
    requeue RESTARTS the clock, and the eventual placement's total
    measures from the requeue, not the first arrival."""
    from kube_batch_tpu.scheduler import Scheduler
    from kube_batch_tpu.solver import warm

    cache = _cache()
    cache.add_queue(build_queue("q0", weight=1))
    cache.add_node(build_node(
        "n0", build_resource_list(cpu="8", memory="32Gi", pods=110),
    ))
    conf = (
        'actions: "allocate_tpu"\n'
        "tiers:\n"
        "- plugins:\n"
        "  - name: priority\n"
        "  - name: gang\n"
        "  - name: conformance\n"
        "- plugins:\n"
        "  - name: drf\n"
        "  - name: predicates\n"
        "  - name: proportion\n"
        "  - name: nodeorder\n"
    )
    sched = Scheduler(cache, scheduler_conf=conf)
    # After Scheduler init: the constructor installs its own clock.
    clock = FakeClock(10.0)
    LEDGER.configure(enabled=True, clock=clock.now)
    cache.add_pod_group(build_pod_group(
        "pg0", namespace="ns", min_member=1, queue="q0",
    ))
    cache.add_pod(build_pod(
        "ns", "pg0-p0", "", PodPhase.PENDING,
        build_resource_list(cpu="250m", memory="256Mi"),
        group_name="pg0",
    ))
    sched.run_once()
    assert cache.wait_for_side_effects(timeout=30.0)
    assert cache.wait_for_bookkeeping(timeout=30.0)
    # Void the warm state so the next micro cycle MUST defer (cold).
    warm.invalidate(cache)
    clock.tick(0.001)
    cache.add_pod_group(build_pod_group(
        "pgd", namespace="ns", min_member=1, queue="q0",
    ))
    cache.add_pod(build_pod(
        "ns", "pgd-p0", "", PodPhase.PENDING,
        build_resource_list(cpu="250m", memory="256Mi"),
        group_name="pgd",
    ))
    requeues_before = LEDGER.requeues
    assert sched.run_micro()
    entry = next(
        e for e in LEDGER._entries.values() if e.pod == "ns/pgd-p0"
    )
    assert entry.stage == "requeued"
    assert entry.requeues == 1
    assert entry.last_reason == "micro-defer:cold"
    assert LEDGER.requeues == requeues_before + 1
    # Periodic pickup 4 ms after the defer: total is measured from the
    # requeue stamp (0.004s), NOT the original arrival (0.005s).
    clock.tick(0.004)
    sched.run_once()
    assert cache.wait_for_side_effects(timeout=30.0)
    done = next(d for d in LEDGER._done if d["pod"] == "ns/pgd-p0")
    assert done["requeues"] == 1
    assert done["total_s"] == pytest.approx(0.004, abs=1e-6)
    cache.shutdown()


def test_audit_records_carry_no_wall_clock():
    """Replay byte-stability contract: nothing wall-clock-shaped in a
    record — only the ledger clock (vclock) and the cycle counter."""
    clock = FakeClock(7.0)
    LEDGER.configure(clock=clock.now)
    LEDGER.begin_cycle(3, kind="micro")
    AUDIT.append({"action": "placed", "job": "t/j", "queue": "q",
                  "count": 1})
    rec = AUDIT.records()[-1]
    assert rec["vclock"] == 7.0 and rec["cycle"] == 3
    assert rec["kind"] == "micro"
    assert "ts" not in rec and "t_start" not in rec


# -- HTTP surface ------------------------------------------------------------


def test_debug_latency_endpoint_and_vars():
    from kube_batch_tpu.cli import start_metrics_server

    cache = _gang_cache()
    ssn = _run_allocate_tpu(cache)
    assert cache.wait_for_side_effects(timeout=30.0)
    server, _thread = start_metrics_server("127.0.0.1:0")
    try:
        port = server.server_address[1]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/latency", timeout=5
        ) as resp:
            doc = json.loads(resp.read().decode())
        assert doc["type"] == "placement-latency"
        assert doc["applied"] == 2
        assert "default" in doc["percentiles"]
        assert doc["audit"]["records"] >= 1
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/vars", timeout=5
        ) as resp:
            dvars = json.loads(resp.read().decode())
        assert dvars["latency"]["applied"] == 2
        assert "total" in dvars["latency"]["stage_p99_s"]
    finally:
        server.shutdown()
        close_session(ssn)
        cache.shutdown()
