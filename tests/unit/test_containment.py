"""Solver fault containment (doc/design/robustness.md): degradation
ladder, deadline-bounded fetch with late-result discard, circuit
breaker, loop watchdog, leadership fencing, and the resync terminal
cap. An accelerator failure must degrade scheduling QUALITY, never
scheduler LIVENESS."""

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np
import pytest

from kube_batch_tpu.actions import allocate_tpu as atpu
from kube_batch_tpu.actions.allocate_tpu import AsyncSolveHandle
from kube_batch_tpu.api import PodPhase, build_resource_list
from kube_batch_tpu.cache.cache import CacheFencedError
from kube_batch_tpu.metrics import metrics as m
from kube_batch_tpu.obs import RECORDER
from kube_batch_tpu.obs import explain
from kube_batch_tpu.scheduler import LoopWatchdog, Scheduler
from kube_batch_tpu.solver import containment
from kube_batch_tpu.solver.containment import (
    CircuitBreaker,
    SolveFailed,
    SolveTimeout,
    call_with_deadline,
)
from kube_batch_tpu.utils.test_utils import (
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
)

from tests.actions.test_actions import make_cache, req, run_action


@pytest.fixture(autouse=True)
def _fresh_containment():
    """Breaker/hook/budget are process-global; every test starts (and
    leaves) them pristine."""
    containment.reset_breaker()
    containment.set_device_fault_hook(None)
    containment.configure(None)
    explain.clear()
    yield
    containment.reset_breaker()
    containment.set_device_fault_hook(None)
    containment.configure(None)
    explain.clear()


# ---------------------------------------------------------------- deadline


class TestCallWithDeadline:
    def test_returns_result(self):
        assert call_with_deadline(lambda: 41 + 1, 1.0) == 42

    def test_propagates_exception(self):
        with pytest.raises(ValueError):
            call_with_deadline(
                lambda: (_ for _ in ()).throw(ValueError("x")), 1.0
            )

    def test_timeout_abandons_and_discards_late_result(self):
        done = threading.Event()

        def slow():
            time.sleep(0.3)
            done.set()
            return "late"

        t0 = time.perf_counter()
        with pytest.raises(SolveTimeout):
            call_with_deadline(slow, 0.05, label="t")
        # Raised at the budget, well before the call finished.
        assert time.perf_counter() - t0 < 0.25
        assert not done.is_set()
        # The abandoned thread completes later; its result went nowhere.
        assert done.wait(2.0)


# -------------------------------------------------------- fetch memoization


class _SlowResult:
    """jax-path stand-in whose device→host sync hangs."""

    rounds = 1
    refills = None
    stages = None

    def __init__(self, delay, value):
        self.delay = delay
        self.value = value
        self.materialized = threading.Event()

    @property
    def assigned(self):
        time.sleep(self.delay)
        self.materialized.set()
        return self.value


class TestFetchMemoization:
    def test_failed_fetch_memoized_as_typed_error(self):
        h = AsyncSolveHandle("native")
        fut = Future()
        fut.set_exception(ValueError("device exploded"))
        h._future = fut
        with pytest.raises(SolveFailed) as e1:
            h.fetch()
        assert isinstance(e1.value.__cause__, ValueError)
        assert h.failed() and h._future is None  # detached
        # Second fetch re-raises the MEMOIZED failure, same type — never
        # a consumed-future error.
        with pytest.raises(SolveFailed) as e2:
            h.fetch()
        assert "already failed" in str(e2.value)

    def test_timeout_abandons_jax_handle_and_discards_late_result(self):
        h = AsyncSolveHandle("jax-test")
        slow = _SlowResult(0.3, np.asarray([0, 1]))
        h._result = slow
        with pytest.raises(SolveTimeout):
            h.fetch(timeout=0.05)
        assert h.failed() and h._result is None  # detached
        # The hung sync eventually completes on its abandoned thread…
        assert slow.materialized.wait(2.0)
        # …but the handle keeps raising: the late result is discarded.
        with pytest.raises(SolveFailed):
            h.fetch()
        assert h.done()

    def test_native_timeout_abandons_worker(self):
        pool = ThreadPoolExecutor(1)
        h = AsyncSolveHandle("native")
        h._future = pool.submit(
            lambda: (time.sleep(0.3), None) and None
        )
        with pytest.raises(SolveTimeout):
            h.fetch(timeout=0.05)
        with pytest.raises(SolveFailed):
            h.fetch(timeout=5.0)
        pool.shutdown(wait=True)

    def test_keyboard_interrupt_not_swallowed(self):
        """Ctrl-C at the block point must terminate, not be absorbed by
        the ladder as a 'device failure'."""
        h = AsyncSolveHandle("native")
        fut = Future()
        fut.set_exception(KeyboardInterrupt())
        h._future = fut
        with pytest.raises(KeyboardInterrupt):
            h.fetch()

    def test_fault_hook_failure_is_typed(self):
        h = AsyncSolveHandle("jax-test")
        h._result = _SlowResult(0.0, np.asarray([0]))
        h._fault_hook = lambda stage: (_ for _ in ()).throw(
            RuntimeError("injected")
        )
        with pytest.raises(SolveFailed) as e:
            h.fetch(timeout=1.0)
        assert isinstance(e.value.__cause__, RuntimeError)


# ------------------------------------------------------------------ ladder


def _build_pending_cluster(groups=4, pods=6, nodes=8):
    c = make_cache()
    c.add_queue(build_queue("default"))
    for j in range(nodes):
        c.add_node(build_node(
            f"n{j}", build_resource_list(cpu="4", memory="8Gi")
        ))
    for g in range(groups):
        c.add_pod_group(build_pod_group(
            f"pg{g}", namespace="ns", min_member=1
        ))
        for i in range(pods):
            c.add_pod(build_pod(
                "ns", f"pg{g}-p{i}", "", PodPhase.PENDING, req(),
                group_name=f"pg{g}",
            ))
    return c


class TestDegradationLadder:
    def test_mid_cycle_exception_degrades_not_fails(self, monkeypatch):
        """The acceptance path: a solver exception mid-cycle produces a
        COMPLETED cycle with tasks placed via a lower rung, the rung
        sequence visible in stats + flight record + metrics."""
        monkeypatch.setenv("KBT_SOLVER", "jax")
        monkeypatch.delenv("KBT_SOLVER_TOPK", raising=False)
        calls = []

        def hook(stage):
            if stage == "solve" and not calls:
                calls.append(stage)
                raise RuntimeError("injected device fault")

        containment.set_device_fault_hook(hook)
        before = m.solver_fallback.get(("dense", "native", "exception"))
        RECORDER.begin_cycle()
        c = _build_pending_cluster()
        run_action(c, "allocate_tpu")
        assert c.wait_for_side_effects()
        rec = RECORDER.end_cycle()
        # Cycle completed and placed every task, on the floor rung.
        assert len(c.binder.binds) == 24
        ladder = atpu.last_stats["solve_ladder"]
        assert [(e["rung"], e["outcome"]) for e in ladder] == [
            ("dense", "exception"), ("native", "ok"),
        ]
        assert ladder[0]["exc"] == "RuntimeError"
        assert atpu.last_stats["solve_degraded"] is True
        assert atpu.last_stats["backend"] == "native"
        # Flight record carries the same sequence.
        assert rec["solver"]["ladder"] == ladder
        assert rec["solver"]["degraded"] is True
        # Metric with {from,to,reason} labels.
        assert m.solver_fallback.get(
            ("dense", "native", "exception")
        ) == before + 1
        assert containment.last_fallback["reason"] == "exception"
        c.shutdown()

    def test_sparse_rung_falls_to_dense_first(self, monkeypatch):
        monkeypatch.setenv("KBT_SOLVER", "jax")
        monkeypatch.setenv("KBT_SOLVER_TOPK", "4")
        calls = []

        def hook(stage):
            if stage == "solve" and len(calls) < 1:
                calls.append(stage)
                raise RuntimeError("injected")

        containment.set_device_fault_hook(hook)
        c = _build_pending_cluster()
        run_action(c, "allocate_tpu")
        assert c.wait_for_side_effects()
        ladder = atpu.last_stats["solve_ladder"]
        assert [(e["rung"], e["outcome"]) for e in ladder] == [
            ("sparse", "exception"), ("dense", "ok"),
        ]
        assert len(c.binder.binds) == 24
        c.shutdown()

    def test_timeout_jumps_to_native_and_opens_breaker(self, monkeypatch):
        monkeypatch.setenv("KBT_SOLVER", "jax")
        monkeypatch.delenv("KBT_SOLVER_TOPK", raising=False)
        # The budget bounds every rung, the native floor's too: it must
        # leave a loaded host room for the floor's solve, while the
        # device rung cannot finish inside it whatever its length.
        containment.configure(solve_budget=2.0)
        released = threading.Event()

        def hook(stage):
            if stage == "solve":
                # A wedged device sync: blocks until the cycle is over,
                # so the deadline always fires first.
                released.wait(30.0)

        containment.set_device_fault_hook(hook)
        c = _build_pending_cluster()
        try:
            run_action(c, "allocate_tpu")
        finally:
            released.set()
        assert c.wait_for_side_effects()
        ladder = atpu.last_stats["solve_ladder"]
        assert [(e["rung"], e["outcome"]) for e in ladder] == [
            ("dense", "timeout"), ("native", "ok"),
        ]
        assert len(c.binder.binds) == 24
        # An abandoned solve quarantines the device path immediately.
        assert containment.BREAKER.state == "open"

        # Next cycle (fresh pending work): breaker pins straight to
        # native — no device dispatch, no per-cycle failure latency.
        containment.set_device_fault_hook(None)
        c.add_pod_group(build_pod_group("pgx", namespace="ns",
                                        min_member=1))
        c.add_pod(build_pod("ns", "pgx-p0", "", PodPhase.PENDING, req(),
                            group_name="pgx"))
        run_action(c, "allocate_tpu")
        assert atpu.last_stats.get("breaker_pinned") is True
        assert atpu.last_stats["backend"] == "native"
        assert atpu.last_stats["solve_ladder"] == [
            {"rung": "native", "outcome": "ok"}
        ]
        c.shutdown()

    def test_rescued_cycle_keeps_failure_streak(self, monkeypatch):
        """A sparse failure rescued by the dense rung is still a
        device-path failure: if the rescue reset the streak, a
        persistently broken sparse program would burn a failed dispatch
        every cycle forever without ever opening the breaker."""
        monkeypatch.setenv("KBT_SOLVER", "jax")
        monkeypatch.setenv("KBT_SOLVER_TOPK", "4")
        containment.reset_breaker(failure_threshold=2, cooldown_cycles=8)
        state = {}

        def hook(stage):
            if stage == "solve" and state.pop("armed", False):
                raise RuntimeError("sparse-only fault")

        containment.set_device_fault_hook(hook)
        c = _build_pending_cluster()
        state["armed"] = True
        run_action(c, "allocate_tpu")
        assert [
            (e["rung"], e["outcome"])
            for e in atpu.last_stats["solve_ladder"]
        ] == [("sparse", "exception"), ("dense", "ok")]
        assert containment.BREAKER.failure_streak == 1

        c.add_pod_group(build_pod_group("pgx", namespace="ns",
                                        min_member=1))
        c.add_pod(build_pod("ns", "pgx-p0", "", PodPhase.PENDING, req(),
                            group_name="pgx"))
        state["armed"] = True
        run_action(c, "allocate_tpu")
        assert containment.BREAKER.state == "open"
        c.shutdown()

    def test_synchronous_dispatch_exception_contained(self, monkeypatch):
        """A launch that raises SYNCHRONOUSLY (trace/compile error,
        device lost at dispatch — before any fetch) must descend the
        ladder like an async failure, not escape the cycle."""
        monkeypatch.setenv("KBT_SOLVER", "jax")
        monkeypatch.delenv("KBT_SOLVER_TOPK", raising=False)
        orig = atpu.AllocateTpuAction._launch_rung

        def boom(self, rung, inputs, ctx):
            if rung != "native":
                raise RuntimeError("device lost at dispatch")
            return orig(self, rung, inputs, ctx)

        monkeypatch.setattr(atpu.AllocateTpuAction, "_launch_rung", boom)
        c = _build_pending_cluster()
        run_action(c, "allocate_tpu")
        assert c.wait_for_side_effects()
        assert len(c.binder.binds) == 24
        ladder = atpu.last_stats["solve_ladder"]
        assert ladder[-1] == {"rung": "native", "outcome": "ok"}
        assert any(
            e["rung"] == "dense" and e["outcome"] == "exception"
            for e in ladder
        )
        assert atpu.last_stats["backend"] == "native"
        assert containment.BREAKER.failure_streak >= 1
        c.shutdown()

    def test_device_tensorize_exception_contained(self, monkeypatch):
        """A device pack that raises (dead backend during the
        host→device upload) re-tensorizes host-side and solves on the
        native floor, quarantining via the breaker."""
        monkeypatch.setenv("KBT_SOLVER", "jax")
        monkeypatch.delenv("KBT_SOLVER_TOPK", raising=False)
        orig = atpu.tensorize

        def boom(ssn, device=True, **kw):
            if device:
                raise RuntimeError("backend dead during upload")
            return orig(ssn, device=device, **kw)

        monkeypatch.setattr(atpu, "tensorize", boom)
        before = m.solver_fallback.get(("device", "native", "tensorize"))
        c = _build_pending_cluster()
        run_action(c, "allocate_tpu")
        assert c.wait_for_side_effects()
        assert len(c.binder.binds) == 24
        assert atpu.last_stats["backend"] == "native"
        assert atpu.last_stats["solve_ladder"] == [
            {"rung": "native", "outcome": "ok"}
        ]
        assert m.solver_fallback.get(
            ("device", "native", "tensorize")
        ) == before + 1
        assert containment.BREAKER.failure_streak >= 1
        assert containment.last_fallback["reason"] == "tensorize"
        c.shutdown()


# ----------------------------------------------------------------- breaker


class TestCircuitBreaker:
    def test_opens_at_threshold_and_recloses_via_probe(self):
        probe_ok = [False]
        b = CircuitBreaker(
            failure_threshold=3, cooldown_cycles=2,
            probe=lambda t: probe_ok[0],
        )
        b.record_device_failure("exception", exc="E")
        b.record_device_failure("exception", exc="E")
        assert b.state == "closed" and b.allow_device()
        b.record_device_failure("exception", exc="E")
        assert b.state == "open"
        # Cooldown ticks per cycle: one pinned cycle, then half-open +
        # probe; a failing probe re-opens with a fresh cooldown.
        assert b.allow_device() is False
        assert b.allow_device() is False  # probe ran and failed
        assert b.state == "open" and b.probes_failed == 1
        # Fault clears: cooldown again, then the probe re-promotes.
        probe_ok[0] = True
        assert b.allow_device() is False
        assert b.allow_device() is True
        assert b.state == "closed" and b.reclosures == 1
        assert b.allow_device() is True

    def test_success_resets_streak(self):
        b = CircuitBreaker(failure_threshold=3)
        b.record_device_failure("exception")
        b.record_device_failure("exception")
        b.record_device_success()
        b.record_device_failure("exception")
        assert b.state == "closed"

    def test_timeout_opens_immediately(self):
        b = CircuitBreaker(failure_threshold=3)
        b.record_device_failure("timeout", open_now=True)
        assert b.state == "open" and b.trips == 1

    def test_pin_open_blocks_until_unpinned(self):
        b = CircuitBreaker(cooldown_cycles=1, probe=lambda t: True)
        b.pin_open("bench-degraded")
        for _ in range(5):
            assert b.allow_device() is False
        assert b.state_dict()["pinned"] == "bench-degraded"
        b.unpin()
        assert b.allow_device() is True

    def test_state_dict_shape(self):
        b = CircuitBreaker()
        b.record_device_failure("exception", exc="XlaRuntimeError",
                                open_now=True)
        d = b.state_dict()
        assert d["state"] == "open"
        assert d["last_failure"]["exc"] == "XlaRuntimeError"
        assert d["quarantine_age_seconds"] is not None
        assert d["cooldown_cycles_left"] > 0


# ---------------------------------------------------------------- watchdog


class TestLoopWatchdog:
    def test_trips_once_per_wedged_cycle(self):
        trips = []
        before = m.scheduler_watchdog_trips.get()
        wd = LoopWatchdog(budget=0.1, on_trip=trips.append)
        now = time.monotonic()
        wd.cycle_begin(0)
        assert wd.check(now=now) is False  # within budget
        assert wd.check(now=now + 1.0) is True
        assert wd.check(now=now + 2.0) is False  # once per cycle
        assert len(trips) == 1 and "cycle 0" in trips[0]
        assert wd.last_trip["cycle"] == 0
        # A NEW wedged cycle trips again.
        wd.cycle_end()
        wd.cycle_begin(1)
        assert wd.check(now=now + 9.0) is True
        assert m.scheduler_watchdog_trips.get() == before + 2

    def test_no_trip_when_idle_or_healthy(self):
        wd = LoopWatchdog(budget=0.05, on_trip=None)
        assert wd.check() is False  # nothing in flight
        wd.cycle_begin(0)
        wd.cycle_end()
        assert wd.check(now=time.monotonic() + 9.0) is False

    def test_trip_fences_cache_and_hooks_via_scheduler(self):
        from kube_batch_tpu.cache import SchedulerCache
        from kube_batch_tpu.utils.test_utils import (
            FakeBinder,
            FakeEvictor,
            FakeStatusUpdater,
            FakeVolumeBinder,
        )

        cache = SchedulerCache(
            binder=FakeBinder(), evictor=FakeEvictor(),
            status_updater=FakeStatusUpdater(),
            volume_binder=FakeVolumeBinder(),
        )
        s = Scheduler(cache, schedule_period=0.01)
        fenced = []
        s.fence_hooks.append(fenced.append)
        wd = LoopWatchdog(budget=0.01, on_trip=s._on_watchdog_trip)
        wd.cycle_begin(7)
        assert wd.check(now=time.monotonic() + 1.0) is True
        assert fenced and "cycle 7" in fenced[0]
        assert cache.fence_reason() is not None
        with pytest.raises(CacheFencedError):
            cache.bind(type("T", (), {"uid": "t1"})(), "n1")
        cache.shutdown()

    def test_trip_stops_standalone_run_loop(self):
        """Without leader election there is no lost-leadership event to
        end the loop: a trip must stop the run loop itself, or a fenced
        standalone scheduler spins CacheFencedError cycles forever
        while reporting healthy."""
        from kube_batch_tpu.cache import SchedulerCache
        from kube_batch_tpu.utils.test_utils import (
            FakeBinder,
            FakeEvictor,
            FakeStatusUpdater,
            FakeVolumeBinder,
        )

        cache = SchedulerCache(
            binder=FakeBinder(), evictor=FakeEvictor(),
            status_updater=FakeStatusUpdater(),
            volume_binder=FakeVolumeBinder(),
        )
        s = Scheduler(cache, schedule_period=0.01)
        stop = threading.Event()
        s._run_stop = stop  # what run() stamps before starting the dog
        s._on_watchdog_trip("watchdog: cycle 3 exceeded budget")
        assert stop.is_set()
        assert cache.fence_reason() is not None
        cache.shutdown()


# ----------------------------------------------------------------- fencing


class TestCacheFencing:
    def _bound_cluster(self):
        c = make_cache()
        c.add_queue(build_queue("default"))
        c.add_node(build_node(
            "n1", build_resource_list(cpu="8", memory="8Gi")
        ))
        c.add_pod_group(build_pod_group("pg", namespace="ns",
                                        min_member=1))
        c.add_pod(build_pod("ns", "p1", "", PodPhase.PENDING, req(),
                            group_name="pg"))
        return c

    def test_fenced_bind_refused(self):
        c = self._bound_cluster()
        task = next(iter(next(iter(c.jobs.values())).tasks.values()))
        before = m.cache_binds_fenced.get()
        c.fence("lease lost")
        with pytest.raises(CacheFencedError):
            c.bind(task, "n1")
        assert c.bind_batch([task]) == []
        assert m.cache_binds_fenced.get() == before + 2
        assert not c.binder.binds
        c.shutdown()

    def test_fenced_side_effect_thread_refuses_late_bind(self):
        """The zombie-leader case: a bind side effect QUEUED before the
        fence must not reach the cluster after it."""
        c = self._bound_cluster()
        job = next(iter(c.jobs.values()))
        task = next(iter(job.tasks.values()))
        snapshot = task.clone()
        c.fence("watchdog: cycle 3 hung")
        before = m.cache_binds_fenced.get()
        # Call the side-effect half directly — this is exactly what a
        # worker thread of the deposed leader would execute.
        c._bind_side_effect(task.pod, "n1", snapshot)
        assert not c.binder.binds
        assert m.cache_binds_fenced.get() == before + 1
        # The task is NOT resynced either: it belongs to the successor.
        assert c.err_tasks.empty()
        c.shutdown()

    def test_fenced_evict_refused(self):
        c = self._bound_cluster()
        task = next(iter(next(iter(c.jobs.values())).tasks.values()))
        c.fence("deposed")
        with pytest.raises(CacheFencedError):
            c.evict(task, "preempted")
        assert not c.evictor.evicts
        c.shutdown()

    def test_unfence_restores(self):
        c = self._bound_cluster()
        c.fence("x")
        c.unfence()
        assert c.fence_reason() is None
        task = next(iter(next(iter(c.jobs.values())).tasks.values()))
        c.bind(task, "n1")
        assert c.wait_for_side_effects()
        assert len(c.binder.binds) == 1
        c.shutdown()


class TestElectorFencing:
    def test_fence_releases_lease_and_signals_loss(self, tmp_path):
        from kube_batch_tpu.cli.server import LeaderElector

        el = LeaderElector(str(tmp_path), identity="wedged-1")
        assert el.try_acquire() is True
        import os

        assert os.path.exists(el.lock_path)
        lost = threading.Event()
        el._lost = lost
        el.fence("watchdog: cycle 12 exceeded budget")
        assert not os.path.exists(el.lock_path)
        assert lost.is_set()
        assert el.is_leader is False
        assert el.fenced_reason.startswith("watchdog")
        # A healthy successor takes the lease IMMEDIATELY — no waiting
        # out the lease duration behind a zombie's renewals.
        el2 = LeaderElector(str(tmp_path), identity="healthy-2")
        assert el2.try_acquire() is True
        # And the fenced identity cannot re-acquire.
        assert el.try_acquire() is False


# ------------------------------------------------------------ /debug/vars


class TestDebugVarsRobustness:
    def test_one_curl_degraded_visibility(self):
        import json
        import urllib.request

        from kube_batch_tpu.cli import start_metrics_server

        containment.BREAKER.record_device_failure(
            "timeout", exc="SolveTimeout", open_now=True
        )
        containment.note_fallback("dense", "native", "timeout",
                                  exc="SolveTimeout")
        server, _thread = start_metrics_server("127.0.0.1:0")
        try:
            port = server.server_address[1]
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/vars", timeout=5
            ) as resp:
                doc = json.loads(resp.read().decode())
        finally:
            server.shutdown()
        rb = doc["robustness"]
        assert rb["breaker"]["state"] == "open"
        assert rb["breaker"]["quarantine_age_seconds"] is not None
        assert rb["last_fallback"]["reason"] == "timeout"
        assert rb["solve_budget_seconds"] > 0
        assert "watchdog_trips" in rb
        assert "cache_fence" in rb


# ---------------------------------------------------------- resync terminal


class TestResyncTerminalCap:
    def test_poisoned_task_dropped_and_named(self):
        c = make_cache()
        c.add_queue(build_queue("default"))
        c.add_node(build_node(
            "n1", build_resource_list(cpu="8", memory="8Gi")
        ))
        c.add_pod_group(build_pod_group("pg", namespace="ns",
                                        min_member=1))
        c.add_pod(build_pod("ns", "p1", "", PodPhase.PENDING, req(),
                            group_name="pg"))
        c._max_resync_attempts = 4

        def always_fails(task):
            raise RuntimeError("permanently poisoned")

        c._sync_task = always_fails
        job = next(iter(c.jobs.values()))
        task = next(iter(job.tasks.values()))
        before = m.task_resync_terminal.get()
        c._resync_task(task.clone())
        # Drain until quiescent: each pass re-queues with attempt+1
        # until the cap drops the task terminally.
        for _ in range(c._max_resync_attempts + 2):
            c.drain_resync_queue()
            if c.err_tasks.empty():
                break
        assert c.err_tasks.empty()
        assert m.task_resync_terminal.get() == before + 1
        verdict = explain.get_verdict(task.job)
        assert verdict is not None
        assert verdict.reason == "resync-terminal"
        # The standalone verdict counts the drops, so the reason gauge
        # (summing verdict.unassigned) can actually go nonzero.
        assert verdict.unassigned == 1
        assert "ns/p1" in verdict.detail["resync_terminal"]
        assert (
            verdict.detail["resync_terminal"]["ns/p1"]["attempts"]
            >= c._max_resync_attempts
        )
        c.shutdown()

    def test_terminal_gauge_survives_busy_cycles(self, monkeypatch):
        """The sticky standalone resync-terminal verdict must keep the
        reason gauge nonzero on BUSY cycles too — its task is never in
        ctx.tasks, so without the explicit fold the absent-reason
        zeroing erases the bucket whenever other jobs keep the solver
        busy."""
        monkeypatch.delenv("KBT_SOLVER", raising=False)
        c = make_cache()
        c.add_queue(build_queue("default"))
        c.add_node(build_node(
            "n1", build_resource_list(cpu="8", memory="8Gi")
        ))
        # The poisoned job: a best-effort pod (empty resreq) stays
        # PENDING in the cache but is excluded from tensorize, exactly
        # the shape a terminally-dropped task leaves behind.
        c.add_pod_group(build_pod_group("pgdead", namespace="ns",
                                        min_member=1))
        c.add_pod(build_pod("ns", "pdead", "", PodPhase.PENDING,
                            build_resource_list(), group_name="pgdead"))
        dead_job = next(
            j for j in c.jobs.values() if j.name == "pgdead"
        )
        explain.note_resync_terminal(
            dead_job.uid, "ns", "pgdead", "ns/pdead", attempts=8
        )
        # Busy-cycle work: a schedulable pod from another job.
        c.add_pod_group(build_pod_group("pgbusy", namespace="ns",
                                        min_member=1))
        c.add_pod(build_pod("ns", "pbusy", "", PodPhase.PENDING, req(),
                            group_name="pgbusy"))
        run_action(c, "allocate_tpu")
        assert c.wait_for_side_effects()
        assert len(c.binder.binds) == 1  # the cycle was busy, not idle
        assert m.unschedulable_tasks.get(("resync-terminal",)) == 1.0
        c.shutdown()

    def test_recovering_task_not_dropped(self):
        c = make_cache()
        c.add_queue(build_queue("default"))
        c.add_node(build_node(
            "n1", build_resource_list(cpu="8", memory="8Gi")
        ))
        c.add_pod_group(build_pod_group("pg", namespace="ns",
                                        min_member=1))
        c.add_pod(build_pod("ns", "p1", "", PodPhase.PENDING, req(),
                            group_name="pg"))
        attempts = []

        def flaky(task):
            attempts.append(task.uid)
            if len(attempts) < 3:
                raise RuntimeError("transient")

        c._sync_task = flaky
        task = next(iter(next(iter(c.jobs.values())).tasks.values()))
        before = m.task_resync_terminal.get()
        c._resync_task(task.clone())
        for _ in range(6):
            if c.drain_resync_queue():
                break
        assert len(attempts) == 3  # third reconcile succeeded
        assert m.task_resync_terminal.get() == before
        c.shutdown()
