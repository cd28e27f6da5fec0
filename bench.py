#!/usr/bin/env python
"""tpu-batch benchmark harness.

Reproduces the BASELINE.json synthetic configs (1k pods x 100 nodes,
10k x 1k, 50k x 5k gang mix) through the REAL pipeline: SchedulerCache event
ingest -> Session open (plugins) -> tensorize -> batched TPU solve. The
baseline is the NATIVE (C++) reimplementation of the reference's greedy
allocate loop (kube_batch_tpu/native/csrc/greedy.cpp), measured outright at the headline scale
on the same snapshot arrays — the fair stand-in for the reference's
compiled Go loop. The Python greedy action is also timed on the small
config as a sanity datapoint (and as extrapolation fallback when no
native toolchain exists).

Prints ONE JSON line:
  {"metric": ..., "value": <ms>, "unit": "ms", "vs_baseline": <speedup>, ...}

- value: headline 50k x 5k batched solve latency (ms, device solve,
  steady-state after compile; host snapshot time reported separately).
- vs_baseline: measured-native-greedy-ms / tpu-solve-ms.

Usage: python bench.py [--quick] [--config small|medium|large]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, ".")



def _require_device():
    """Fail fast unless this process sees a TPU. ``JAX_PLATFORMS=cpu``
    (``make bench-smoke``) is the one explicit way onto the host CPU: a
    benchmark never falls back to it."""
    import jax

    from kube_batch_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(
            f"bench: no TPU found (default platform {platform!r}); set "
            "JAX_PLATFORMS=cpu to run on the host CPU on purpose",
            file=sys.stderr,
        )
        sys.exit(3)


import kube_batch_tpu.actions  # noqa: F401
import kube_batch_tpu.plugins  # noqa: F401
from kube_batch_tpu.api import PodPhase, TaskStatus, build_resource_list
from kube_batch_tpu.cache import SchedulerCache
from kube_batch_tpu.framework import close_session, get_action, open_session
from kube_batch_tpu.solver import (
    default_mesh,
    plan_for,
    sharded_step,
    solve_jit,
    solve_plan,
    solve_sharded,
    tensorize,
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

CONFIGS = {
    # name: (tasks, nodes, queues, groups)
    "small": (1_000, 100, 1, 10),
    "medium": (10_000, 1_000, 4, 100),
    "large": (50_000, 5_000, 5, 500),
}

TIERS_ARGS = (
    ["priority", "gang", "conformance"],
    ["drf", "predicates", "proportion", "nodeorder"],
)


def build_cluster(n_tasks, n_nodes, n_queues, n_groups, seed=0):
    rng = np.random.RandomState(seed)
    cache = SchedulerCache(
        binder=FakeBinder(),
        evictor=FakeEvictor(),
        status_updater=FakeStatusUpdater(),
        volume_binder=FakeVolumeBinder(),
    )
    for q in range(n_queues):
        cache.add_queue(build_queue(f"q{q}", weight=q + 1))
    for j in range(n_nodes):
        cache.add_node(build_node(
            f"n{j}", build_resource_list(cpu="32", memory="128Gi", pods=110)
        ))
    per_group = n_tasks // n_groups
    cpus = rng.choice([250, 500, 1000, 2000, 4000], size=n_tasks)
    mems = rng.choice([256, 512, 1024, 4096, 8192], size=n_tasks)
    t = 0
    for g in range(n_groups):
        queue = f"q{g % n_queues}"
        min_member = int(rng.randint(1, per_group + 1))
        cache.add_pod_group(build_pod_group(
            f"pg{g}", namespace="bench", min_member=min_member, queue=queue
        ))
        for i in range(per_group):
            cache.add_pod(build_pod(
                "bench", f"pg{g}-p{i}", "", PodPhase.PENDING,
                build_resource_list(
                    cpu=f"{int(cpus[t])}m", memory=f"{int(mems[t])}Mi"
                ),
                group_name=f"pg{g}",
            ))
            t += 1
    return cache


def bench_greedy(cfg, seed=0, runs=3):
    """Greedy allocate action wall time (full Execute) on a config.

    The sample subproblem is PINNED — fixed seed, fixed config shape —
    and the reported time is the MEDIAN of ``runs`` independent
    executions on freshly built clusters. The previous single-shot
    number swung ~2x between bench rounds (1.17M vs 2.57M extrapolated
    ms, BENCH_r04 vs r05) purely on allocator/GC noise, and it feeds
    greedy_extrapolated_ms, so the swing looked like a baseline change."""
    n_tasks, n_nodes, n_queues, n_groups = CONFIGS[cfg]
    times = []
    placed = 0
    for _ in range(max(1, runs)):
        cache = build_cluster(n_tasks, n_nodes, n_queues, n_groups, seed)
        ssn = open_session(cache, make_tiers(*TIERS_ARGS))
        action, _ = get_action("allocate")
        start = time.perf_counter()
        action.execute(ssn)
        times.append(time.perf_counter() - start)
        placed = len(cache.binder.binds)
        close_session(ssn)
        cache.shutdown()
    times.sort()
    return times[len(times) // 2], placed, n_tasks * n_nodes


def bench_native_greedy(inputs, repeats=2):
    """Measured native (C++) reference-loop baseline on the SAME snapshot
    arrays the TPU solver consumes (csrc/greedy.cpp) — the fair stand-in
    for the reference's compiled Go loop. Returns (seconds, placed) or
    None when no toolchain is available."""
    try:
        from kube_batch_tpu.native import NativeUnavailable, greedy_allocate
    except Exception:
        return None
    solver_in = inputs.unpack() if hasattr(inputs, "unpack") else inputs
    task_req = np.asarray(solver_in.task_req)
    valid = np.asarray(solver_in.task_valid)
    task_req = task_req[valid]
    task_queue = np.asarray(solver_in.task_queue)[valid]
    node_feas = np.asarray(solver_in.node_feas)
    node_idle = np.asarray(solver_in.node_idle)[node_feas]
    node_cap = np.asarray(solver_in.node_cap)[node_feas]
    qd = np.asarray(solver_in.queue_deserved)
    qa = np.asarray(solver_in.queue_allocated)
    eps = np.asarray(solver_in.eps)
    lr = float(np.asarray(solver_in.lr_weight))
    br = float(np.asarray(solver_in.br_weight))
    try:
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            _, placed = greedy_allocate(
                task_req, task_queue, node_idle, node_cap, qd, qa, eps,
                lr, br,
            )
            times.append(time.perf_counter() - t0)
        return min(times), placed
    except NativeUnavailable:
        return None


def bench_tpu(cfg, seed=0, repeats=3):
    """Batched solve on a config: returns (host_snapshot_s, solve_s, placed)."""
    n_tasks, n_nodes, n_queues, n_groups = CONFIGS[cfg]
    cache = build_cluster(n_tasks, n_nodes, n_queues, n_groups, seed)

    t0 = time.perf_counter()
    ssn = open_session(cache, make_tiers(*TIERS_ARGS))
    t_session = time.perf_counter() - t0

    t0 = time.perf_counter()
    inputs, ctx = tensorize(ssn)
    t_snapshot = time.perf_counter() - t0
    from kube_batch_tpu.solver.snapshot import last_tensorize_stats

    sparse_stats = dict(last_tensorize_stats.get("sparse") or {})

    # Compile once, then measure steady-state device latency. Timing
    # includes the device->host fetch of the assignment vector (what a real
    # cycle needs back) so async dispatch cannot flatter the number.
    # With >1 device the node axis is sharded over the mesh (multi-chip
    # scale path); padding + host->device transfer happen ONCE outside the
    # timed loop, exactly like the single-device path's device-resident
    # arrays, so the loop isolates the solve itself.
    import jax

    if ctx.plan.mode != "single":
        step, dev_inputs = sharded_step(inputs, ctx.plan)
    else:
        step, dev_inputs = solve_jit, inputs
    result = jax.block_until_ready(step(dev_inputs))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        result = step(dev_inputs)
        assigned_host = np.asarray(result.assigned)
        times.append(time.perf_counter() - t0)
    solve_s = min(times)
    placed = int((assigned_host >= 0).sum())
    rounds = int(result.rounds)
    if result.refills is not None:
        sparse_stats["jax"] = {
            "refill_tasks": int(result.refills),
            "refill_rounds": int(result.stages),
        }
    close_session(ssn)
    return {
        "session_s": t_session,
        "snapshot_s": t_snapshot,
        "solve_s": solve_s,
        "placed": placed,
        "rounds": rounds,
        "work": n_tasks * n_nodes,
        "inputs": inputs,
        # Candidate-selection stats of this snapshot (solver/topk.py).
        "sparse": sparse_stats,
        # NumPy-backed SolverInputs for the native baselines — feeding
        # them the device PackedInputs would bill ~140 ms of eager JAX
        # slicing to a C++ loop (r4 delta-profile lesson).
        "host_inputs": ctx.host_inputs,
        # Every task is still Pending (the solve was never applied):
        # bench_cycle reuses this cluster instead of rebuilding it.
        "cache": cache,
    }


def bench_cycle(cfg, seed=0, cache=None, trace_path=None,
                measure_obs=False):
    """Full scheduling cycles through the production allocate_tpu action —
    the number BASELINE.md's <100 ms target is really about (the reference
    hot path is the whole runOnce, scheduler.go:88-103, not the inner
    kernel). Four scenarios:

    - cold:   first cycle on a fresh full-scale pending burst;
    - steady: the very next cycle — every placed job/node changed in
      cold, so the COW snapshot pool re-clones the world (its worst
      case);
    - idle:   one more unchanged cycle — nothing dirty, the pool and
      early-exit tensorize shine (the common 1 Hz case);
    - delta:  a ~1% batch of new gangs arrives, next cycle.

    Each cycle reports open/tensorize/solve/apply/epilogue/close phases
    (from actions.allocate_tpu.last_stats) plus the e2e wall time.
    Attribution flags ride along per cycle: ``apply_handlers_batched``
    / ``apply_job_groups_hint`` (aggregate plugin handler dispatch) and
    ``tensorize_incremental`` / ``tensorize_dirty_nodes`` /
    ``tensorize_full_reason`` (incremental snapshot patching and the
    row counts it actually touched).

    With ``trace_path`` the span tracer records the four cycles and
    exports one Chrome trace-event file (the acceptance artifact: the
    cold cycle's solve/apply overlap shows as concurrent tracks in
    Perfetto). ``measure_obs`` appends an ``obs`` section: tracer
    overhead measured on/off over repeated idle-shape cycles at this
    config, plus span counts per cycle.
    """
    from kube_batch_tpu.actions import allocate_tpu as _atpu
    from kube_batch_tpu.obs.tracer import TRACER

    n_tasks, n_nodes, n_queues, n_groups = CONFIGS[cfg]
    if cache is None:
        # Callers that already built this config's cluster (bench_tpu
        # leaves every task pending) pass it in — a second 50k build
        # costs ~2 min of the driver's deadline.
        cache = build_cluster(n_tasks, n_nodes, n_queues, n_groups, seed)
    else:
        # The passed cache saw a prior session open + tensorize, so the
        # COW pool and the per-pod tensorize caches are warm; a real
        # pending burst arrives with fresh pods. Re-cold BOTH so the
        # cold cycle measures burst-arrival cost: dirty every job
        # (forces re-clone; nodes legitimately stay reused — pod
        # arrivals do not touch them) and drop the per-pod predicate
        # caches via the plugin-owned helper (the attr list lives there).
        from kube_batch_tpu.plugins.predicates import clear_pod_caches

        for job in cache.jobs.values():
            job._ver += 1
            clear_pod_caches(t.pod for t in job.tasks.values())
    action, _ = get_action("allocate_tpu")

    cycle_counter = [0]

    def one_cycle():
        # Same GC deferral as the production Scheduler.run_once: the
        # collection runs after t_close, in what would be think-time.
        from kube_batch_tpu.obs import span
        from kube_batch_tpu.utils import deferred_gc

        TRACER.begin_cycle(cycle_counter[0])
        cycle_counter[0] += 1
        t_start = time.perf_counter()
        with span("cycle"), deferred_gc():
            ssn = open_session(cache, make_tiers(*TIERS_ARGS))
            t_open = time.perf_counter()
            action.execute(ssn)
            t_exec = time.perf_counter()
            close_session(ssn)
            t_close = time.perf_counter()
        out = {
            "open_ms": round((t_open - t_start) * 1e3, 1),
            "action_ms": round((t_exec - t_open) * 1e3, 1),
            "close_ms": round((t_close - t_exec) * 1e3, 1),
            # 3 decimals: the obs section's tracer-overhead comparison
            # needs sub-0.1ms resolution on idle cycles.
            "cycle_ms": round((t_close - t_start) * 1e3, 3),
            # close_session now runs under its own (nested) deferred_gc
            # guard, so a generational collection can never land inside
            # the close and jitter close_ms (r5: 2.1 -> 17.7 ms spikes).
            "close_gc_deferred": True,
        }
        for k, v in _atpu.last_stats.items():
            out[k] = round(v, 1) if isinstance(v, float) else v
        # Drain async bind side effects outside the timed region so the
        # next cycle's timings aren't polluted by this cycle's backlog.
        # A failed drain makes the next cycle's numbers suspect — record it.
        out["drain_ok"] = cache.wait_for_side_effects(timeout=120.0)
        return out

    tracing = trace_path is not None
    if tracing:
        TRACER.reset()
        TRACER.enable()

    def spans_since(mark):
        return TRACER.spans_recorded - mark

    mark = TRACER.spans_recorded
    cold = one_cycle()
    cold["spans"] = spans_since(mark)
    mark = TRACER.spans_recorded
    steady = one_cycle()
    steady["spans"] = spans_since(mark)
    mark = TRACER.spans_recorded
    idle = one_cycle()
    idle["spans"] = spans_since(mark)
    mark = TRACER.spans_recorded

    # ~1% new gangs arrive, drawn from the same shape mix as build_cluster.
    rng = np.random.RandomState(seed + 1)
    new_groups = max(1, n_groups // 100)
    per_group = n_tasks // n_groups

    def add_burst(prefix, groups=None):
        for g in range(groups if groups is not None else new_groups):
            name = f"{prefix}{g}"
            cache.add_pod_group(build_pod_group(
                name, namespace="bench",
                min_member=int(rng.randint(1, per_group + 1)),
                queue=f"q{g % n_queues}",
            ))
            for i in range(per_group):
                cache.add_pod(build_pod(
                    "bench", f"{name}-p{i}", "", PodPhase.PENDING,
                    build_resource_list(
                        cpu=f"{int(rng.choice([250, 500, 1000, 2000, 4000]))}m",
                        memory=f"{int(rng.choice([256, 512, 1024, 4096, 8192]))}Mi",
                    ),
                    group_name=name,
                ))

    add_burst("pgd")
    delta = one_cycle()
    delta["spans"] = spans_since(mark)

    # Degraded-mode floor: one more same-size burst cycle with the
    # fault-containment breaker PINNED open (solver/containment.py) —
    # the whole cycle runs on the native floor with zero device
    # dispatch, exactly what an open breaker costs in production.
    # bench_compare tracks this point like any headline number, so the
    # floor's latency cannot silently regress.
    from kube_batch_tpu.solver import containment

    add_burst("pgx")
    mark = TRACER.spans_recorded
    containment.BREAKER.pin_open("bench-degraded")
    try:
        degraded = one_cycle()
    finally:
        containment.BREAKER.unpin()
    degraded["spans"] = spans_since(mark)

    # --- steady_warm: the warm-started 1%-churn steady state ---------
    # Each round: a ~1% gang burst arrives, the next cycle places it
    # through the warm-start plan (solver/warm.py) — incremental
    # tensorize, selection-cache reuse, residual capacities. The cycle
    # AFTER the last burst absorbs its placement wave as a warm no-op.
    # Reported per-round + median; `warm_outcome`/`tensorize_incremental`
    # are the acceptance flags (warm must ENGAGE, the placement wave
    # must never trip a full rebuild).
    one_cycle()  # settle the degraded round's wave; re-warms the state
    warm_rounds = []
    for r in range(5):
        add_burst(f"pgw{r}_")
        warm_rounds.append(one_cycle())
    absorb = one_cycle()
    warm_med = sorted(
        r["cycle_ms"] for r in warm_rounds
    )[len(warm_rounds) // 2]
    steady_warm = {
        "cycle_ms": round(warm_med, 3),
        "rounds_ms": [round(r["cycle_ms"], 3) for r in warm_rounds],
        "warm_outcome": warm_rounds[-1].get("warm_outcome"),
        "warm_engaged": all(
            r.get("warm_outcome") in ("solve", "noop")
            for r in warm_rounds
        ),
        "tensorize_incremental": all(
            r.get("tensorize_incremental", False) for r in warm_rounds
        ),
        "tensorize_wave_patched": warm_rounds[-1].get(
            "tensorize_wave_patched"
        ),
        "placed_per_round": [r.get("placed", 0) for r in warm_rounds],
        "sparse_engaged": warm_rounds[-1].get("sparse_engaged"),
        "absorb_cycle_ms": absorb["cycle_ms"],
        "absorb_warm_outcome": absorb.get("warm_outcome"),
        "open_ms": warm_rounds[-1].get("open_ms"),
        "action_ms": warm_rounds[-1].get("action_ms"),
        "close_ms": warm_rounds[-1].get("close_ms"),
        "tensorize_ms": warm_rounds[-1].get("tensorize_ms"),
        "solve_ms": warm_rounds[-1].get("solve_ms"),
        "apply_ms": warm_rounds[-1].get("apply_ms"),
    }

    # --- micro_cycle: arrival-to-placement latency ------------------
    # The event-driven fast path (Scheduler.run_micro semantics: full
    # session, micro flag, warm-path-only placement) measured from the
    # moment a burst lands in the mirror to its placements applied, at
    # ~0.1% and ~1% churn.
    def micro_round(prefix, burst_tasks):
        groups = max(1, burst_tasks // per_group)
        add_burst(prefix, groups=groups)
        from kube_batch_tpu.utils import deferred_gc as _dgc

        t0 = time.perf_counter()
        with _dgc():
            ssn = open_session(cache, make_tiers(*TIERS_ARGS))
            ssn.micro_cycle = True
            action.execute(ssn)
            close_session(ssn)
            # Stop the clock INSIDE the guard: the deferred collection
            # at guard exit belongs to think-time, exactly as in
            # one_cycle()/Scheduler.run_once.
            ms = (time.perf_counter() - t0) * 1e3
        stats = dict(_atpu.last_stats)
        cache.wait_for_side_effects(timeout=120.0)
        one_cycle()  # absorb the wave before the next round
        return {
            "arrival_to_placement_ms": round(ms, 3),
            "burst_tasks": groups * per_group,
            "placed": stats.get("placed", 0),
            "warm_outcome": stats.get("warm_outcome"),
            "deferred": stats.get("micro_deferred"),
        }

    micro_cycle = {
        "burst_0p1": micro_round("pgm1_", max(1, n_tasks // 1000)),
        "burst_1p": micro_round("pgm2_", max(1, n_tasks // 100)),
    }

    out = {"cold": cold, "steady": steady, "idle": idle, "delta": delta,
           "degraded": degraded, "steady_warm": steady_warm,
           "micro_cycle": micro_cycle}
    if tracing:
        out["trace_path"] = TRACER.export(trace_path)
        out["trace_spans"] = TRACER.spans_recorded
        out["trace_spans_dropped"] = TRACER.dropped
        TRACER.disable()
    if measure_obs:
        out["obs"] = bench_obs(one_cycle, cache=cache)
        # Quality scorecard cost against the same (still-live) benched
        # cache; amortized against the measured warm steady cycle.
        out["quality"] = bench_quality(
            cache, steady_ms=steady_warm.get("cycle_ms")
        )
    cache.shutdown()
    return out


def bench_obs(one_cycle, runs=7, cache=None):
    """Tracer + telemetry overhead at the benched shape.

    Two tracer measurements, because cycle-to-cycle wall-time variance
    at 50k scale (GC, allocator state) is orders of magnitude larger
    than the microseconds a handful of spans cost:

    - **pinned overhead** = measured per-span cost (tight microbench of
      the enabled span path) x spans recorded per cycle, as a fraction
      of the tracer-OFF cycle median — deterministic, this is the
      number the <1%-of-an-idle-cycle budget is checked against;
    - **a/b delta** = interleaved off/on cycle medians, reported as
      corroborating evidence (expected to sit inside run noise).

    Plus the telemetry enabled-path cost: the full per-cycle
    ``observe_scheduler_cycle`` (flight-record extraction, watermark
    probes, the amortized fairness probe against the REAL benched
    cache) timed over enough cycles to include window rolls and
    fairness refreshes — pinned against the same <1% budget.
    """
    from kube_batch_tpu.obs.tracer import TRACER

    was_enabled = TRACER.enabled
    TRACER.disable()
    one_cycle()  # settle after the caller's last cycle
    off, on = [], []
    span_count = 0
    # Interleaved a/b so slow drift (cache warmth, GC pressure) hits
    # both arms equally.
    for _ in range(runs):
        TRACER.disable()
        off.append(one_cycle()["cycle_ms"])
        TRACER.enable()
        mark = TRACER.spans_recorded
        on.append(one_cycle()["cycle_ms"])
        span_count += TRACER.spans_recorded - mark
    off.sort()
    on.sort()
    off_ms = off[len(off) // 2]
    on_ms = on[len(on) // 2]
    spans_per_cycle = span_count / float(runs)

    # Deterministic per-span cost of the ENABLED recording path.
    probe_n = 20_000
    TRACER.reset()
    TRACER.enable()
    t0 = time.perf_counter()
    for _ in range(probe_n):
        with TRACER.span("obs-probe"):
            pass
    span_cost_us = (time.perf_counter() - t0) / probe_n * 1e6
    TRACER.reset()
    if not was_enabled:
        TRACER.disable()  # and with it the GC hook

    # Telemetry enabled-path cost: a scratch Telemetry instance (the
    # global one must not absorb bench samples) fed a representative
    # flight record + the real cache, 1024 cycles — covering 16 window
    # rolls, 16 expensive-probe/fairness samples (both on the 64-cycle
    # tier), and a node-total refresh, so the amortized probes are
    # priced in, not dodged.
    from kube_batch_tpu.obs.telemetry import Telemetry

    scratch = Telemetry(window_cycles=64, max_windows=64,
                        raw_capacity=128)
    fake_rec = {
        "e2e_ms": off_ms,
        "phases_ms": {
            "open_session": 2.0,
            "action:allocate_tpu": off_ms * 0.8,
            "close_session": 2.0,
        },
        "solver": {"placed": 0, "tasks": 0, "rounds": 1},
    }
    telem_n = 1024
    t0 = time.perf_counter()
    for _ in range(telem_n):
        scratch.observe_scheduler_cycle(fake_rec, cache=cache)
    telemetry_cost_us = (time.perf_counter() - t0) / telem_n * 1e6

    # Placement-ledger + decision-audit enabled-path cost, pinned
    # against the same <1%-of-an-idle-cycle budget: the per-pod full
    # lifecycle (arrival→placed→dispatched→applied, incl. the
    # Prometheus histogram observes), the per-record audit append, and
    # the per-cycle fixed cost an IDLE cycle actually pays
    # (begin_cycle + the telemetry p99 probe over populated sketches).
    from kube_batch_tpu.obs.latency import AuditLog, PlacementLedger

    scratch_ledger = PlacementLedger()
    lat_n = 5_000
    t0 = time.perf_counter()
    for i in range(lat_n):
        uid = f"obs-lat-{i}"
        job = f"obs-job-{i % 50}"
        scratch_ledger.note_arrival(uid, uid, job)
        scratch_ledger.note_placed(((uid, job),), {job: "q0"})
        scratch_ledger.note_dispatched((uid,))
        scratch_ledger.note_applied(uid)
    latency_pod_cost_us = (time.perf_counter() - t0) / lat_n * 1e6

    scratch_audit = AuditLog(capacity=1024)
    audit_n = 5_000
    t0 = time.perf_counter()
    for i in range(audit_n):
        scratch_audit.append({
            "action": "placed", "job": f"obs-job-{i % 50}",
            "queue": "q0", "count": 1, "kind": "periodic",
            "backend": "native", "warm": "solve", "degraded": False,
        })
    audit_append_cost_us = (time.perf_counter() - t0) / audit_n * 1e6

    cyc_n = 2_000
    t0 = time.perf_counter()
    for i in range(cyc_n):
        scratch_ledger.begin_cycle(i)
        scratch_ledger.telemetry_sample()
    latency_cycle_cost_us = (time.perf_counter() - t0) / cyc_n * 1e6

    overhead_ms = spans_per_cycle * span_cost_us / 1e3
    delta_ms = max(0.0, on_ms - off_ms)
    return {
        "latency_pod_cost_us": round(latency_pod_cost_us, 2),
        "audit_append_cost_us": round(audit_append_cost_us, 2),
        "latency_cycle_cost_us": round(latency_cycle_cost_us, 2),
        "latency_overhead_pct": (
            round(latency_cycle_cost_us / 1e3 / off_ms * 100.0, 3)
            if off_ms else 0.0
        ),
        "telemetry_cost_us": round(telemetry_cost_us, 2),
        "telemetry_overhead_pct": (
            round(telemetry_cost_us / 1e3 / off_ms * 100.0, 3)
            if off_ms else 0.0
        ),
        "idle_cycle_off_ms": round(off_ms, 3),
        "idle_cycle_on_ms": round(on_ms, 3),
        "spans_per_cycle": round(spans_per_cycle, 1),
        "span_cost_us": round(span_cost_us, 2),
        "tracer_overhead_ms": round(overhead_ms, 4),
        "tracer_overhead_pct": (
            round(overhead_ms / off_ms * 100.0, 3) if off_ms else 0.0
        ),
        "ab_delta_ms": round(delta_ms, 3),
        "ab_delta_pct": (
            round(delta_ms / off_ms * 100.0, 2) if off_ms else 0.0
        ),
        "runs": runs,
    }


def bench_quality(cache, steady_ms=None, repeats=5):
    """Placement-quality scorecard cost at the benched shape
    (obs/quality.py): a full ``compute_scorecard`` against the REAL
    benched cache (50k tasks x 5k nodes on the large config), median
    of ``repeats`` with the memo state warm, plus the amortized
    production overhead — per-card cost divided by the
    KBT_QUALITY_EVERY cadence, as a percentage of the measured warm
    steady cycle (the <1% budget the design doc quotes). The benched
    snapshot's headline density/fairness numbers ride along, so the
    committed rounds carry a packing-quality trend next to the latency
    trend."""
    from kube_batch_tpu.obs.quality import (
        DEFAULT_QUALITY_EVERY,
        compute_scorecard,
    )

    state = {}
    card = compute_scorecard(cache, state=state)  # cold: builds memos
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        card = compute_scorecard(cache, state=state)
        times.append(time.perf_counter() - t0)
    times.sort()
    card_ms = times[len(times) // 2] * 1e3
    every = DEFAULT_QUALITY_EVERY
    out = {
        "card_ms": round(card_ms, 3),
        "every": every,
        "amortized_ms": round(card_ms / every, 4),
        "nodes": card["nodes"],
        "queues": card["queues"],
        "density_dom": card["density_dom"],
        "density": card["density"],
        "fairness_jain": card["fairness"]["jain"],
        "emptiable_frac": card["frag"]["emptiable_frac"],
    }
    if steady_ms:
        out["overhead_pct_of_steady"] = round(
            100.0 * (card_ms / every) / steady_ms, 3
        )
    return out


def bench_arrival_latency(quick=False, seed=23):
    """Stage-decomposed arrival→bind placement-latency percentiles
    under the high-arrival sim mixes (the ROADMAP item 2 SLI section,
    obs/latency.py): three seeded deterministic-simulator runs —
    ~0.1%-of-the-50k-headline sustained arrivals (with micro cycles
    engaged), ~1% sustained, and a 10k+-pods-per-virtual-second burst
    profile — each reporting the ledger's p50/p95/p99 per stage and
    per (queue, cycle kind).

    Latencies are VIRTUAL seconds off the sim clock, so the values are
    machine-independent and exactly reproducible: bench_compare tracks
    them with ratio semantics (no canary normalization) — a p99 climb
    here is a scheduling-delay regression, not machine drift. (On the
    virtual timeline dispatch/bind collapse to 0 — side effects settle
    within the cycle — and the solve stage carries the real solve wall
    time; the Prometheus histogram and the obs section carry the
    real-time stage split for production cycles.)"""
    from kube_batch_tpu.native import native_available
    from kube_batch_tpu.obs.latency import LEDGER
    from kube_batch_tpu.sim import SimConfig, WorkloadSpec
    from kube_batch_tpu.sim.harness import run_sim

    backend = "native" if native_available() else "auto"

    def mix(cycles, micro_every=0, period=1.0, nodes=64, **spec_kw):
        spec = WorkloadSpec(
            nodes=nodes, node_cpu_m=16000, node_mem_mi=32768,
            duration_cycles=(2, 6), **spec_kw,
        )
        report, records = run_sim(SimConfig(
            cycles=cycles, seed=seed, workload=spec, backend=backend,
            check_invariants=False, micro_every=micro_every,
            period=period,
        ))
        lat = report.latency or {}
        stages = LEDGER.stage_percentiles()
        # Carried-backlog depth off the trace records (replay-stable):
        # congestion verdicts need the SHAPE — a keeping-up scheduler's
        # series plateaus, a falling-behind one climbs monotonically.
        carried = [
            (r.get("stats") or {}).get("carried", 0)
            for r in records if r.get("type") == "cycle"
        ]
        step = max(1, len(carried) // 64)
        return {
            "cycles": cycles,
            "placements": report.placements,
            "carried_depth_max": max(carried) if carried else 0,
            "carried_depth_end": carried[-1] if carried else 0,
            "carried_depth_series": carried[::step],
            "stamped": lat.get("stamped", 0),
            "applied": lat.get("applied", 0),
            "queue_p99_s": lat.get("queue_p99_s", {}),
            "total_p99_s": (stages.get("total") or {}).get("p99_s"),
            "queue_wait_p99_s": (
                (stages.get("queue_wait") or {}).get("p99_s")
            ),
            "gang_total_p99_s": (
                (stages.get("gang_total") or {}).get("p99_s")
            ),
            "stages": stages,
            "by_queue_kind": LEDGER.percentiles(),
            "audit_records": report.audit_records,
        }

    # Mix sizes are pod-arrival equivalents of the 50k-pod headline
    # (avg gang ≈ 2.45 pods): 0.1% ≈ 50 pods/cycle sustained, 1% ≈
    # 500 sustained, burst ≈ 10.3k pods landing in ONE virtual second
    # (the 10k+ arrivals/s-equivalent spike), draining over the rest
    # of the run. Quick mode scales ~10x down — the section's shape
    # (keys, stages) is identical, only the committed large rounds'
    # numbers are the tracked trend.
    scale = 10 if quick else 1
    return {
        "sustained_0p1": mix(
            120 // (2 if quick else 1), micro_every=2,
            arrival_rate=20 / scale,
            arrival_profile="sustained", max_jobs_in_flight=512,
        ),
        "sustained_1p": mix(
            40 // (2 if quick else 1), arrival_rate=200 / scale,
            arrival_profile="sustained", max_jobs_in_flight=2048,
        ),
        "burst": mix(
            30 // (2 if quick else 1), arrival_rate=2,
            arrival_profile="burst",
            burst_every=50, burst_size=4200 // scale,
            max_jobs_in_flight=20000,
        ),
        # Congested micro steady state (r17): sim ticks ARE the micro
        # coalescing windows (period = 5 ms virtual), the periodic
        # cycle demoted to every 8th tick. sustained: 20 jobs/tick ×
        # ~2.45 pods / 5 ms ≈ 10k pod-arrivals per virtual second,
        # continuously — the p99 gate (< 10 ms, i.e. placed in the
        # arrival tick or the next) only holds if the subset-solve
        # micro path keeps pace without waiting on periodic cycles.
        # burst: 400-job storms every 100 ticks against HALF the
        # cluster (32 nodes) so each storm over-subscribes capacity —
        # a real carried backlog forms, the rank-stable subset solves
        # rotate through it, and the depth series must drain back to 0
        # between storms (carried_depth_end is a bench_compare row).
        "congested_10k": mix(
            400 // (4 if quick else 1), micro_every=8, period=0.005,
            arrival_rate=20 / scale,
            arrival_profile="sustained", max_jobs_in_flight=4096,
        ),
        "congested_burst": mix(
            300 // (3 if quick else 1), micro_every=8, period=0.005,
            nodes=32, arrival_rate=4,
            arrival_profile="burst", burst_every=100,
            burst_size=400 // scale, max_jobs_in_flight=8192,
        ),
    }


def bench_serving(quick=False, seed=29):
    """Serving-SLO section (doc/design/serving.md): the congested micro
    steady-state mix (the 50k×5k headline's pod-arrival equivalent,
    10k pod-arrivals per virtual second) with a serving deployment
    stream layered on top — annotated SLO replicas (50 ms
    arrival→bind target), replica churn, a 20% spot slice and two
    topology tiers across the node pool. Reports the latency ledger's
    per-class attainment/violations/budget burn plus the per-class
    arrival→bind p99 (serving queue vs the batch queues).

    Virtual-time values (machine-independent, exactly reproducible):
    bench_compare tracks attainment with a higher-is-better floor and
    the p99s with ratio semantics — an attainment dip or a serving-p99
    climb is a scheduling regression, not machine drift."""
    from kube_batch_tpu.native import native_available
    from kube_batch_tpu.obs.latency import LEDGER
    from kube_batch_tpu.sim import SimConfig, WorkloadSpec
    from kube_batch_tpu.sim.harness import run_sim

    backend = "native" if native_available() else "auto"
    scale = 10 if quick else 1
    cycles = 400 // (4 if quick else 1)
    spec = WorkloadSpec(
        nodes=64, node_cpu_m=16000, node_mem_mi=32768,
        duration_cycles=(2, 6),
        arrival_rate=20 / scale, arrival_profile="sustained",
        max_jobs_in_flight=4096,
        serving_rate=2 / scale, serving_slo_s=0.05,
        serving_churn=0.05, reserved_frac=0.8, node_tiers=2,
    )
    report, _records = run_sim(SimConfig(
        cycles=cycles, seed=seed, workload=spec, backend=backend,
        check_invariants=False, micro_every=8, period=0.005,
    ))
    lat = report.latency or {}
    serving = lat.get("serving") or {}
    # Per-class arrival→bind p99 off the per-queue sketches (serving
    # jobs land on the dedicated "serving" queue, batch on the rest).
    # 0.0 is the expected healthy value at this shape — every placement
    # lands inside its arrival tick on the virtual clock — so the
    # bench_compare ratio rows gate any climb OFF zero.
    per_queue = {"serving": 0.0, "batch": 0.0}
    for queue, kinds in LEDGER.percentiles().items():
        cls = "serving" if queue == "serving" else "batch"
        for stages_of_kind in kinds.values():
            total = stages_of_kind.get("total") or {}
            p99 = total.get("p99_s")
            if p99 is not None and p99 > per_queue[cls]:
                per_queue[cls] = p99
    stages = LEDGER.stage_percentiles()
    return {
        "cycles": cycles,
        "placements": report.placements,
        "attainment_pct": serving.get("attainment_pct"),
        "violations": serving.get("violations"),
        "budget_burn": serving.get("budget_burn"),
        "classes": serving.get("classes", {}),
        "serving_bind_p99_s": per_queue["serving"],
        "batch_bind_p99_s": per_queue["batch"],
        "total_p99_s": (stages.get("total") or {}).get("p99_s"),
    }


def bench_device_cache(cfg="small", seed=0):
    """Device-resident snapshot pack across cold/steady/delta cycles:
    the per-field reuse/patch/upload stats (solver/device_cache.py) for
    the bench JSON. Always exercises the DEVICE pack path (tensorize
    device=True) regardless of how allocate_tpu routes the solve, so
    even a CPU-fallback artifact carries patched-row/bytes-shipped
    evidence for the code in the tree; on a real accelerator run the
    same stats additionally land in every cycle's ``device_*`` keys."""
    from kube_batch_tpu.solver.device_cache import last_pack_stats

    n_tasks, n_nodes, n_queues, n_groups = CONFIGS[cfg]
    cache = build_cluster(n_tasks, n_nodes, n_queues, n_groups, seed)
    tiers = make_tiers(*TIERS_ARGS)
    out = {"config": cfg}

    def pack_summary(t_ms):
        keys = ("uploads", "patches", "reuses", "rows_patched",
                "bytes_shipped", "bytes_total")
        s = {k: last_pack_stats.get(k, 0) for k in keys}
        s["tensorize_ms"] = round(t_ms, 1)
        return s

    def one(label, ssn):
        t0 = time.perf_counter()
        inputs, _ctx = tensorize(ssn)
        out[label] = pack_summary((time.perf_counter() - t0) * 1e3)
        return inputs

    ssn = open_session(cache, tiers)
    one("cold", ssn)      # every field uploads (cold cache)
    one("steady", ssn)    # nothing changed: zero uploads, zero bytes
    # Small churn: allocate ONE whole gang through the session (a full
    # gang is JobReady, so its binds actually reach the cache mirror —
    # partial allocations are session-only and would vanish at the next
    # snapshot), packed onto a couple of nodes so the next pack patches
    # a couple of node rows.
    job = min(
        (j for j in ssn.jobs.values()
         if j.task_status_index.get(TaskStatus.PENDING)),
        key=lambda j: (len(j.task_status_index[TaskStatus.PENDING]),
                       j.uid),
    )
    gang = sorted(
        job.task_status_index[TaskStatus.PENDING].values(),
        key=lambda t: t.uid,
    )
    nodes = sorted(ssn.nodes)[: max(8, n_nodes // 10)]
    ssn.allocate_batch([
        (t, nodes[i % len(nodes)]) for i, t in enumerate(gang)
    ])
    cache.wait_for_side_effects()
    cache.wait_for_bookkeeping()
    close_session(ssn)
    ssn = open_session(cache, tiers)
    one("delta", ssn)     # dirty node rows patch; untouched fields reuse
    close_session(ssn)
    cache.shutdown()
    return out


def _select_scale_ab(mask, task_req, node_idle, eps, k, seed=0):
    """Selection device-vs-host A/B at a scale point. Four timed runs:

    - ``select_ms_host``: host NumPy full pass (cold — what every
      committed round before the device engine measured as
      ``select_ms``);
    - ``select_ms_device``: device-resident full pass, cold — engine
      allocation + every key row built on device + top-K extraction
      (includes first-use jit compiles, like any cold jax number here);
    - ``select_ms_host_warm`` / ``select_ms_device_warm``: the same
      ~1% node churn pushed through both paths with their cross-cycle
      caches warm — the steady-state per-cycle cost a scheduler
      actually pays (both recompute only churned columns);
    - ``select_device_parity``: 1 iff the device slabs were bit-equal
      to the host slabs on BOTH the cold and the churned-warm run.

    ``select_ms`` (the headline the committed rounds track) is the
    steady-state cost of the engaged path: the churned-warm device
    pass when the device path engaged (the engine and jits live for
    the process — cold is a once-per-process cost kept in
    ``select_ms_device``), else the host cold pass (``select_path``
    records which). Returns ``(keys, host_cold_cs)`` — the host
    CandidateSet feeds the solve stage unchanged."""
    from kube_batch_tpu.solver import select_device
    from kube_batch_tpu.solver.topk import select_candidates

    N = node_idle.shape[0]
    zeros = np.zeros_like(node_idle)
    zc = np.zeros(N, np.int32)
    ids = np.arange(N, dtype=np.int64)
    vers = np.zeros(N, np.int64)

    class _Holder:  # anchor for the cross-cycle selection caches
        pass

    # Separate holders per path: the host leg's _SelectionCache rows
    # are GBs at XL shapes and the device path never reads them — one
    # shared holder would just couple the legs through the allocator.
    holder_host = _Holder()
    holder_dev = _Holder()

    def run(idle, vers_, state, holder):
        t0 = time.perf_counter()
        cs_ = select_candidates(
            mask, {}, task_req, task_req, idle, idle, zeros, zc, zc,
            eps, 1.0, 1.0, k, cache_holder=holder,
            node_fp=(ids, vers_, None), device_state=state,
        )
        return round((time.perf_counter() - t0) * 1e3, 1), cs_

    host_ms, cs = run(node_idle, vers, None, holder_host)
    out = {"select_ms": host_ms, "select_ms_host": host_ms,
           "select_path": "host"}
    if cs is None or not select_device.device_select_enabled():
        if cs is not None:
            out["select_path"] = "host:env-disabled"
        return out, cs

    state = select_device.standalone_state(
        node_idle, node_idle, zc, zc, mask.node_ok, mask.group_rows
    )
    dev_ms, dev_cs = run(node_idle, vers, state, holder_dev)
    if dev_cs is None or dev_cs.stats.get("select_path") != "device":
        out["select_path"] = (
            dev_cs.stats.get("select_path", "host")
            if dev_cs is not None else "host"
        )
        return out, cs
    parity = int(
        (dev_cs.cand_idx == cs.cand_idx).all()
        and (dev_cs.cand_info == cs.cand_info).all()
        and (dev_cs.task_cand == cs.task_cand).all()
    )

    # Churned warm cycle: ~1% of nodes lose idle capacity. Production
    # re-places the node stacks through device_cache.pack_partial;
    # standalone mode re-uploads them and carries the engine (resident
    # key matrix + row digests) across, which is the same residency
    # contract.
    rng = np.random.RandomState(seed + 1)
    churn = rng.choice(N, size=max(N // 100, 1), replace=False)
    idle2 = node_idle.copy()
    idle2[churn] = np.maximum(idle2[churn] - 500.0, 0.0)
    vers2 = vers.copy()
    vers2[churn] += 1
    state2 = select_device.standalone_state(
        idle2, idle2, zc, zc, mask.node_ok, mask.group_rows
    )
    state2._engine = state.engine()
    # Device warm before host warm: the warm device pass is the
    # HEADLINE number, and on a burst-throttled single-core box the
    # last leg of a long process pays decayed CPU — the order must not
    # systematically tax the number the committed rounds track.
    dev_warm_ms, dev_warm_cs = run(idle2, vers2, state2, holder_dev)
    host_warm_ms, host_warm_cs = run(idle2, vers2, None, holder_host)
    if (
        host_warm_cs is not None and dev_warm_cs is not None
        and dev_warm_cs.stats.get("select_path") == "device"
    ):
        parity = int(parity and (
            (dev_warm_cs.cand_idx == host_warm_cs.cand_idx).all()
            and (dev_warm_cs.cand_info == host_warm_cs.cand_info).all()
        ))
        out.update(
            select_ms_host_warm=host_warm_ms,
            select_ms_device_warm=dev_warm_ms,
            sel_cache_hits_warm=int(
                dev_warm_cs.stats.get("sel_cache_hits", 0)
            ),
        )
    # Headline = the steady-state per-cycle cost of the engaged path:
    # selection runs EVERY cycle against a process-lifetime engine, so
    # the churned-warm device pass is what a scheduler pays; the cold
    # pass (engine build + first-use jit compiles, once per process)
    # stays reported as select_ms_device. The speedup ratio divides
    # the committed-history select_ms semantic (host cold full pass)
    # by the new steady-state headline.
    steady_ms = out.get("select_ms_device_warm", dev_ms)
    out.update(
        select_ms=steady_ms,
        select_ms_device=dev_ms,
        select_path="device",
        select_device_parity=parity,
        select_device_speedup=round(host_ms / max(steady_ms, 1e-6), 1),
    )
    return out, cs


def bench_sparse_scale(shape="200000x20000", seed=0, wide_mix=False):
    """Sparse-only scale point: shapes where the DENSE solver is
    arithmetically infeasible — at 200k x 20k one [T, N] f32 score
    matrix is 16 GB, at 1M x 100k it is 400 GB (and the solver
    materializes mask + score + key per round), so there is nothing to
    A/B against; the point of this benchmark is that a cycle completes
    AT ALL.

    Solver inputs are built synthetically at the array level: a 200k-pod
    cache/session build measures Python object churn for minutes and
    multiple GB before the solver ever runs, while the solver consumes
    identical columnar arrays either way (the 50k headline config covers
    the full-pipeline path). Candidate selection runs the REAL topk pass
    — A/B'd device-vs-host with a bit-equality check and a churned-warm
    leg (see :func:`_select_scale_ab`) — and the solve runs the REAL
    sparse backend (native when available, else the jitted JAX sparse
    kernels).

    ``wide_mix`` draws requests from a 64x32-value grid instead of the
    5x5 one (the 1M x 100k point): a million-pod cluster has thousands
    of distinct pod shapes, and class diversity is what sizes the slab
    union — with 25 classes x K=64 only 1 600 nodes are ever candidates
    and the refill stage would drain the other ~97% of tasks at full-N
    cost, which is a degenerate workload, not a scale measurement. The
    200k point keeps the original mix so its committed numbers stay
    comparable."""
    from kube_batch_tpu.solver.kernels import SolverInputs
    from kube_batch_tpu.solver.masks import CombinedMask

    T, N = (int(x) for x in shape.lower().split("x"))
    rng = np.random.RandomState(seed)
    R = 2
    if wide_mix:
        # ~66% cluster utilisation at 1M x 100k (32-cpu/128Gi nodes):
        # the scale point measures solver throughput, not a thundering
        # -herd overload (that regime is the sim's job).
        cpu_mix = np.linspace(250, 4000, 64).round()
        mem_mix = np.linspace(256, 16384, 32).round()
    else:
        cpu_mix = [250, 500, 1000, 2000, 4000]
        mem_mix = [256, 512, 1024, 4096, 8192]
    task_req = np.c_[
        rng.choice(cpu_mix, T),
        rng.choice(mem_mix, T),
    ].astype(np.float32)
    node_idle = np.tile(
        np.asarray([32000.0, 128 * 1024.0], np.float32), (N, 1)
    )
    eps = np.asarray([10.0, 10.0], np.float32)
    mask = CombinedMask(
        node_ok=np.ones(N, bool),
        task_group=np.zeros(T, np.int32),
        group_rows=np.ones((1, N), bool),
        pair_idx=np.zeros((0,), np.int32),
        pair_rows=np.zeros((0, N), bool),
    )
    plan = solve_plan(T, N, None)
    k = plan.k if plan.sparse else 64
    sel, cs = _select_scale_ab(mask, task_req, node_idle, eps, k, seed)
    out = {
        "shape": f"{T}x{N}",
        "k": int(k),
        **sel,
        "dense_score_bytes": int(T) * int(N) * 4,
        "dense_documented_infeasible": True,
    }
    if cs is None:
        out["error"] = "selection aborted (class budget)"
        return out
    out.update({
        key: cs.stats[key]
        for key in ("classes", "slab_bytes", "truncated_classes")
    })
    inputs = SolverInputs(
        task_req=task_req, task_fit=task_req,
        task_rank=np.arange(T, dtype=np.int32),
        task_job=(np.arange(T) // 10).astype(np.int32),
        task_queue=np.zeros(T, np.int32),
        task_valid=np.ones(T, bool),
        task_group=np.zeros(T, np.int32),
        node_feas=np.ones(N, bool),
        group_feas=np.ones((1, N), bool),
        pair_idx=np.zeros((0,), np.int32),
        pair_feas=np.zeros((0, N), bool),
        score_idx=np.zeros((0,), np.int32),
        score_rows=np.zeros((0, N), np.float32),
        node_idle=node_idle,
        node_releasing=np.zeros_like(node_idle),
        node_cap=node_idle,
        node_task_count=np.zeros(N, np.int32),
        node_max_tasks=np.zeros(N, np.int32),
        queue_deserved=np.full((1, R), np.inf, np.float32),
        queue_allocated=np.zeros((1, R), np.float32),
        eps=eps,
        lr_weight=np.float32(1.0),
        br_weight=np.float32(1.0),
        task_cand=cs.task_cand, cand_idx=cs.cand_idx,
        cand_static=cs.cand_static, cand_info=cs.cand_info,
    )
    native_ok = False
    try:
        from kube_batch_tpu.native import last_solve_stats, solve_native

        t0 = time.perf_counter()
        _assigned, placed = solve_native(inputs)
        native_ok = True
    except Exception:  # NativeUnavailable / no toolchain: jax fallback
        native_ok = False
    if native_ok:
        out.update(
            solve_ms=round((time.perf_counter() - t0) * 1e3, 1),
            backend="native",
            placed=int(placed),
            refill_rounds=int(last_solve_stats.get("refill_rounds", 0)),
            widened=int(last_solve_stats.get("widened", 0)),
        )
        return out
    import jax

    from kube_batch_tpu.solver import solve_sparse_jit

    result = jax.block_until_ready(solve_sparse_jit(inputs))  # compile
    t0 = time.perf_counter()
    result = solve_sparse_jit(inputs)
    assigned = np.asarray(result.assigned)
    out.update(
        solve_ms=round((time.perf_counter() - t0) * 1e3, 1),
        backend=f"jax-{jax.devices()[0].platform}",
        placed=int((assigned >= 0).sum()),
        refill_rounds=int(result.stages),
        refill_tasks=int(result.refills),
    )
    return out


_SHARDED_AB_SCRIPT = r"""
import json, time
import numpy as np
from kube_batch_tpu.utils.backend import force_cpu_devices
assert force_cpu_devices(%(devices)d)
import jax, jax.numpy as jnp
from kube_batch_tpu.solver import (
    default_mesh, make_inputs, pad_tasks, solve_sparse_jit,
    solve_sparse_spmd,
)
from kube_batch_tpu.solver.masks import CombinedMask
from kube_batch_tpu.solver.topk import select_candidates

T, N, K = %(tasks)d, %(nodes)d, 64
rng = np.random.RandomState(7)
R = 2
task_req = np.c_[
    rng.choice(np.linspace(250, 4000, 64).round(), T),
    rng.choice(np.linspace(256, 16384, 32).round(), T),
].astype(np.float32)
node_idle = np.tile(
    np.asarray([32000.0, 128 * 1024.0], np.float32), (N, 1)
)
eps = np.asarray([10.0, 10.0], np.float32)
mask = CombinedMask(
    node_ok=np.ones(N, bool), task_group=np.zeros(T, np.int32),
    group_rows=np.ones((1, N), bool),
    pair_idx=np.zeros((0,), np.int32),
    pair_rows=np.zeros((0, N), bool),
)
cs = select_candidates(
    mask, {}, task_req, task_req, node_idle, node_idle,
    np.zeros_like(node_idle), np.zeros(N, np.int32),
    np.zeros(N, np.int32), eps, 1.0, 1.0, K,
)
inputs = make_inputs(
    task_req=jnp.asarray(task_req), task_fit=jnp.asarray(task_req),
    task_rank=jnp.arange(T, dtype=jnp.int32),
    task_job=jnp.asarray((np.arange(T) // 10).astype(np.int32)),
    task_queue=jnp.zeros(T, jnp.int32),
    node_idle=jnp.asarray(node_idle),
    node_releasing=jnp.zeros((N, R), jnp.float32),
    node_cap=jnp.asarray(node_idle),
    node_task_count=jnp.zeros(N, jnp.int32),
    node_max_tasks=jnp.zeros(N, jnp.int32),
    queue_deserved=jnp.full((1, R), jnp.inf, dtype=jnp.float32),
    queue_allocated=jnp.zeros((1, R), jnp.float32),
    eps=jnp.asarray(eps),
    lr_weight=jnp.asarray(1.0, jnp.float32),
    br_weight=jnp.asarray(1.0, jnp.float32),
    task_cand=jnp.asarray(cs.task_cand),
    cand_idx=jnp.asarray(cs.cand_idx),
    cand_static=jnp.asarray(cs.cand_static),
    cand_info=jnp.asarray(cs.cand_info),
)
mesh = default_mesh()
out = {"devices": mesh.size, "shape": f"{T}x{N}", "k": K}

def timed(fn, *a, **kw):
    r = jax.block_until_ready(fn(*a, **kw))  # compile
    t0 = time.perf_counter()
    r = fn(*a, **kw)
    assigned = np.asarray(r.assigned)
    return (time.perf_counter() - t0) * 1e3, assigned

single_ms, single_a = timed(solve_sparse_jit, inputs)
padded = pad_tasks(inputs, mesh.size)
flat_ms, flat_a = timed(solve_sparse_spmd, padded, mesh)
# Static byte accounting of the commit collective this dispatch ran
# (delta-packed exchange vs the legacy full-state broadcast).
from kube_batch_tpu.solver import spmd as _spmd
out.update({k: int(v) for k, v in _spmd.last_commit_stats.items()})
two_ms, two_a = timed(
    solve_sparse_spmd, padded, mesh, two_level=True
)
out.update(
    single_ms=round(single_ms, 1),
    flat_ms=round(flat_ms, 1),
    two_level_ms=round(two_ms, 1),
    parity=int((single_a == flat_a[:T]).all()),
    placed=int((single_a >= 0).sum()),
    two_level_placed=int((two_a[:T] >= 0).sum()),
)
print("SHARDED_AB " + json.dumps(out))
"""


def bench_sharded_vs_single(tasks=65536, nodes=4096, devices=4):
    """Sharded-vs-single sparse A/B on a forced 4-device host mesh, in
    a SUBPROCESS (the host device count is frozen at backend init, and
    the main bench must keep its real topology). On an oversubscribed
    CPU mesh the shards serialize, so the honest target here is
    ``parity == 1`` (flat bit-equal to single) and completion of both
    sharded modes, not wall-clock speedup — the timings exist so
    committed rounds track the collective overhead trend."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # subprocess owns its device count
    script = _SHARDED_AB_SCRIPT % {
        "devices": devices, "tasks": tasks, "nodes": nodes,
    }
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=1800, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    for line in proc.stdout.splitlines():
        if line.startswith("SHARDED_AB "):
            return json.loads(line[len("SHARDED_AB "):])
    return {
        "error": f"subprocess exit {proc.returncode}",
        "stderr": proc.stderr[-2000:],
    }


_TWOLEVEL_QUALITY_SCRIPT = r"""
import json
from kube_batch_tpu.utils.backend import force_cpu_devices
assert force_cpu_devices(%(devices)d)
from kube_batch_tpu import metrics
from kube_batch_tpu.sim import SimConfig, WorkloadSpec
from kube_batch_tpu.sim.harness import run_sim

report, _ = run_sim(SimConfig(
    cycles=%(cycles)d, seed=%(seed)d, backend="sparse", topk=8,
    workload=WorkloadSpec(
        nodes=%(nodes)d, arrival_rate=4.0, max_jobs_in_flight=128,
    ),
    check_invariants=True,
))
out = {
    "placements": int(report.placements),
    "violations": len(report.violations),
    "cycle_errors": int(report.cycle_errors),
    "bind_failures": int(report.bind_failures),
    "jobs_completed": int(report.jobs_completed),
    "sharded_solves": int(metrics.solver_sparse_sharded.total()),
}
print("TWOLEVEL_Q " + json.dumps(out))
"""


def bench_twolevel_quality(devices=4, cycles=60, seed=9, nodes=32):
    """Sim-based placement-quality study for the two-level (per-rack)
    sharded solve vs the bit-equal flat mode: the same seeded workload
    runs through the FULL production cycle on a forced 4-device host
    mesh with ``KBT_SPARSE_SHARD_MODE`` pinning each mode, and the
    placement outcomes are compared. Two-level is quality-approximate
    by design (each rack solves against only its own node block before
    the psum reconcile), so the numbers that matter are the placement
    delta and that the invariant checker stays clean in BOTH modes —
    the default-policy decision in doc/design/sparse-candidate-solver.md
    cites this study. Subprocesses for the same reason as
    :func:`bench_sharded_vs_single` (host device count is frozen at
    backend init)."""
    import subprocess
    import sys

    def one(mode):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "KBT_SOLVER": "jax", "KBT_SPARSE_SHARD_MODE": mode,
        })
        env.pop("XLA_FLAGS", None)  # subprocess owns its device count
        script = _TWOLEVEL_QUALITY_SCRIPT % {
            "devices": devices, "cycles": cycles, "seed": seed,
            "nodes": nodes,
        }
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True,
            text=True, timeout=1800, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        for line in proc.stdout.splitlines():
            if line.startswith("TWOLEVEL_Q "):
                return json.loads(line[len("TWOLEVEL_Q "):])
        return {
            "error": f"subprocess exit {proc.returncode}",
            "stderr": proc.stderr[-2000:],
        }

    flat = one("flat")
    two = one("two-level")
    out = {
        "devices": devices, "cycles": cycles, "nodes": nodes,
        "flat": flat, "two_level": two,
    }
    if flat.get("placements"):
        out["placements_delta_pct"] = round(
            100.0 * (two.get("placements", 0) - flat["placements"])
            / flat["placements"], 2,
        )
    return out


def bench_integrity(cfg="large", seed=0):
    """Cluster-truth anti-entropy + post-solve validation cost at the
    headline shape (doc/design/robustness.md, event-stream hardening):

    - ``sweep_cold_ms``: first sweep (builds the per-object digest
      caches);
    - ``sweep_steady_ms``: median consistent-mirror sweep — the cost a
      production cycle amortizes over KBT_ANTIENTROPY_EVERY;
    - ``sweep_divergent_ms``: sweep over a 1%-divergent mirror (watch
      detached, 1% of pods bound + a slice deleted behind the cache's
      back), with detected/repaired counts asserted;
    - ``validation_ms``: post-solve validation of a full placement
      vector (O(placements) mask + capacity recheck), plus the
      tampered-vector rejection cost and ``validation_pct_of_steady``
      vs the steady cycle — the <1% budget the tracer overhead is also
      pinned against.
    """
    from kube_batch_tpu.cluster import InProcessCluster
    from kube_batch_tpu.solver.validate import validate_placements

    n_tasks, n_nodes, n_queues, n_groups = CONFIGS[cfg]
    rng = np.random.RandomState(seed)
    cluster = InProcessCluster(simulate_kubelet=False)
    cache = SchedulerCache(
        cluster=cluster,
        binder=FakeBinder(),
        evictor=FakeEvictor(),
        status_updater=FakeStatusUpdater(),
        volume_binder=FakeVolumeBinder(),
    )
    for q in range(n_queues):
        cluster.create_queue(build_queue(f"q{q}", weight=q + 1))
    for j in range(n_nodes):
        cluster.create_node(build_node(
            f"n{j}", build_resource_list(cpu="32", memory="128Gi", pods=110)
        ))
    per_group = n_tasks // n_groups
    cpus = rng.choice([250, 500, 1000, 2000, 4000], size=n_tasks)
    mems = rng.choice([256, 512, 1024, 4096, 8192], size=n_tasks)
    t = 0
    pods = []
    for g in range(n_groups):
        cluster.create_pod_group(build_pod_group(
            f"pg{g}", namespace="bench",
            min_member=int(rng.randint(1, per_group + 1)),
            queue=f"q{g % n_queues}",
        ))
        for i in range(per_group):
            pod = build_pod(
                "bench", f"pg{g}-p{i}", "", PodPhase.PENDING,
                build_resource_list(
                    cpu=f"{int(cpus[t])}m", memory=f"{int(mems[t])}Mi"
                ),
                group_name=f"pg{g}",
            )
            cluster.create_pod(pod)
            pods.append(pod)
            t += 1
    cache.start_ingest()

    ae = cache.antientropy
    t0 = time.perf_counter()
    ae.sweep()
    sweep_cold_ms = (time.perf_counter() - t0) * 1e3
    steady = []
    for _ in range(3):
        t0 = time.perf_counter()
        rep = ae.sweep()
        steady.append((time.perf_counter() - t0) * 1e3)
    assert not rep["detected"], rep
    sweep_steady_ms = sorted(steady)[1]
    # Churned variant: one benign cluster write moves the event rv, so
    # the sweep pays the full truth listing + O(pods) witness loop —
    # what a real 1%-churn steady state pays every
    # KBT_ANTIENTROPY_EVERY cycles (the rv-unchanged shortcut above is
    # the idle-cluster case).
    churned = []
    for _ in range(3):
        cluster.update("Pod", pods[0])
        t0 = time.perf_counter()
        rep = ae.sweep()
        churned.append((time.perf_counter() - t0) * 1e3)
    assert not rep["detected"], rep
    sweep_churned_ms = sorted(churned)[1]

    # 1% divergence injected behind the cache's back: the watch is
    # detached, a slice of pods is bound (missed-bind) and a smaller
    # slice deleted (phantom-task), then the sweep must find + repair
    # every one of them through the stamping handlers.
    cluster.remove_watch(cache._on_watch_event)
    n_div = max(2, n_tasks // 100)
    picks = rng.choice(len(pods), size=n_div, replace=False)
    for k, idx in enumerate(picks):
        pod = pods[int(idx)]
        if k % 8 == 0:
            cluster.delete_pod(pod)
        else:
            try:
                cluster.bind_pod(pod, f"n{int(idx) % n_nodes}")
            except ValueError:
                pass  # already bound by an earlier pick
    cluster.add_watch(cache._on_watch_event)
    t0 = time.perf_counter()
    div = ae.sweep(budget=None)
    sweep_divergent_ms = (time.perf_counter() - t0) * 1e3
    detected = sum(div["detected"].values())
    repaired = sum(div["repaired"].values())

    # Post-solve validation cost on a FULL placement vector.
    ssn = open_session(cache, make_tiers(*TIERS_ARGS))
    try:
        inputs, ctx = tensorize(ssn, device=False)
        T, N = len(ctx.tasks), len(ctx.nodes)
        a = (np.arange(T) % N).astype(np.int64)
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            bad, reasons = validate_placements(ctx, a)
            times.append((time.perf_counter() - t0) * 1e3)
        validation_ms = sorted(times)[2]
        # Steady-churn-sized vector (1% of tasks placed — what a warm
        # steady cycle actually proposes): the per-STEADY-cycle
        # validation cost the <1% pin is quoted against; the full
        # vector above is the cold-burst worst case.
        a_steady = np.full(T, -1, dtype=np.int64)
        n_churn = max(1, T // 100)
        a_steady[:n_churn] = a[:n_churn]
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            validate_placements(ctx, a_steady)
            times.append((time.perf_counter() - t0) * 1e3)
        validation_steady_ms = sorted(times)[2]
        tampered = a.copy()
        tampered[: min(16, T)] = 2**30
        t0 = time.perf_counter()
        bad_t, reasons_t = validate_placements(ctx, tampered)
        validation_reject_ms = (time.perf_counter() - t0) * 1e3
        assert reasons_t.get("bad-index", 0) >= 1, reasons_t
    finally:
        close_session(ssn)
    cache.shutdown()

    return {
        "config": cfg,
        "pods": n_tasks,
        "nodes": n_nodes,
        "sweep_cold_ms": round(sweep_cold_ms, 2),
        "sweep_steady_ms": round(sweep_steady_ms, 2),
        "sweep_churned_ms": round(sweep_churned_ms, 2),
        "sweep_divergent_ms": round(sweep_divergent_ms, 2),
        "divergence_injected": int(n_div),
        "divergence_detected": int(detected),
        "divergence_repaired": int(repaired),
        "validation_ms": round(validation_ms, 3),
        "validation_steady_ms": round(validation_steady_ms, 3),
        "validation_reject_ms": round(validation_reject_ms, 3),
    }


def bench_sim(cycles=80, seed=11):
    """Deterministic-simulator throughput: seeded fault run through the
    full production cycle (virtual clock, so the measured time is pure
    scheduling+churn work), once with the invariant checker and once
    without — the checker's overhead must stay a small fraction of the
    cycle or long-horizon CI runs get expensive."""
    from kube_batch_tpu.native import native_available
    from kube_batch_tpu.sim import SimConfig, WorkloadSpec
    from kube_batch_tpu.sim.harness import run_sim

    backend = "native" if native_available() else "auto"

    def one(check):
        report, _ = run_sim(SimConfig(
            cycles=cycles,
            seed=seed,
            faults="bind:0.05,node-flap:0.02",
            workload=WorkloadSpec(nodes=12),
            backend=backend,
            check_invariants=check,
        ))
        return report

    checked = one(True)
    unchecked = one(False)
    out = {
        "cycles": cycles,
        "backend": backend,
        "placements": checked.placements,
        "violations": len(checked.violations),
        "cycles_per_sec": round(checked.cycles_per_sec, 1),
        "cycles_per_sec_nocheck": round(unchecked.cycles_per_sec, 1),
        "invariant_check_ms_per_cycle": round(
            checked.check_seconds / cycles * 1e3, 3
        ),
        "invariant_check_overhead_pct": round(
            100.0 * checked.check_seconds
            / max(checked.wall_seconds, 1e-9), 1
        ),
    }
    return out


def bench_recovery(cfg="large", seed=0):
    """Cold-takeover failover recovery at the benched shape
    (doc/design/robustness.md, failover section): a predecessor died
    mid-bind-drain leaving a populated cluster + a bind-intent journal
    with every classification class represented; measure what a
    successor pays before it can schedule — fresh-cache ingest of the
    whole cluster, the journal scan + reconcile (incl. gang re-drives
    and one eviction), and its first post-recovery scheduling cycle."""
    from kube_batch_tpu.api.objects import DEFAULT_SCHEDULER_NAME
    from kube_batch_tpu.cache.recovery import reconcile_journal
    from kube_batch_tpu.cluster import InProcessCluster

    n_tasks, n_nodes, n_queues, n_groups = CONFIGS[cfg]
    rng = np.random.RandomState(seed)
    cluster = InProcessCluster(simulate_kubelet=True)
    for q in range(n_queues):
        cluster.create_queue(build_queue(f"q{q}", weight=q + 1))
    for j in range(n_nodes):
        cluster.create_node(build_node(
            f"n{j}", build_resource_list(cpu="32", memory="128Gi", pods=110)
        ))
    per_group = n_tasks // n_groups
    # ~1/16 of the gangs were mid-dispatch at the crash; the rest are
    # the predecessor's steady-state placements (bound + Running).
    inflight_from = n_groups - max(2, n_groups // 16)
    cpus = rng.choice([250, 500, 1000, 2000], size=n_tasks)
    mems = rng.choice([256, 512, 1024, 4096], size=n_tasks)
    t = 0
    journaled = 0
    intents = []
    for g in range(n_groups):
        inflight = g >= inflight_from
        # The last in-flight gang targets a node that died with the
        # leader — unrepairable, recovery must evict its partial
        # placement (the all-or-nothing arm).
        node_gone = inflight and g == n_groups - 1
        cluster.create_pod_group(build_pod_group(
            f"pg{g}", namespace="bench",
            min_member=per_group if inflight else int(
                rng.randint(1, per_group + 1)
            ),
            queue=f"q{g % n_queues}",
        ))
        tasks = []
        for i in range(per_group):
            target = f"n{t % n_nodes}"
            pod = build_pod(
                "bench", f"pg{g}-p{i}", "",
                PodPhase.PENDING,
                build_resource_list(
                    cpu=f"{int(cpus[t])}m", memory=f"{int(mems[t])}Mi"
                ),
                group_name=f"pg{g}",
            )
            cluster.create_pod(pod)
            if not inflight:
                cluster.bind_pod(pod, target)
            else:
                lot = i % 5
                if node_gone:
                    # Half bound (to evict), half lost to a dead node.
                    if lot < 2:
                        cluster.bind_pod(pod, target)
                    else:
                        target = "nGONE"
                elif lot < 2:
                    cluster.bind_pod(pod, target)  # applied, marked
                elif lot == 2:
                    cluster.bind_pod(pod, target)  # applied, mark lost
                # lot > 2: lost — recovery re-drives to complete
                tasks.append({
                    "uid": pod.uid, "pod": f"bench/{pod.name}",
                    "node": target, "job": f"bench/pg{g}",
                    "mark": "applied" if lot < 2 else None,
                })
            t += 1
        if tasks:
            journaled += len(tasks)
            seq = cluster.append_bind_intent({
                "leader": "bench-dead-leader",
                "tasks": [
                    {k: v for k, v in task.items() if k != "mark"}
                    for task in tasks
                ],
                "gangs": {f"bench/pg{g}": per_group},
            })
            intents.append(seq)
            for task in tasks:
                if task["mark"]:
                    cluster.mark_bind_intent(seq, task["uid"], task["mark"])

    # The successor: fresh cache, full ingest, reconcile, first cycle.
    t0 = time.perf_counter()
    cache = SchedulerCache(
        cluster=cluster, scheduler_name=DEFAULT_SCHEDULER_NAME,
        default_queue="q0",
    )
    cache.start_ingest()
    ingest_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    report = reconcile_journal(cluster, "bench-successor")
    reconcile_s = time.perf_counter() - t1
    cache.wait_for_side_effects()

    t2 = time.perf_counter()
    ssn = open_session(cache, make_tiers(*TIERS_ARGS))
    action, _ = get_action("allocate_tpu")
    action.execute(ssn)
    close_session(ssn)
    first_cycle_s = time.perf_counter() - t2
    cache.wait_for_side_effects()
    cache.shutdown()
    return {
        "shape": f"{n_tasks}x{n_nodes}",
        "intents": len(intents),
        "tasks_journaled": journaled,
        "ingest_ms": round(ingest_s * 1e3, 1),
        "reconcile_ms": round(reconcile_s * 1e3, 1),
        "first_cycle_ms": round(first_cycle_s * 1e3, 1),
        "takeover_ms": round(
            (ingest_s + reconcile_s + first_cycle_s) * 1e3, 1
        ),
        "outcomes": dict(sorted(report.outcomes.items())),
        "gangs_repaired": len(report.gangs_repaired),
        "gangs_evicted": len(report.gangs_evicted),
        "recovery_errors": report.errors,
    }


def run_smoke():
    """``bench.py --smoke`` (the `make bench-smoke` target): small
    shapes through the full production cycle with the sparse solver
    FORCED (KBT_SOLVER_TOPK defaults to 8 here so the small config
    engages it), asserting via the cycle stats that the candidate path
    actually ran — exit 4 when it silently fell back to dense."""
    os.environ.setdefault("KBT_SOLVER_TOPK", "8")
    cycle = bench_cycle("small")
    cold = cycle.get("cold", {})
    engaged = bool(cold.get("sparse_engaged"))
    print(json.dumps({
        "metric": "bench-smoke-sparse",
        "sparse_engaged": engaged,
        "sparse_k": cold.get("sparse_k"),
        "sparse_refill_rounds": cold.get("sparse_refill_rounds"),
        "cold_solve_ms": cold.get("solve_ms"),
        "backend": cold.get("backend"),
        "cycle": cycle,
    }))
    if not engaged:
        print("bench-smoke: sparse path did NOT engage", file=sys.stderr)
        sys.exit(4)
    # Steady-cycle assertion (mirror of the sparse-engaged check): the
    # cycle after a placement wave must ride the incremental tensorize —
    # a full_reason there means the wave dirtied its way past the
    # narrow-ledger patching, the exact regression the warm-start work
    # removed (ROADMAP item 1 / the retired cycle.steady.cycle_ms
    # allowlist entry).
    steady = cycle.get("steady", {})
    warm = cycle.get("steady_warm", {})
    steady_ok = (
        steady.get("tensorize_incremental", True)
        and "tensorize_full_reason" not in steady
        and warm.get("warm_engaged", False)
        and warm.get("tensorize_incremental", False)
    )
    if not steady_ok:
        print(
            "bench-smoke: steady cycle did NOT stay incremental "
            f"(steady={ {k: v for k, v in steady.items() if 'tensorize' in k} }, "
            f"warm_engaged={warm.get('warm_engaged')})",
            file=sys.stderr,
        )
        sys.exit(5)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small+medium only (CI-sized)")
    ap.add_argument("--config", choices=list(CONFIGS), default=None)
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture a JAX profiler trace of the headline "
                         "solve into DIR (view with TensorBoard)")
    ap.add_argument(
        "--smoke", action="store_true",
        help="sparse-path smoke (make bench-smoke): small config "
             "through the full cycle with KBT_SOLVER_TOPK forced; "
             "exit 4 unless the sparse solver engaged",
    )
    ap.add_argument(
        "--shape", default=None, metavar="TxN",
        help="extra sparse-only scale point (e.g. 200000x20000); the "
             "default large run includes 200000x20000 automatically",
    )
    ap.add_argument(
        "--shape-xl", default=None, metavar="TxN",
        help="headline sparse scale point with the wide class mix "
             "(default large run: 1000000x100000 — dense [T,N] is 400 "
             "GB there, completion itself is the result)",
    )
    ap.add_argument(
        "--trace", metavar="PATH", default=None,
        help="export one Chrome trace-event JSON of the benched "
             "production cycles to PATH (open in Perfetto)",
    )
    args = ap.parse_args()
    _require_device()
    if args.smoke:
        run_smoke()
        return

    headline_cfg = args.config or ("medium" if args.quick else "large")

    # Python greedy action on the small config (sanity datapoint only).
    greedy_s, greedy_placed, greedy_work = bench_greedy("small")

    tpu = bench_tpu(headline_cfg)
    solve_ms = tpu["solve_s"] * 1e3

    if args.profile:
        # Profiler hook (SURVEY.md §5 tracing parity: latency histograms
        # + JAX profiler for the solver): trace one steady-state solve.
        import jax

        with jax.profiler.trace(args.profile):
            jax.block_until_ready(
                solve_sharded(
                    tpu["inputs"], plan_for(tpu["inputs"], default_mesh())
                )
            )

    # vs_baseline: measured NATIVE reference loop at the headline scale
    # (the honest Go-loop stand-in); falls back to the O(T*N)-extrapolated
    # Python greedy when no native toolchain exists.
    native = bench_native_greedy(tpu["host_inputs"])
    headline_work = CONFIGS[headline_cfg][0] * CONFIGS[headline_cfg][1]
    greedy_extrapolated_s = greedy_s * headline_work / greedy_work
    extra = {}
    if native is not None:
        native_s, native_placed = native
        speedup = native_s / tpu["solve_s"]
        extra = {
            "native_greedy_ms": round(native_s * 1e3, 1),
            "native_greedy_placed": native_placed,
            "baseline_kind": "native-greedy-measured",
        }
    else:
        speedup = greedy_extrapolated_s / tpu["solve_s"]
        extra = {"baseline_kind": "python-greedy-extrapolated"}

    import jax

    # Full production cycles (open+tensorize+solve+apply+close) at the
    # headline scale: cold burst, unchanged steady state, 1%-delta arrival.
    # Guarded: a crash/hang here must not lose the already-measured headline
    # (round-1 lesson — a bench that dies records nothing).
    try:
        cycle = bench_cycle(
            headline_cfg, cache=tpu["cache"], trace_path=args.trace,
            measure_obs=True,
        )
    except Exception as exc:  # pragma: no cover - defensive
        cycle = {"error": f"{type(exc).__name__}: {exc}"}
    obs = cycle.pop("obs", None) if isinstance(cycle, dict) else None
    quality = (
        cycle.pop("quality", None) if isinstance(cycle, dict) else None
    )

    # Device-resident snapshot pack stats (small config: the mechanics,
    # not the scale — the headline cycles carry device_* keys whenever
    # the jax path solved them). Guarded like the cycles.
    try:
        device_cache = bench_device_cache("small")
    except Exception as exc:  # pragma: no cover - defensive
        device_cache = {"error": f"{type(exc).__name__}: {exc}"}

    # Sparse-only scale point: shapes the dense path cannot touch. Part
    # of the default large run; --shape overrides. Guarded — an OOM or
    # toolchain failure here must not lose the headline.
    sparse_scale = None
    scale_shape = args.shape or (
        "200000x20000" if headline_cfg == "large" else None
    )
    if scale_shape:
        try:
            sparse_scale = bench_sparse_scale(scale_shape)
        except Exception as exc:  # pragma: no cover - defensive
            sparse_scale = {"error": f"{type(exc).__name__}: {exc}"}

    # Headline raw-scale point (1M x 100k, wide class mix) + the
    # sharded-vs-single sparse A/B (subprocess, forced 4-device host
    # mesh). Both guarded — an OOM or subprocess failure must not lose
    # the rest of the run.
    sparse_scale_xl = None
    xl_shape = args.shape_xl or (
        "1000000x100000" if headline_cfg == "large" else None
    )
    if xl_shape:
        try:
            sparse_scale_xl = bench_sparse_scale(xl_shape, wide_mix=True)
        except Exception as exc:  # pragma: no cover - defensive
            sparse_scale_xl = {"error": f"{type(exc).__name__}: {exc}"}
    sharded_vs_single = None
    twolevel_quality = None
    if headline_cfg == "large":
        try:
            sharded_vs_single = bench_sharded_vs_single()
        except Exception as exc:  # pragma: no cover - defensive
            sharded_vs_single = {"error": f"{type(exc).__name__}: {exc}"}
        # Two-level placement-quality study (full-cycle sim, both
        # sharded modes forced in turn); guarded like the A/B above.
        try:
            twolevel_quality = bench_twolevel_quality()
        except Exception as exc:  # pragma: no cover - defensive
            twolevel_quality = {"error": f"{type(exc).__name__}: {exc}"}

    # Long-horizon simulator throughput + invariant-checker overhead
    # (guarded like the other sections).
    try:
        sim = bench_sim()
    except Exception as exc:  # pragma: no cover - defensive
        sim = {"error": f"{type(exc).__name__}: {exc}"}

    # Cold-takeover failover recovery at the headline shape (journal
    # scan + reconcile + first post-recovery cycle); guarded.
    try:
        recovery = bench_recovery(headline_cfg)
    except Exception as exc:  # pragma: no cover - defensive
        recovery = {"error": f"{type(exc).__name__}: {exc}"}

    # Arrival→bind placement-latency percentiles under the high-arrival
    # sim mixes (virtual-time, machine-independent; guarded).
    try:
        arrival_latency = bench_arrival_latency(
            quick=headline_cfg != "large"
        )
    except Exception as exc:  # pragma: no cover - defensive
        arrival_latency = {"error": f"{type(exc).__name__}: {exc}"}

    # Serving-SLO attainment + per-class bind p99 under the mixed
    # congested regime (virtual-time, machine-independent; guarded).
    try:
        serving = bench_serving(quick=headline_cfg != "large")
    except Exception as exc:  # pragma: no cover - defensive
        serving = {"error": f"{type(exc).__name__}: {exc}"}

    # Anti-entropy sweep + post-solve validation cost at the headline
    # shape, with the steady-cycle-relative budgets the <1% pin is
    # quoted against (guarded like every section).
    try:
        integrity = bench_integrity(headline_cfg)
        steady_ms = None
        if isinstance(cycle, dict):
            sw = cycle.get("steady_warm") or cycle.get("steady") or {}
            steady_ms = sw.get("cycle_ms")
        if steady_ms:
            integrity["validation_pct_of_steady"] = round(
                100.0 * integrity["validation_steady_ms"] / steady_ms, 3
            )
            every = int(os.environ.get("KBT_ANTIENTROPY_EVERY", "256"))
            integrity["sweep_every"] = every
            # Amortized off the CHURNED sweep — the honest steady-state
            # cost (churn moves the cluster rv every cycle, so the
            # idle-cluster shortcut never fires there).
            integrity["sweep_amortized_pct_of_steady"] = round(
                100.0 * (integrity["sweep_churned_ms"] / every)
                / steady_ms, 3,
            )
            integrity["integrity_pct_of_steady"] = round(
                integrity["sweep_amortized_pct_of_steady"]
                + integrity["validation_pct_of_steady"], 3,
            )
    except Exception as exc:  # pragma: no cover - defensive
        integrity = {"error": f"{type(exc).__name__}: {exc}"}

    dev0 = jax.devices()[0]
    provenance = {
        "platform": str(dev0.platform),
        "device_kind": str(getattr(dev0, "device_kind", "")),
        "num_devices": len(jax.devices()),
    }

    print(json.dumps({
        "metric": f"gang-cycle-solve-latency-{headline_cfg}"
                  f"-{CONFIGS[headline_cfg][0]}x{CONFIGS[headline_cfg][1]}",
        "value": round(solve_ms, 3),
        "unit": "ms",
        "vs_baseline": round(speedup, 1),
        "pods_placed": tpu["placed"],
        "pods_placed_per_sec": round(tpu["placed"] / tpu["solve_s"], 1),
        "solver_rounds": tpu["rounds"],
        "host_snapshot_ms": round(tpu["snapshot_s"] * 1e3, 1),
        "session_open_ms": round(tpu["session_s"] * 1e3, 1),
        "greedy_small_ms": round(greedy_s * 1e3, 1),
        "greedy_extrapolated_ms": round(greedy_extrapolated_s * 1e3, 1),
        "device": str(jax.devices()[0].platform),
        "device_provenance": provenance,
        "cycle": cycle,
        "obs": obs,
        "quality": quality,
        "device_cache": device_cache,
        "solver_sparse": tpu["sparse"],
        "sim": sim,
        "recovery": recovery,
        "arrival_latency": arrival_latency,
        "serving": serving,
        "integrity": integrity,
        **({"sparse_scale": sparse_scale} if sparse_scale else {}),
        **({"sparse_scale_xl": sparse_scale_xl} if sparse_scale_xl
           else {}),
        **({"sharded_vs_single": sharded_vs_single} if sharded_vs_single
           else {}),
        **({"twolevel_quality": twolevel_quality} if twolevel_quality
           else {}),
        **extra,
    }))


if __name__ == "__main__":
    main()
