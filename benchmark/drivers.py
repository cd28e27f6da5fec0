"""The two general traffic drivers: ``burst`` and ``steady``.

A traffic mix is a data file (``traffic/<mix>.json``) whose ``kind`` names
one of them and whose other keys are its parameters. Each driver builds the
deployment, warms every shape its traffic uses (set-up), measures for
``seconds``, and returns a :class:`Run`: what the window recorded, for the
metric readers and for the correctness check, which runs after the window.
"""

import sys
import threading
import time

import numpy as np

import reference
from gen import Deployment, GangSource, node_allocatable, queue_weights
from watch import WatchRecorder

WINDOW_MARK = "kbt_bench_window"
DRAIN_LIMIT_S = 60.0


def log(msg):
    """Progress on standard error (the result line is on standard out)."""
    print(f"[bench {time.perf_counter():.3f}] {msg}", file=sys.stderr,
          flush=True)


class BenchFailure(RuntimeError):
    """The run cannot give a result (not a wrong answer: that is
    ``correct: false``)."""


class CompileMeter:
    """Backend compiles, from jax.monitoring (copied from chip_smoke.py)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


class SolveObserver:
    """Copies the allocate action's ``last_stats`` after every execute
    (periodic and micro cycles). Observation only: the wrapper returns
    what it wraps, and leaving the ``with`` block restores the action."""

    KEYS = ("backend", "solve_ladder", "solve_degraded",
            "validation_rejected", "breaker_pinned", "select_path",
            "sparse_engaged", "apply_ms")

    def __enter__(self):
        from kube_batch_tpu.actions import allocate_tpu
        from kube_batch_tpu.framework import get_action
        from kube_batch_tpu.solver import containment

        self.records = []
        action, _ = get_action("allocate_tpu")
        execute = action.execute
        stats = allocate_tpu.last_stats

        def observed(ssn):
            try:
                return execute(ssn)
            finally:
                rec = {k: stats[k] for k in self.KEYS if k in stats}
                rec["t"] = time.perf_counter()
                rec["breaker"] = containment.BREAKER.state
                rec["breaker_closed"] = (
                    containment.BREAKER.state == containment.STATE_CLOSED)
                self.records.append(rec)

        action.execute = observed
        self._release = lambda: delattr(action, "execute")
        return self

    def __exit__(self, *exc):
        self._release()


def device_problems(rec, platform, sparse_required):
    """Why a cycle that dispatched a solve was not a first-rung device
    solve (chip_smoke.check_device_cycle's conditions); empty if it was."""
    if "solve_ladder" not in rec:
        return []
    problems = []
    if rec.get("backend") != f"jax-{platform}":
        problems.append(f"backend={rec.get('backend')}")
    if len(rec.get("solve_ladder") or []) != 1:
        problems.append("ladder left its first rung")
    for key in ("solve_degraded", "validation_rejected", "breaker_pinned"):
        if rec.get(key):
            problems.append(key)
    if not rec["breaker_closed"]:
        problems.append(f"breaker {rec['breaker']}")
    if sparse_required:
        if rec.get("select_path") != "device":
            problems.append(f"select_path={rec.get('select_path')}")
        if not rec.get("sparse_engaged"):
            problems.append("sparse path not engaged")
    return problems


class Run:
    """What one run recorded, for the metric readers."""

    def __init__(self, kind, platform, seconds, trace):
        self.kind = kind
        self.platform = platform
        self.seconds = seconds
        self.trace = trace
        self.setup_s = None
        self.window = None          # (start, end) host perf_counter s
        self.cycles = []            # burst cycles: dicts, see run_burst
        self.gangs = []             # steady: dicts per gang due in window
        self.binds_in_window = 0
        self.compiles_in_window = 0
        self.spans = []             # (name, t0, t1, cycle) host s, window
        self.device = None          # trace_reduce results, or None
        self.profile_window = None  # (start, end) host s of the profile
        self.gen_lag_s = []
        self.checks = {}
        self.attempted = 0
        self.failed = 0
        self.memory_peak_bytes = None
        self.notes = {}             # pending pods at window start/end


class Profiler:
    """The JAX profiler over a part of the window, with the harness's own
    annotation marking where that part begins on the trace's clock."""

    def __init__(self, out_dir):
        self.out_dir = out_dir
        self.start_s = None
        self.stop_s = None
        self._mark = None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # every Python call: slows the host
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation(WINDOW_MARK)
        self._mark.__enter__()
        self.start_s = time.perf_counter()

    def stop(self):
        import jax

        self.stop_s = time.perf_counter()
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()


def _spans_in(window):
    from kube_batch_tpu.obs.tracer import TRACER

    lo, hi = window
    out = []
    for rec in list(TRACER._events):
        name, t0, t1, _tid, _sid, _parent, cycle = rec[:7]
        if t0 >= lo and t1 <= hi:
            out.append((name, t0, t1, cycle))
    return out


def _enable_spans():
    from kube_batch_tpu.obs.tracer import TRACER

    TRACER.reset()
    TRACER.enable()


def _peak_bytes():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _pod_requests(gangs):
    return {f"{g.name}-{i}": (int(c), int(m))
            for g in gangs
            for i, (c, m) in enumerate(zip(g.cpu_milli, g.mem_mi))}


def _replay_checks(run, rec, pod_req, cfg):
    alloc = node_allocatable(cfg)
    run.checks["oversubscribed_nodes"] = reference.oversubscribed_nodes(
        rec.binds, rec.deletes, rec.node_of, pod_req, alloc, cfg["nodes"])
    run.checks["double_binds"] = len(rec.rebinds)


def run_burst(cfg, mix, seed, seconds, trace, platform, t_start,
              profile_dir, place=None):
    """Gang bursts: a backlog of ``backlog_pods_per_node`` x nodes pending
    pods lands at once and one ``Scheduler.run_once`` places it. Between
    bursts, untimed, the placed pods are deleted (the jobs complete) and
    the next backlog is created. ``place`` replaces ``run_once`` (the
    control and the fault tests)."""
    run = Run("burst", platform, seconds, trace)
    rng = np.random.default_rng(seed)
    source = GangSource(cfg, rng)
    per_burst = cfg["nodes"] * mix["backlog_pods_per_node"] \
        // cfg["pods_per_group"]
    dep = Deployment(cfg)
    rec = WatchRecorder(dep.cluster)
    dep.cache.run(dep.stop)
    if not dep.cache.wait_for_cache_sync(dep.stop):
        raise BenchFailure("scheduler cache never synced")
    meter = CompileMeter()
    all_gangs = []
    place = place or (lambda d: d.sched.run_once())
    profiler = Profiler(profile_dir) if trace else None

    def one_burst(k, profile):
        tc = time.perf_counter()
        for name in list(dep.live):
            dep.delete(name)
        gangs = source.draw(f"b{k}g", per_burst)
        all_gangs.extend(gangs)
        for g in gangs:
            dep.create(g)
        if profile:
            profiler.start()
        n_obs = len(obs.records)
        t0 = time.perf_counter()
        place(dep)
        t1 = time.perf_counter()
        drained = dep.cache.wait_for_side_effects(timeout=300.0)
        t2 = time.perf_counter()
        if profile:
            profiler.stop()
        if not drained:
            raise BenchFailure("bind side effects did not drain in 300 s")
        log(f"burst {k}: created in {t0 - tc:.3f} s, run_once "
            f"{t1 - t0:.3f} s, drain {t2 - t1:.3f} s")
        stored = {}
        for g in gangs:
            for pod in dep.live[g.name][2]:
                stored[pod.metadata.name] = pod.spec.node_name
        return {"t0": t0, "t1": t1, "t2": t2, "stats": obs.records[n_obs:],
                "gangs": gangs, "stored": stored}

    with SolveObserver() as obs:
        try:
            for k in range(mix["warm_bursts"]):
                one_burst(k, False)
            if trace:
                _enable_spans()
            c0 = meter.count
            w0 = time.perf_counter()
            run.setup_s = w0 - t_start
            k = mix["warm_bursts"]
            while not run.cycles or time.perf_counter() - w0 < seconds:
                run.cycles.append(one_burst(k, trace and not run.cycles))
                k += 1
            w1 = time.perf_counter()
            run.window = (w0, w1)
            run.compiles_in_window = meter.count - c0
            run.memory_peak_bytes = _peak_bytes()
            if trace:
                run.spans = _spans_in(run.window)
                run.profile_window = (profiler.start_s, profiler.stop_s)
        finally:
            dep.close()

    # --- after the window: the reference judges what the window placed
    pod_req = _pod_requests(all_gangs)
    _replay_checks(run, rec, pod_req, cfg)
    alloc = node_allocatable(cfg)
    weights = queue_weights(cfg)
    partial = mismatch = left = non_device = 0
    failed = set()
    for cyc in run.cycles:
        judged = [(g.name, g.queue, g.min_member, g.pod_names())
                  for g in cyc["gangs"]]
        p, m, lf, f = reference.judge_gangs(
            judged, cyc["stored"], rec.node_of, alloc, cfg["nodes"],
            pod_req, weights)
        partial, mismatch, left = partial + p, mismatch + m, left + lf
        failed |= f
        # Every burst must have solved on the device, first rung.
        solved = [s for s in cyc["stats"] if "solve_ladder" in s]
        non_device += (not solved) or any(
            device_problems(s, platform, True) for s in solved)
        run.attempted += len(judged)
    run.failed = len(failed)
    run.checks.update(partial_gangs=partial, bind_mismatches=mismatch,
                      pods_left_that_fit=left,
                      non_device_cycles=non_device)
    return run


def _arrival_times(rng, n, lo, hi):
    """``n`` arrival times in [lo, hi): a Poisson process conditioned on
    its count, so every seed offers the same amount of work."""
    return np.sort(rng.uniform(lo, hi, size=n))


def run_steady(cfg, mix, seed, seconds, trace, platform, t_start,
               profile_dir, place=None):
    """Open-loop gang arrivals against a standing population, driven by
    the production loop ``Scheduler.run`` (micro cycles on) in its own
    thread. Jobs complete at the arrival rate, each a uniformly chosen
    running job, so occupancy holds (exponential lifetimes with mean
    standing pods / offered pod rate). ``place(dep, stop)`` replaces the
    scheduler loop (the control and the fault tests)."""
    run = Run("steady", platform, seconds, trace)
    rng = np.random.default_rng(seed)
    source = GangSource(cfg, rng)
    size = cfg["pods_per_group"]
    dep = Deployment(cfg, scheduler_period=mix["schedule_period_s"])
    rec = WatchRecorder(dep.cluster)
    meter = CompileMeter()
    log("deployment created")
    standing = source.draw("s", cfg["nodes"] * mix["standing_pods_per_node"]
                           // size)
    for g in standing:
        dep.create(g)
    pods_created = [len(standing) * size]
    warm_gangs = []
    rate = mix["gang_rate_per_s"]
    warm = mix["warmup_s"]
    n_warm, n_win = round(rate * warm), round(rate * seconds)
    arrivals = source.draw("a", n_warm + n_win)
    arrive_t = np.r_[_arrival_times(rng, n_warm, 0.0, warm),
                     _arrival_times(rng, n_win, warm, warm + seconds)]
    finish_t = np.r_[_arrival_times(rng, n_warm, 0.0, warm),
                     _arrival_times(rng, n_win, warm, warm + seconds)]
    pick = rng.random(len(finish_t))
    timeline = sorted([(t, 0, i) for i, t in enumerate(arrive_t)]
                      + [(t, 1, i) for i, t in enumerate(finish_t)])
    thread = threading.Thread(
        target=(place or (lambda d, s: d.sched.run(s))),
        args=(dep, dep.stop), name="bench-scheduler", daemon=True)
    profiler = Profiler(profile_dir) if trace else None
    lag, started_at = [], {}

    def started(g):
        """Host time of the gang's minMember-th bind, or None."""
        if g.name not in started_at:
            times = sorted(t for t in (rec.bind_time.get(p)
                                       for p in g.pod_names())
                           if t is not None)
            if len(times) < g.min_member:
                return None
            started_at[g.name] = times[g.min_member - 1]
        return started_at[g.name]

    def pending_pods():
        """Pods created and not yet bound (deleted pods were bound)."""
        return pods_created[0] - rec.bound_count() + unbound_deleted()

    def unbound_deleted():
        return sum(1 for _, _, p in rec.deletes if p not in rec.node_of)

    def wait_all_started(limit):
        deadline = time.perf_counter() + limit
        while time.perf_counter() < deadline:
            if all(started(dep.live[n][0]) is not None for n in dep.live):
                return True
            time.sleep(0.05)
        return False

    with SolveObserver() as obs:
        try:
            thread.start()
            if not wait_all_started(600.0):
                raise BenchFailure("standing population not placed in 600 s")
            if not dep.cache.wait_for_side_effects(timeout=300.0):
                raise BenchFailure("bind side effects did not drain")
            log("standing population placed")
            # Warm the task-axis shape buckets the window's cycles can
            # meet: waves of 1, 2, 4, ... gangs at once, each placed,
            # then completed.
            for n in mix["warm_waves"]:
                wave = source.draw(f"w{n}x", n)
                warm_gangs.extend(wave)
                for g in wave:
                    dep.create(g)
                    pods_created[0] += size
                deadline = time.perf_counter() + 120.0
                while not all(started(g) is not None for g in wave):
                    if time.perf_counter() > deadline:
                        raise BenchFailure(f"warm wave of {n} not placed")
                    time.sleep(0.02)
                for g in wave:
                    dep.delete(g.name)
            log("warm waves placed")
            running = [g.name for g in standing]
            t_zero = time.perf_counter() + 0.05
            w0, w1 = t_zero + warm, t_zero + warm + seconds
            c0 = None
            prof_thread = None
            if trace:
                def profile():
                    p0 = w1 - mix["profile_s"]
                    time.sleep(max(0.0, p0 - time.perf_counter()))
                    profiler.start()
                    time.sleep(max(0.0, w1 - time.perf_counter()))
                    profiler.stop()
                prof_thread = threading.Thread(target=profile,
                                               name="bench-profiler")
            for t, what, i in timeline:
                due = t_zero + t
                if c0 is None and due >= w0:
                    time.sleep(max(0.0, w0 - time.perf_counter()))
                    run.setup_s = time.perf_counter() - t_start
                    c0 = meter.count
                    run.notes["pending_pods_at_start"] = pending_pods()
                    if trace:
                        _enable_spans()
                        prof_thread.start()
                time.sleep(max(0.0, due - time.perf_counter()))
                if what == 0:
                    g = arrivals[i]
                    if due >= w0:
                        lag.append(time.perf_counter() - due)
                        run.gangs.append({"gang": g, "due": due})
                    dep.create(g)
                    pods_created[0] += size
                    running.append(g.name)
                else:
                    done = [n for n in running
                            if started(dep.live[n][0]) is not None]
                    if done:
                        name = done[int(pick[i] * len(done))]
                        running.remove(name)
                        dep.delete(name)
            if c0 is None:
                raise BenchFailure("no arrival fell in the window")
            time.sleep(max(0.0, w1 - time.perf_counter()))
            run.compiles_in_window = meter.count - c0
            run.notes["pending_pods_at_end"] = pending_pods()
            run.window = (w0, w1)
            if trace:
                prof_thread.join()
                run.spans = _spans_in(run.window)
                run.profile_window = (profiler.start_s, profiler.stop_s)
            # Late answers are late, not wrong: wait for every gang.
            wait_all_started(DRAIN_LIMIT_S)
            drain_end = time.perf_counter()
            dep.cache.wait_for_side_effects(timeout=30.0)
            run.memory_peak_bytes = _peak_bytes()
        finally:
            dep.stop.set()
            thread.join(timeout=60.0)
            dep.close()
        if thread.is_alive():
            raise BenchFailure("scheduler loop did not stop")

    run.gen_lag_s = lag
    for rec_g in run.gangs:
        s = started(rec_g["gang"])
        rec_g["start"] = s
        rec_g["latency"] = (s if s is not None else drain_end) - rec_g["due"]
    run.binds_in_window = sum(1 for _, t, _, _ in rec.binds
                              if w0 <= t < w1)
    # --- after the window: the reference judges the final placement
    pod_req = _pod_requests(standing + warm_gangs + arrivals)
    _replay_checks(run, rec, pod_req, cfg)
    stored = {}
    judged = []
    for name, (g, _, pods) in dep.live.items():
        judged.append((name, g.queue, g.min_member, g.pod_names()))
        for pod in pods:
            stored[pod.metadata.name] = pod.spec.node_name
    p, m, lf, failed = reference.judge_gangs(
        judged, stored, rec.node_of, node_allocatable(cfg), cfg["nodes"],
        pod_req, queue_weights(cfg))
    never = {r["gang"].name for r in run.gangs if r["start"] is None}
    solved = [s for s in obs.records if "solve_ladder" in s
              and s["t"] >= run.window[0]]
    non_device = sum(bool(device_problems(s, platform, False))
                     for s in solved)
    if place is None and not solved:
        non_device += 1  # the window must drive the device path
    run.attempted = len(run.gangs)
    run.failed = len(never | (failed & {r["gang"].name for r in run.gangs}))
    run.checks.update(partial_gangs=p, bind_mismatches=m,
                      pods_left_that_fit=lf, gangs_never_started=len(never),
                      non_device_cycles=non_device)
    return run


DRIVERS = {"burst": run_burst, "steady": run_steady}
