#!/usr/bin/env python
"""Chip smoke: the production scheduling cycle on a TPU, through the real
scheduler, at the BASELINE headline deployment.

Default run (one chip, the first, even on a host that shows more):
  5,000 nodes (32 CPU, 128Gi, 110 pods) and 5 queues (weights 1..5);
  500 PodGroups (minMember uniform in 1..100) holding 50,000 pending pods
  with the bench.py request mix, all generated from ``--seed``. Objects
  enter through ``InProcessCluster.create_*`` and the cache's watch
  ingest, exactly as ``cli/server.py`` wires a scheduler. Cycles:

  1. a cold burst ``Scheduler.run_once``;
  2. a 1% arrival wave (500 pods in 5 new gangs), then ``run_once``;
  3. a second such wave (warm-up: the first wave after the burst
     patches every node row, later waves patch churn-sized buckets);
  4. a third: this repeat must add zero compilations;
  5. one more such wave, placed by one ``Scheduler.run_micro``.

  Every cycle that places pods must have solved on the TPU on the first
  rung of the degradation ladder, with device candidate selection and
  the sparse solver engaged and the breaker closed. After every cycle
  the simulator's ``InvariantChecker`` must find no oversubscribed node,
  no partial gang and no double bind. All 50,000 pods must be placed,
  and cycle 1's snapshot re-solved on the host CPU backend in this same
  process must place as many (the differing rows are printed).

``--chips 4`` runs only the multi-chip phase on a 4-chip host: a
100,000-pod x 10,000-node snapshot (same mix), past the flat sharding
crossover. ``run_once`` on the 4-chip mesh must pick the flat sparse
mode and be bit-equal to the single-device solve of the same snapshot
on chip 0; the two-level mode then schedules the same deployment, must
pass the invariants, and its placed count is printed next to flat's.

Timings printed here are smoke readings, not benchmark results. The last
line of standard output is the one JSON result object; a run that finds
no TPU, or fails any check, exits non-zero without printing it.

Usage: python chip_smoke.py [--seed S] [--chips 1|4]
"""

import argparse
import json
import os
import sys
import threading
import time

NS = "smoke"
# Request mix of bench.build_cluster (the BASELINE configs).
CPU_MILLI = (250, 500, 1000, 2000, 4000)
MEM_MI = (256, 512, 1024, 4096, 8192)
CONF = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "config", "tpu-batch-conf-tpu.yaml",
)


class SmokeFailure(RuntimeError):
    pass


def log(msg):
    print(msg, flush=True)


def pin_first_chip():
    """Make this process see chip 0 only (the libtpu per-process chip
    bounds). Must run before jax initializes its TPU backend."""
    os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
    os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
    os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


class CompileMeter:
    """Backend compile events and seconds, from jax.monitoring."""

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

    def mark(self):
        return self.count, self.seconds


class Deployment:
    """An in-process cluster, its scheduler cache and a Scheduler, wired
    as cli/server.py wires them, with the deployment created through the
    cluster API (the cache ingests it through its watch)."""

    def __init__(self, seed, nodes, pods, groups, queues=5):
        import numpy as np

        from kube_batch_tpu.api import build_resource_list
        from kube_batch_tpu.cache import new_scheduler_cache
        from kube_batch_tpu.cluster import InProcessCluster
        from kube_batch_tpu.scheduler import Scheduler
        from kube_batch_tpu.utils.test_utils import build_node, build_queue

        self.rng = np.random.RandomState(seed)
        self.queues = queues
        self.per_group = pods // groups
        self.cluster = InProcessCluster(simulate_kubelet=True)
        self.cache = new_scheduler_cache(self.cluster, "tpu-batch", "default")
        self.stop = threading.Event()
        self.cache.run(self.stop)
        if not self.cache.wait_for_cache_sync(self.stop):
            raise SmokeFailure("scheduler cache never synced")
        self.sched = Scheduler(self.cache, scheduler_conf=CONF)
        for q in range(queues):
            self.cluster.create_queue(build_queue(f"q{q}", weight=q + 1))
        for j in range(nodes):
            self.cluster.create_node(build_node(
                f"n{j}",
                build_resource_list(cpu="32", memory="128Gi", pods=110),
            ))
        self.n_gangs = 0
        self.add_gangs("pg", groups)

    def add_gangs(self, prefix, count):
        """Create ``count`` new PodGroups of ``per_group`` pending pods."""
        from kube_batch_tpu.api import PodPhase, build_resource_list
        from kube_batch_tpu.utils.test_utils import build_pod, build_pod_group

        n = count * self.per_group
        cpus = self.rng.choice(CPU_MILLI, size=n)
        mems = self.rng.choice(MEM_MI, size=n)
        first = self.n_gangs
        self.n_gangs += count
        t = 0
        for g in range(count):
            name = f"{prefix}{first + g}"
            min_member = int(self.rng.randint(1, self.per_group + 1))
            self.cluster.create_pod_group(build_pod_group(
                name, namespace=NS, min_member=min_member,
                queue=f"q{(first + g) % self.queues}",
            ))
            for i in range(self.per_group):
                self.cluster.create_pod(build_pod(
                    NS, f"{name}-p{i}", "", PodPhase.PENDING,
                    build_resource_list(
                        cpu=f"{int(cpus[t])}m", memory=f"{int(mems[t])}Mi"
                    ),
                    group_name=name,
                ))
                t += 1

    def bound_pods(self):
        return sum(
            1 for p in self.cluster.list_objects("Pod")
            if p.namespace == NS and p.spec.node_name
        )

    def settle(self):
        if not self.cache.wait_for_side_effects(timeout=300.0):
            raise SmokeFailure("bind side effects did not drain in 300 s")

    def close(self):
        self.stop.set()
        self.cache.shutdown()


class SolveCapture:
    """Records, for the most recent allocate_tpu execute in this process,
    the snapshot's host arrays and the assignment the ladder returned.
    Observation only: both wrappers return exactly what they wrap, and
    leaving the ``with`` block puts the originals back (the action is a
    registered singleton)."""

    def __enter__(self):
        import numpy as np

        from kube_batch_tpu.actions import allocate_tpu
        from kube_batch_tpu.framework import get_action

        self.host_inputs = None
        self.assigned = None
        action, _ = get_action("allocate_tpu")
        self.max_rounds = action.max_rounds
        tensorize = allocate_tpu.tensorize
        ladder = action._solve_ladder

        def capture_tensorize(*a, **kw):
            inputs, ctx = tensorize(*a, **kw)
            if ctx is not None:
                # Copies: later cycles patch resident stacks in place.
                self.host_inputs = type(ctx.host_inputs)(*(
                    None if x is None else np.array(x)
                    for x in ctx.host_inputs
                ))
            return inputs, ctx

        def capture_ladder(*a, **kw):
            assigned, handle = ladder(*a, **kw)
            self.assigned = np.array(assigned)
            return assigned, handle

        allocate_tpu.tensorize = capture_tensorize
        action._solve_ladder = capture_ladder

        def release():
            allocate_tpu.tensorize = tensorize
            del action._solve_ladder

        self._release = release
        return self

    def __exit__(self, *exc):
        self._release()


def check_device_cycle(label, platform):
    """The cycle just run placed through the device path, first rung."""
    from kube_batch_tpu.actions.allocate_tpu import last_stats
    from kube_batch_tpu.solver import containment

    problems = []
    if last_stats.get("backend") != f"jax-{platform}":
        problems.append(f"backend={last_stats.get('backend')!r}")
    ladder = last_stats.get("solve_ladder") or []
    if len(ladder) != 1:
        problems.append(f"solve_ladder={ladder!r}")
    for key in ("solve_degraded", "validation_rejected", "breaker_pinned"):
        if last_stats.get(key):
            problems.append(f"{key}={last_stats[key]!r}")
    if containment.BREAKER.state != containment.STATE_CLOSED:
        problems.append(f"breaker={containment.BREAKER.state}")
    if last_stats.get("select_path") != "device":
        problems.append(f"select_path={last_stats.get('select_path')!r}")
    if not last_stats.get("sparse_engaged"):
        problems.append("sparse path not engaged")
    if problems:
        raise SmokeFailure(f"{label}: not a first-rung device solve: "
                           + ", ".join(problems))


def check_invariants(dep, checker, cycle, label):
    found = checker.check(dep.cache, cycle, namespace=NS)
    if found:
        raise SmokeFailure(f"{label}: {len(found)} invariant violations, "
                           f"first: {found[0].to_dict()}")


def run_cycle(label, fn, dep, checker, cycle, meter, platform,
              expect_placed):
    """Run one scheduling cycle and check it; returns its smoke reading.
    ``expect_placed`` None accepts any count."""
    import jax

    from kube_batch_tpu.actions.allocate_tpu import last_stats
    from kube_batch_tpu.solver import kernels

    before = dep.bound_pods()
    jit0 = kernels.jit_compilation_count()
    c0, s0 = meter.mark()
    t0 = time.perf_counter()
    ok = fn()
    wall_ms = (time.perf_counter() - t0) * 1e3
    if ok is False:
        raise SmokeFailure(f"{label}: cycle reported failure")
    dep.settle()
    placed = dep.bound_pods() - before
    c1, s1 = meter.mark()
    reading = {
        "cycle": label,
        "cycle_wall_ms": wall_ms,
        "phase_ms": {
            k[:-3]: v for k, v in last_stats.items()
            if k.endswith("_ms") and isinstance(v, (int, float))
        },
        "placed": placed,
        "backend_compiles": c1 - c0,
        "compile_s": s1 - s0,
        "jit_variants_added": kernels.jit_compilation_count() - jit0,
        "peak_bytes_in_use": (
            jax.devices()[0].memory_stats() or {}
        ).get("peak_bytes_in_use"),
        "solve_ladder": last_stats.get("solve_ladder"),
        "select_path": last_stats.get("select_path"),
        "sparse_engaged": last_stats.get("sparse_engaged"),
        "shard_mode": last_stats.get("sparse_shard_mode"),
    }
    log("smoke reading, not a benchmark: " + json.dumps(reading, default=str))
    if expect_placed is not None and placed != expect_placed:
        raise SmokeFailure(
            f"{label}: placed {placed} pods, expected {expect_placed}")
    if placed:
        check_device_cycle(label, platform)
    check_invariants(dep, checker, cycle, label)
    return reading


def solve_on(device, host_inputs, max_rounds):
    """The single-device solve of a captured snapshot on ``device``."""
    import jax
    import numpy as np

    from kube_batch_tpu.solver.kernels import solve_jit

    with jax.default_device(device):
        res = solve_jit(jax.device_put(host_inputs, device),
                        max_rounds=max_rounds)
        return np.asarray(res.assigned)


def compare(label, got, ref):
    n = min(len(got), len(ref))
    diff = int((got[:n] != ref[:n]).sum())
    placed_got = int((got[:n] >= 0).sum())
    placed_ref = int((ref[:n] >= 0).sum())
    log(f"{label}: placed {placed_got} vs {placed_ref}, "
        f"rows differing {diff} of {n}")
    return placed_got, placed_ref, diff


def run_single(seed, platform, nodes=5000, pods=50_000, groups=500):
    """The default one-chip phase (sizes are parameters so the same code
    can be rehearsed at a small size on the host CPU)."""
    import jax

    from kube_batch_tpu.sim.invariants import InvariantChecker

    meter = CompileMeter()
    t0 = time.perf_counter()
    dep = Deployment(seed, nodes=nodes, pods=pods, groups=groups)
    log(f"smoke reading, not a benchmark: deployment of {pods} pods x "
        f"{nodes} nodes ingested in {time.perf_counter() - t0:.3f} s")
    checker = InvariantChecker()
    wave = max(1, groups // 100)
    with SolveCapture() as capture:
        try:
            run_cycle("cold-burst", dep.sched.run_once, dep, checker, 0,
                      meter, platform, pods)
            cold_inputs, cold_assigned = capture.host_inputs, capture.assigned
            # The first wave after the burst patches every node row; the
            # warm-up wave brings the steady churn-sized buckets, so the
            # repeat must compile nothing.
            for cycle, label in enumerate(
                ("arrival-wave", "arrival-wave-warmup",
                 "arrival-wave-repeat"),
                start=1,
            ):
                dep.add_gangs("wave", wave)
                repeat = run_cycle(label, dep.sched.run_once, dep, checker,
                                   cycle, meter, platform,
                                   wave * dep.per_group)
            if repeat["jit_variants_added"] > 0 or repeat["backend_compiles"]:
                raise SmokeFailure(
                    "warm arrival wave compiled: "
                    f"{repeat['jit_variants_added']} jit variants, "
                    f"{repeat['backend_compiles']} backend compiles")
            dep.add_gangs("micro", wave)
            run_cycle("micro", dep.sched.run_micro, dep, checker, 4, meter,
                      platform, wave * dep.per_group)
        finally:
            dep.close()
    total = dep.bound_pods()
    if total != pods + 4 * wave * dep.per_group:
        raise SmokeFailure(f"{total} pods bound in all")
    got, ref, diff = compare(
        "cold-burst vs host-CPU solve of the same snapshot",
        cold_assigned,
        solve_on(jax.devices("cpu")[0], cold_inputs, capture.max_rounds),
    )
    if got != ref or got != pods:
        raise SmokeFailure(
            f"cold burst placed {got}, the CPU reference {ref}, of {pods}")
    return {"cold_rows_differing_from_cpu": diff}


def run_multichip(seed, platform, nodes=10_000, pods=100_000, groups=1000):
    """The ``--chips 4`` phase: flat vs single-device, then two-level on
    the same seed's deployment (two-level is quality-approximate, so its
    placed count is printed and its invariants checked)."""
    import jax
    import numpy as np

    from kube_batch_tpu.sim.invariants import InvariantChecker
    from kube_batch_tpu.solver import sharding

    meter = CompileMeter()
    placed = {}
    with SolveCapture() as capture:
        for mode in ("flat", "two-level"):
            t0 = time.perf_counter()
            dep = Deployment(seed, nodes=nodes, pods=pods, groups=groups)
            log(f"smoke reading, not a benchmark: deployment of {pods} "
                f"pods x {nodes} nodes ingested in "
                f"{time.perf_counter() - t0:.3f} s")
            if mode == "two-level":
                os.environ["KBT_SPARSE_SHARD_MODE"] = mode
            try:
                run_cycle(f"{mode}-cold-burst", dep.sched.run_once, dep,
                          InvariantChecker(), 0, meter, platform,
                          pods if mode == "flat" else None)
                disp = dict(sharding.last_dispatch)
                if disp.get("mode") != mode or disp.get("shards") != 4:
                    raise SmokeFailure(f"{mode}: dispatch was {disp}")
                placed[mode] = dep.bound_pods()
                if mode == "flat":
                    _, _, diff = compare(
                        "flat 4-chip vs single-device solve on chip 0",
                        capture.assigned,
                        solve_on(jax.devices()[0], capture.host_inputs,
                                 capture.max_rounds),
                    )
                    if diff:
                        raise SmokeFailure(
                            f"flat differs from single-device in {diff} "
                            "rows")
            finally:
                os.environ.pop("KBT_SPARSE_SHARD_MODE", None)
                dep.close()
    mesh = sharding.default_mesh()
    log("rack_perm from device coords: " + json.dumps({
        "coords": [list(getattr(d, "coords", None) or [])
                   for d in np.asarray(mesh.devices).flat],
        "rack_perm": sharding.rack_perm(mesh).tolist(),
    }))
    log(f"placed: flat {placed['flat']}, two-level {placed['two-level']} "
        f"of {pods}")
    return placed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if args.chips == 1:
        pin_first_chip()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (default platform {platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "devices are visible", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from kube_batch_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()
    try:
        if args.chips == 4:
            run_multichip(args.seed, platform)
        else:
            run_single(args.seed, platform)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
