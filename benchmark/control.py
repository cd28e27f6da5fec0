"""The control: the plain reference placer put in the scheduler's place,
with one guarantee of the configuration broken (it ignores cpu), so that
the comparison that decides ``correct`` is shown to fail.

The benchmark's own runs never use it. On the chip, at a cell's own size:

    python benchmark/control.py --workload k8s5k.burst --seeds 1,2,3 \
        --seconds 10

prints, per seed, the numbers compared and whether the run came out
correct (it must not). ``benchmark/tests`` runs it at a small size.
"""

import argparse
import json
import os
import sys
import time

import reference
from gen import node_allocatable


def _place_pending(dep, check_cpu):
    """One pass of the reference placer over every pending pod of the
    live gangs, binding through the cluster API as a scheduler would."""
    cfg = dep.cfg
    live = list(dep.live.values())
    pod_req, bound, gangs = {}, {}, []
    for gang, _, pods in live:
        members = []
        for pod, cpu, mem in zip(pods, gang.cpu_milli, gang.mem_mi):
            name = pod.metadata.name
            pod_req[name] = (int(cpu), int(mem))
            if pod.spec.node_name:
                bound[name] = pod.spec.node_name
            members.append((name, int(cpu), int(mem)))
        gangs.append((gang.queue, gang.min_member, members))
    free = reference.free_capacity(node_allocatable(cfg), cfg["nodes"],
                                   bound, pod_req)
    by_name = {p.metadata.name: p for _, _, pods in live for p in pods}
    for name, j in reference.greedy_place(gangs, bound, free,
                                          check_cpu=check_cpu):
        dep.cluster.bind_pod(by_name[name], f"n{j}")


def burst_control(dep):
    """In place of ``Scheduler.run_once``."""
    _place_pending(dep, check_cpu=False)


def steady_control(dep, stop):
    """In place of ``Scheduler.run``: place what is pending every 50 ms."""
    while not stop.wait(0.05):
        _place_pending(dep, check_cpu=False)


CONTROLS = {"burst": burst_control, "steady": steady_control}


def main(argv=None):
    import run as harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell, cfg, mix = harness.cell_plan(bench, args.workload)
    harness.pin_chips(cell["chips"])
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, harness.ROOT)
    harness.use_compile_cache()
    import drivers

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run = drivers.DRIVERS[mix["kind"]](
            cfg, mix, seed, args.seconds, 0, "tpu", t0,
            os.path.join(harness.TRACE_ROOT, "control"),
            place=CONTROLS[mix["kind"]])
        print(json.dumps({
            "workload": args.workload, "seed": seed, "control": True,
            "correct": all(v == 0 for v in run.checks.values()),
            "checks": run.checks,
            "attempted": run.attempted, "failed": run.failed,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
