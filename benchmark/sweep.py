"""The knee sweep of a steady cell, run once on the chip to fix its rate.

    python benchmark/sweep.py --workload k8s5k.steady --rates 8,16,24 \
        --seconds 20 --seed 1

runs the cell's steady driver once per offered gang rate, each on a fresh
deployment in this one process, and prints per rate the pending backlog at
the window's start and end, the gang start latencies and the bind rate.
The knee is the highest rate whose backlog does not grow across the
window; the cell's traffic file then holds four fifths of it, as a
number. The benchmark's runs never call this.
"""

import argparse
import json
import os
import sys
import time

import numpy as np


def main(argv=None):
    import run as harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell, cfg, mix = harness.cell_plan(bench, args.workload)
    harness.pin_chips(cell["chips"])
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep.py: no TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, harness.ROOT)
    harness.use_compile_cache()
    import drivers

    for rate in (float(r) for r in args.rates.split(",")):
        run = drivers.run_steady(
            cfg, dict(mix, gang_rate_per_s=rate), args.seed, args.seconds,
            0, "tpu", time.perf_counter(),
            os.path.join(harness.TRACE_ROOT, "sweep"))
        lat = np.asarray([g["latency"] for g in run.gangs])
        print(json.dumps({
            "rate_gangs_per_s": rate,
            "pending_pods_at_start": run.notes["pending_pods_at_start"],
            "pending_pods_at_end": run.notes["pending_pods_at_end"],
            "gang_start_p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "gang_start_p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "pods_bound_per_s": run.binds_in_window / args.seconds,
            "generator_lag_p99_ms": float(np.percentile(
                np.asarray(run.gen_lag_s), 99)) * 1e3,
            "checks": run.checks,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
