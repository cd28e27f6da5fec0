#!/usr/bin/env python
"""Headline-scale (50k x 5k) sharded-solve stage of the multi-chip proof.

Separate from ``__graft_entry__.dryrun_multichip`` because at this scale
the four solves plus compiles take ~5-7 minutes on the 1-core CPU mesh —
too slow for the driver's dryrun budget. Run manually:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python tools/multichip_50k.py --out MULTICHIP_50K_r05.json

Asserts bit-exact placement parity between the single-device staged
solver and the hierarchical sharded solver (solver/spmd.py) and records
interleaved wall times. On a 1-core host the 8 virtual devices
serialize, so the sharded number measures pure sharding overhead — the
[T, N/s] blocks sum to the same work; real ICI runs them in parallel.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args()

    from kube_batch_tpu.utils.backend import force_cpu_devices

    # A virtual CPU mesh: must precede the first backend resolution.
    if not force_cpu_devices(args.devices):
        raise SystemExit("CPU mesh unavailable (jax already initialized)")

    import jax
    import numpy as np
    from jax.sharding import Mesh

    import __graft_entry__ as g
    from kube_batch_tpu.solver import plan_for, solve_staged_jit, solve_sharded

    big = g._synthetic_inputs(T=50_000, N=5_120, R=3, Q=5, J=2000, seed=2)
    mesh = Mesh(np.asarray(jax.devices()[: args.devices]), ("nodes",))
    plan = dataclasses.replace(plan_for(big, mesh), staged=True)

    # Warm both compiles, then interleave best-of-2 (noisy box).
    single = jax.block_until_ready(solve_staged_jit(big, max_rounds=64))
    sharded = jax.block_until_ready(
        solve_sharded(big, plan, max_rounds=64)
    )
    t_single, t_sharded = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        single = jax.block_until_ready(solve_staged_jit(big, max_rounds=64))
        t_single.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        sharded = jax.block_until_ready(
            solve_sharded(big, plan, max_rounds=64)
        )
        t_sharded.append(time.perf_counter() - t0)

    a1 = np.asarray(single.assigned)
    a2 = np.asarray(sharded.assigned)
    parity = bool((a1 == a2).all())
    assert parity, f"{int((a1 != a2).sum())} rows diverge"
    out = {
        "shape": [50_000, 5_120],
        "devices": args.devices,
        "placed": int((a2 >= 0).sum()),
        "parity_with_single_device": parity,
        "rounds": int(sharded.rounds),
        "stages": int(sharded.stages),
        "single_device_staged_solve_s": round(min(t_single), 2),
        "sharded_staged_solve_s": round(min(t_sharded), 2),
        "sharded_impl": "spmd-hierarchical",
        "host_cpu_count": os.cpu_count(),
        "recorded": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    line = json.dumps(out)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
