#!/usr/bin/env python
"""Cluster e2e driver — the hack/run-e2e-kind.sh analog (reference
hack/run-e2e-kind.sh:46-82: bring up a cluster, install CRDs + default
queue, run the scheduler binary against it, run a gang spec, tear down).

Fake mode (default, no cluster needed): starts the in-repo fake
Kubernetes API server (kube_batch_tpu.utils.fake_kube — the kubemark
analog: real scheduler, simulated kubelet), writes a kubeconfig, launches
the REAL scheduler CLI (``python -m kube_batch_tpu --kubeconfig ...``) as
a subprocess, seeds a queue, nodes, and a minMember=3 gang through the
API, and asserts all three pods get Binding-POSTed and flip Running.

Real mode: point hack/run-e2e.sh at a kubeconfig — it applies
config/crds/ + the default queue with kubectl and runs this flow against
the live API server.

Usage: python tools/run_e2e.py [--pods N] [--min-member M] [--timeout S]
Exit code 0 = gang scheduled; 1 = failure (scheduler log tail printed).
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from kube_batch_tpu.utils.fake_kube import (  # noqa: E402
    GROUP,
    FakeKube,
    node_doc,
    pod_doc,
)


def write_kubeconfig(path: str, server: str) -> None:
    cfg = {
        "apiVersion": "v1",
        "kind": "Config",
        "current-context": "e2e",
        "contexts": [
            {"name": "e2e", "context": {"cluster": "e2e", "user": "e2e"}}
        ],
        "clusters": [{"name": "e2e", "cluster": {"server": server}}],
        "users": [{"name": "e2e", "user": {}}],
    }
    with open(path, "w") as f:
        json.dump(cfg, f)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pods", type=int, default=3)
    ap.add_argument("--min-member", type=int, default=3)
    ap.add_argument("--timeout", type=float, default=60.0)
    ap.add_argument("--conf", default=os.path.join(
        REPO, "config", "tpu-batch-conf.yaml"
    ))
    args = ap.parse_args()

    fake = FakeKube()
    print(f"fake API server: {fake.url}")

    # Default queue (reference config/queue/default.yaml).
    fake.create("Queue", {
        "apiVersion": f"{GROUP}/v1alpha1", "kind": "Queue",
        "metadata": {"name": "default"}, "spec": {"weight": 1},
    })
    for i in range(2):
        fake.create("Node", node_doc(f"n{i}", cpu="4"))

    kubeconfig = tempfile.NamedTemporaryFile(
        suffix=".kubeconfig", delete=False
    )
    kubeconfig.close()
    write_kubeconfig(kubeconfig.name, fake.url)

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
    })
    log = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".log", delete=False
    )
    sched = subprocess.Popen(
        [sys.executable, "-m", "kube_batch_tpu",
         "--kubeconfig", kubeconfig.name,
         "--scheduler-conf", args.conf,
         "--listen-address", "127.0.0.1:0",
         "--schedule-period", "0.5"],
        stdout=log, stderr=subprocess.STDOUT, env=env, cwd=REPO,
    )
    try:
        time.sleep(1.0)  # let list+watch establish

        # The gang spec (reference example/job.yaml: one PodGroup,
        # minMember=3, one queue).
        fake.create("PodGroup", {
            "apiVersion": f"{GROUP}/v1alpha1", "kind": "PodGroup",
            "metadata": {"name": "e2e-gang", "namespace": "default"},
            "spec": {"minMember": args.min_member, "queue": "default"},
        })
        for i in range(args.pods):
            fake.create(
                "Pod", pod_doc(f"e2e-p{i}", group="e2e-gang")
            )

        deadline = time.time() + args.timeout
        while time.time() < deadline:
            if sched.poll() is not None:
                print("FAIL: scheduler exited early")
                break
            with fake.lock:
                done = len(fake.bindings) >= args.pods
                running = sum(
                    1 for p in fake.objects["Pod"].values()
                    if p["status"]["phase"] == "Running"
                )
            if done and running >= args.pods:
                print(
                    f"PASS: {len(fake.bindings)}/{args.pods} pods bound "
                    f"and Running: {sorted(fake.bindings)}"
                )
                return 0
            time.sleep(0.2)
        print(f"FAIL: bindings after {args.timeout}s: {fake.bindings}")
        log.flush()
        with open(log.name) as f:
            tail = f.read()[-3000:]
        print("--- scheduler log tail ---")
        print(tail)
        return 1
    finally:
        sched.terminate()
        try:
            sched.wait(10)
        except subprocess.TimeoutExpired:
            sched.kill()
        fake.close()
        os.unlink(kubeconfig.name)


if __name__ == "__main__":
    sys.exit(main())
