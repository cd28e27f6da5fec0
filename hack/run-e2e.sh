#!/usr/bin/env bash
# Cluster e2e runner — the reference hack/run-e2e-kind.sh analog
# (/root/reference/hack/run-e2e-kind.sh:46-82: cluster up, CRDs +
# default queue installed, scheduler launched against it, spec run,
# teardown).
#
# Fake mode (default, zero dependencies):
#   ./hack/run-e2e.sh
#   Starts the in-repo fake Kubernetes API server (the kubemark analog)
#   and drives the real scheduler CLI against it via tools/run_e2e.py.
#
# Real-cluster mode:
#   KUBECONFIG=~/.kube/config ./hack/run-e2e.sh real
#   Requires kubectl. Installs the CRDs and default queue, launches the
#   scheduler against the cluster, applies a minMember=3 gang, waits for
#   it to run, and tears the test resources down. Works against any
#   conformant cluster (kind: `kind create cluster` first).
set -euo pipefail
cd "$(dirname "$0")/.."

MODE="${1:-fake}"

if [ "$MODE" = "fake" ]; then
    exec env JAX_PLATFORMS=cpu \
        python tools/run_e2e.py "${@:2}"
fi

[ "$MODE" = "real" ] || { echo "usage: $0 [fake|real]" >&2; exit 2; }
: "${KUBECONFIG:?real mode needs KUBECONFIG}"
command -v kubectl >/dev/null || { echo "kubectl not found" >&2; exit 2; }

NS=tpu-batch-e2e
cleanup() {
    kubectl delete namespace "$NS" --ignore-not-found >/dev/null 2>&1 || true
    [ -n "${SCHED_PID:-}" ] && kill "$SCHED_PID" 2>/dev/null || true
}
trap cleanup EXIT

# CRDs + default queue (reference run-e2e-kind.sh:70-79).
kubectl apply -f config/crds/
kubectl apply -f - <<'YAML'
apiVersion: scheduling.incubator.k8s.io/v1alpha1
kind: Queue
metadata:
  name: default
spec:
  weight: 1
YAML

# Scheduler against the cluster (reference run-e2e-kind.sh:82).
env JAX_PLATFORMS=cpu \
    python -m kube_batch_tpu \
    --kubeconfig "$KUBECONFIG" \
    --scheduler-conf config/tpu-batch-conf.yaml \
    --listen-address 127.0.0.1:0 &
SCHED_PID=$!

kubectl create namespace "$NS"
kubectl apply -n "$NS" -f - <<'YAML'
apiVersion: scheduling.incubator.k8s.io/v1alpha1
kind: PodGroup
metadata:
  name: e2e-gang
spec:
  minMember: 3
  queue: default
YAML
for i in 0 1 2; do
kubectl apply -n "$NS" -f - <<YAML
apiVersion: v1
kind: Pod
metadata:
  name: e2e-p$i
  annotations:
    scheduling.k8s.io/group-name: e2e-gang
spec:
  schedulerName: tpu-batch
  containers:
  - name: main
    image: registry.k8s.io/pause:3.9
    resources:
      requests: {cpu: 100m, memory: 64Mi}
YAML
done

echo "waiting for the gang to schedule..."
for _ in $(seq 60); do
    n=$(kubectl get pods -n "$NS" \
        -o jsonpath='{range .items[*]}{.spec.nodeName}{"\n"}{end}' \
        | grep -c . || true)
    [ "$n" -ge 3 ] && { echo "PASS: $n/3 pods scheduled"; exit 0; }
    sleep 2
done
echo "FAIL: gang did not schedule in 120s" >&2
kubectl get pods -n "$NS" -o wide >&2
exit 1
