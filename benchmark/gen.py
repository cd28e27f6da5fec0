"""The deployment and its gangs, generated from a configuration file and a
seed, and created through the cluster API as a client would create them.

Copied from ``chip_smoke.Deployment`` and ``bench.build_cluster``'s request
mix (PR 21) so that a later change to the program does not move this
yardstick. The only program objects used here are the system under test
(cluster, cache, scheduler) and its API object types.
"""

import threading

import numpy as np

NS = "bench"


class Gang:
    """One PodGroup and its pods, as generated (not as scheduled)."""

    __slots__ = ("name", "queue", "min_member", "cpu_milli", "mem_mi")

    def __init__(self, name, queue, min_member, cpu_milli, mem_mi):
        self.name = name
        self.queue = queue
        self.min_member = min_member
        self.cpu_milli = cpu_milli  # int array, one entry per pod
        self.mem_mi = mem_mi

    def pod_names(self):
        return [f"{self.name}-{i}" for i in range(len(self.cpu_milli))]


class GangSource:
    """Draws gangs of the configuration's job mix from one generator."""

    def __init__(self, cfg, rng):
        self.cfg = cfg
        self.rng = rng
        self.count = 0

    def draw(self, prefix, n):
        cfg, rng = self.cfg, self.rng
        size = cfg["pods_per_group"]
        lo, hi = cfg["min_member"]
        cpus = rng.choice(np.asarray(cfg["cpu_milli"]), size=(n, size))
        mems = rng.choice(np.asarray(cfg["memory_mi"]), size=(n, size))
        mins = rng.integers(lo, hi + 1, size=n)
        queues = len(cfg["queue_weights"])
        out = []
        for g in range(n):
            k = self.count + g
            out.append(Gang(f"{prefix}{k}", f"q{k % queues}", int(mins[g]),
                            cpus[g], mems[g]))
        self.count += n
        return out


def node_allocatable(cfg):
    """(cpu milli, memory MiB, pods) that each node allocates."""
    node = cfg["node"]
    cpu = node["cpu"]
    cpu_milli = int(cpu[:-1]) if cpu.endswith("m") else int(cpu) * 1000
    mem = node["memory"]
    units = {"Gi": 1024, "Mi": 1, "Ti": 1024 * 1024}
    mem_mi = int(mem[:-2]) * units[mem[-2:]]
    return cpu_milli, mem_mi, int(node["pods"])


def queue_weights(cfg):
    """Queue name -> weight, as the deployment creates its queues."""
    return {f"q{q}": w for q, w in enumerate(cfg["queue_weights"])}


class Deployment:
    """An in-process cluster, its scheduler cache and a Scheduler, wired as
    ``cli/server.py`` wires them. Nodes and queues exist from the start;
    gangs are created with :meth:`create`. The cache is not started here:
    the burst driver starts it, the steady driver lets ``Scheduler.run``
    start it, as the server does."""

    def __init__(self, cfg, scheduler_period=1.0):
        from kube_batch_tpu.api import (
            Node, NodeSpec, NodeStatus, ObjectMeta, Queue, QueueSpec,
            build_resource_list,
        )
        from kube_batch_tpu.cache import new_scheduler_cache
        from kube_batch_tpu.cluster import InProcessCluster
        from kube_batch_tpu.scheduler import Scheduler

        self.cfg = cfg
        self.stop = threading.Event()
        self.cluster = InProcessCluster(simulate_kubelet=True)
        self.cache = new_scheduler_cache(self.cluster, "tpu-batch", "default")
        self.sched = Scheduler(self.cache, scheduler_conf=cfg["scheduler_conf"],
                               schedule_period=scheduler_period)
        for q, weight in enumerate(cfg["queue_weights"]):
            self.cluster.create_queue(Queue(
                metadata=ObjectMeta(name=f"q{q}", namespace=""),
                spec=QueueSpec(weight=weight),
            ))
        node = cfg["node"]
        alloc = build_resource_list(cpu=node["cpu"], memory=node["memory"],
                                    pods=node["pods"])
        for j in range(cfg["nodes"]):
            self.cluster.create_node(Node(
                metadata=ObjectMeta(name=f"n{j}"),
                spec=NodeSpec(),
                status=NodeStatus(allocatable=dict(alloc),
                                  capacity=dict(alloc)),
            ))
        self.live = {}  # gang name -> (Gang, PodGroup, [Pod])

    def create(self, gang):
        """Create one gang through the cluster API: its PodGroup, then its
        pods, each ingested by the cache's watch as it is created."""
        from kube_batch_tpu.api import (
            GROUP_NAME_ANNOTATION_KEY, Container, ObjectMeta, Pod, PodGroup,
            PodGroupSpec, PodPhase, PodSpec, PodStatus,
        )

        pg = PodGroup(
            metadata=ObjectMeta(name=gang.name, namespace=NS),
            spec=PodGroupSpec(min_member=gang.min_member, queue=gang.queue),
        )
        self.cluster.create_pod_group(pg)
        pods = []
        for i, (cpu, mem) in enumerate(zip(gang.cpu_milli, gang.mem_mi)):
            name = f"{gang.name}-{i}"
            pod = Pod(
                metadata=ObjectMeta(
                    name=name, namespace=NS, uid=f"{NS}-{name}",
                    annotations={GROUP_NAME_ANNOTATION_KEY: gang.name},
                ),
                spec=PodSpec(containers=[Container(requests={
                    "cpu": f"{int(cpu)}m", "memory": f"{int(mem)}Mi",
                })]),
                status=PodStatus(phase=PodPhase.PENDING),
            )
            self.cluster.create_pod(pod)
            pods.append(pod)
        self.live[gang.name] = (gang, pg, pods)

    def delete(self, name):
        """Delete a gang as a completed job is deleted: its pods, then its
        PodGroup."""
        _, pg, pods = self.live.pop(name)
        for pod in pods:
            self.cluster.delete_pod(pod)
        self.cluster.delete("PodGroup", pg)

    def close(self):
        self.stop.set()
        self.cache.shutdown()
