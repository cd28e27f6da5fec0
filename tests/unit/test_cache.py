"""SchedulerCache tests (port of reference cache/cache_test.go:128-309)."""

import pytest

from kube_batch_tpu.api import (
    ObjectMeta,
    PodPhase,
    PriorityClass,
    TaskStatus,
    build_resource_list,
)
from kube_batch_tpu.cache import SchedulerCache, shadow_pod_group
from kube_batch_tpu.cluster import ClusterAPI, InProcessCluster
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


def make_cache(**kwargs):
    return SchedulerCache(
        binder=FakeBinder(),
        evictor=FakeEvictor(),
        status_updater=FakeStatusUpdater(),
        volume_binder=FakeVolumeBinder(),
        **kwargs,
    )


class _PooledInProcessCluster(InProcessCluster):
    """An in-process cluster that leaves ``bind_is_local`` at the
    ClusterAPI default, so the cache fans bind chunks over its pool."""

    bind_is_local = ClusterAPI.bind_is_local


def req_resource():
    from kube_batch_tpu.api import Resource
    return Resource(milli_cpu=500, memory=256 * 2**20)


def req(cpu="1", mem="1Gi"):
    return build_resource_list(cpu=cpu, memory=mem)


class TestIngest:
    def test_add_pod_creates_shadow_job(self):
        # reference cache_test.go TestAddPod: pods without a group get a
        # shadow PodGroup keyed by owner/pod UID on the default queue.
        c = make_cache()
        owner = "owner-1"
        p1 = build_pod("c1", "p1", "", PodPhase.PENDING, req(), owner_uid=owner)
        p2 = build_pod("c1", "p2", "n1", PodPhase.RUNNING, req(), owner_uid=owner)
        c.add_node(build_node("n1", build_resource_list(cpu="2", memory="2Gi")))
        c.add_pod(p1)
        c.add_pod(p2)
        assert owner in c.jobs
        job = c.jobs[owner]
        assert len(job.tasks) == 2
        assert shadow_pod_group(job.pod_group)
        assert job.queue == "default"
        assert c.nodes["n1"].used.milli_cpu == 1000

    def test_add_node_with_existing_bound_pods(self):
        # reference cache_test.go TestAddNode: bound pod arrives before node
        c = make_cache()
        p = build_pod("c1", "p1", "n1", PodPhase.RUNNING, req())
        c.add_pod(p)
        # node exists as placeholder, not ready
        assert not c.nodes["n1"].ready()
        c.add_node(build_node("n1", build_resource_list(cpu="2", memory="2Gi")))
        ni = c.nodes["n1"]
        assert ni.ready()
        assert ni.idle.milli_cpu == 1000
        assert ni.used.milli_cpu == 1000

    def test_pod_group_attaches_to_job(self):
        c = make_cache()
        c.add_pod_group(build_pod_group("pg1", namespace="ns", min_member=3))
        c.add_pod(
            build_pod("ns", "p1", "", PodPhase.PENDING, req(), group_name="pg1")
        )
        job = c.jobs["ns/pg1"]
        assert job.min_available == 3
        assert len(job.tasks) == 1
        assert not shadow_pod_group(job.pod_group)

    def test_pod_group_empty_queue_gets_default(self):
        c = make_cache()
        pg = build_pod_group("pg1", namespace="ns", queue="")
        c.add_pod_group(pg)
        assert c.jobs["ns/pg1"].queue == "default"

    def test_other_scheduler_pending_pod_ignored(self):
        c = make_cache()
        p = build_pod("c1", "p1", "", PodPhase.PENDING, req())
        p.spec.scheduler_name = "default-scheduler"
        c.add_pod(p)
        assert not c.jobs

    def test_other_scheduler_running_pod_occupies_node(self):
        c = make_cache()
        c.add_node(build_node("n1", build_resource_list(cpu="2", memory="2Gi")))
        p = build_pod("c1", "p1", "n1", PodPhase.RUNNING, req())
        p.spec.scheduler_name = "default-scheduler"
        c.add_pod(p)
        assert not c.jobs  # no job tracked...
        assert c.nodes["n1"].used.milli_cpu == 1000  # ...but resources held

    def test_update_pod_rebinds_accounting(self):
        c = make_cache()
        c.add_node(build_node("n1", build_resource_list(cpu="4", memory="4Gi")))
        old = build_pod("ns", "p1", "", PodPhase.PENDING, req(), group_name="pg1")
        c.add_pod_group(build_pod_group("pg1", namespace="ns"))
        c.add_pod(old)
        new = build_pod("ns", "p1", "n1", PodPhase.RUNNING, req(), group_name="pg1")
        new.metadata.uid = old.metadata.uid
        c.update_pod(old, new)
        job = c.jobs["ns/pg1"]
        assert len(job.tasks) == 1
        assert job.tasks[old.metadata.uid].status == TaskStatus.RUNNING
        assert job.total_request.milli_cpu == 1000  # no double count
        assert c.nodes["n1"].used.milli_cpu == 1000

    def test_delete_pod(self):
        c = make_cache()
        c.add_node(build_node("n1", build_resource_list(cpu="4", memory="4Gi")))
        p = build_pod("ns", "p1", "n1", PodPhase.RUNNING, req(), group_name="pg1")
        c.add_pod_group(build_pod_group("pg1", namespace="ns"))
        c.add_pod(p)
        c.delete_pod(p)
        assert not c.jobs["ns/pg1"].tasks
        assert c.nodes["n1"].used.milli_cpu == 0

    def test_queue_ingest(self):
        c = make_cache()
        c.add_queue(build_queue("q1", weight=4))
        assert c.queues["q1"].weight == 4
        c.delete_queue(build_queue("q1"))
        assert "q1" not in c.queues


class TestSnapshot:
    def test_snapshot_is_deep_clone(self):
        c = make_cache()
        c.add_node(build_node("n1", build_resource_list(cpu="4", memory="4Gi")))
        c.add_pod_group(build_pod_group("pg1", namespace="ns"))
        c.add_pod(build_pod("ns", "p1", "", PodPhase.PENDING, req(), group_name="pg1"))
        snap = c.snapshot()
        task = next(iter(snap.jobs["ns/pg1"].tasks.values()))
        snap.jobs["ns/pg1"].update_task_status(task, TaskStatus.ALLOCATED)
        snap.nodes["n1"].idle.sub(task.resreq)
        # cache unchanged
        cache_task = c.jobs["ns/pg1"].tasks[task.uid]
        assert cache_task.status == TaskStatus.PENDING
        assert c.nodes["n1"].idle.milli_cpu == 4000

    def test_snapshot_mutation_detector(self):
        """Cache-mutation tripwire (the analog of the client-go cache
        mutation detector the reference enables in unit tests,
        hack/make-rules/test.sh:26-28): aggressively mutate every
        reachable aggregate of a snapshot — node vectors, task clones,
        job aggregates, queue weights — and assert the cache's state is
        bit-identical afterwards."""
        c = make_cache()
        c.add_queue(build_queue("q1", weight=2))
        c.add_node(build_node("n1", build_resource_list(cpu="4", memory="4Gi")))
        c.add_pod_group(build_pod_group("pg1", namespace="ns", queue="q1"))
        c.add_pod(build_pod("ns", "p1", "n1", PodPhase.RUNNING, req(),
                            group_name="pg1"))
        c.add_pod(build_pod("ns", "p2", "", PodPhase.PENDING, req(),
                            group_name="pg1"))

        def fingerprint():
            n = c.nodes["n1"]
            j = c.jobs["ns/pg1"]
            return (
                n.idle.milli_cpu, n.idle.memory, n.used.milli_cpu,
                n.releasing.milli_cpu, n.allocatable.milli_cpu,
                sorted(n.tasks), n.state.phase,
                j.total_request.milli_cpu, j.allocated.milli_cpu,
                sorted(j.tasks),
                {s: sorted(t) for s, t in j.task_status_index.items()},
                c.queues["q1"].weight,
            )

        before = fingerprint()
        snap = c.snapshot()
        node = snap.nodes["n1"]
        node.idle.sub(req_resource())
        node.used.add(req_resource())
        node.releasing.add(req_resource())
        node.allocatable.milli_cpu = 0
        node.state.phase = "NotReady"
        for t in node.tasks.values():
            t.status = TaskStatus.RELEASING
            # Task request vectors are FROZEN (shared across clones);
            # mutation attempts must raise instead of corrupting every
            # holder — the strongest form of the tripwire.
            with pytest.raises(TypeError):
                t.resreq.milli_cpu = 99999
            with pytest.raises(TypeError):
                t.resreq.add(req_resource())
        job = snap.jobs["ns/pg1"]
        job.total_request.add(req_resource())
        job.allocated.add(req_resource())
        pending = [
            t for t in job.tasks.values()
            if t.status == TaskStatus.PENDING
        ]
        job.update_task_status(pending[0], TaskStatus.ALLOCATED)
        for t in job.tasks.values():
            with pytest.raises(TypeError):
                t.resreq.scalar_resources = {"x": 1.0}
        for q in snap.queues.values():
            q.weight = 99
        assert fingerprint() == before

    def test_frozen_scalar_dict_rejects_entry_mutation(self):
        """In-place dict-entry writes on a frozen request vector must
        raise too (clones share the dict via MappingProxyType)."""
        c = make_cache()
        c.add_pod_group(build_pod_group("pg1", namespace="ns"))
        c.add_pod(build_pod(
            "ns", "p1", "", PodPhase.PENDING,
            build_resource_list(cpu="1", **{"nvidia.com/gpu": 1}),
            group_name="pg1"))
        snap = c.snapshot()
        t = next(iter(snap.jobs["ns/pg1"].tasks.values()))
        assert t.resreq.scalar_resources
        with pytest.raises(TypeError):
            t.resreq.scalar_resources["nvidia.com/gpu"] = 99.0

    def test_snapshot_skips_not_ready_nodes_and_specless_jobs(self):
        c = make_cache()
        c.add_pod(build_pod("ns", "p1", "ghost", PodPhase.RUNNING, req(), group_name="pg"))
        snap = c.snapshot()
        assert "ghost" not in snap.nodes  # placeholder node is NotReady
        assert "ns/pg" not in snap.jobs  # no PodGroup → no scheduling spec

    def test_snapshot_resolves_priority_class(self):
        c = make_cache()
        c.add_priority_class(
            PriorityClass(metadata=ObjectMeta(name="high", namespace=""), value=100)
        )
        c.add_priority_class(
            PriorityClass(
                metadata=ObjectMeta(name="low", namespace=""),
                value=5,
                global_default=True,
            )
        )
        c.add_pod_group(
            build_pod_group("pg1", namespace="ns", priority_class_name="high")
        )
        c.add_pod_group(build_pod_group("pg2", namespace="ns"))
        snap = c.snapshot()
        assert snap.jobs["ns/pg1"].priority == 100
        assert snap.jobs["ns/pg2"].priority == 5  # global default


class TestSideEffects:
    def setup_bound_job(self, c):
        c.add_node(build_node("n1", build_resource_list(cpu="4", memory="4Gi")))
        c.add_pod_group(build_pod_group("pg1", namespace="ns"))
        p = build_pod("ns", "p1", "", PodPhase.PENDING, req(), group_name="pg1")
        c.add_pod(p)
        return c.jobs["ns/pg1"].tasks[p.metadata.uid]

    def test_bind(self):
        c = make_cache()
        task = self.setup_bound_job(c)
        c.bind(task, "n1")
        assert task.status == TaskStatus.BINDING
        assert task.node_name == "n1"
        assert c.nodes["n1"].used.milli_cpu == 1000
        # async binder fired
        key = c.binder.channel.get(timeout=3)
        assert c.binder.binds[key] == "n1"

    def test_bind_missing_host_raises(self):
        c = make_cache()
        task = self.setup_bound_job(c)
        with pytest.raises(KeyError):
            c.bind(task, "nope")

    def test_evict(self):
        c = make_cache()
        task = self.setup_bound_job(c)
        c.bind(task, "n1")
        c.evict(task, "preempted")
        assert task.status == TaskStatus.RELEASING
        assert c.nodes["n1"].releasing.milli_cpu == 1000
        key = c.evictor.channel.get(timeout=3)
        assert key == "ns/p1"

    def test_bind_batch_reverts_node_rejected_tasks(self):
        # A staged task the node's accounting rejects must not be left
        # wedged in BINDING with node_name set and no resync — it reverts
        # to its prior status so the next cycle can schedule it again.
        c = make_cache()
        c.add_node(build_node("n1", build_resource_list(cpu="1", memory="1Gi")))
        c.add_pod_group(build_pod_group("pg1", namespace="ns", min_member=2))
        pods = [
            build_pod("ns", f"p{i}", "", PodPhase.PENDING, req(),
                      group_name="pg1")
            for i in range(2)
        ]
        for p in pods:
            c.add_pod(p)
        tasks = [c.jobs["ns/pg1"].tasks[p.metadata.uid] for p in pods]
        # Session-side clones carry the solver's placement; the cache's
        # stored tasks still have node_name="" (the prior state a revert
        # must restore).
        infos = [t.clone() for t in tasks]
        for info in infos:
            info.node_name = "n1"  # both target n1; only one cpu fits
            info.volume_ready = True

        # bind_batch is optimistic (bookkeeping is deferred to the
        # side-effect pool); barrier before asserting mirror state.
        c.bind_batch(infos)
        assert c.wait_for_bookkeeping(timeout=10)
        assert {t.status for t in tasks} == {
            TaskStatus.BINDING, TaskStatus.PENDING
        }
        rejected = next(t for t in tasks if t.status == TaskStatus.PENDING)
        assert rejected.node_name == ""
        accepted = next(t for t in tasks if t.status == TaskStatus.BINDING)
        assert c.nodes["n1"].used.milli_cpu == 1000
        key = c.binder.channel.get(timeout=3)
        assert key == f"ns/{accepted.name}"

    def test_bind_batch_reverts_when_node_deleted_mid_flight(self):
        # A node-delete watch event can land in the async window between
        # dispatch and the deferred bookkeeping. The whole staged group
        # for that hostname must revert (not KeyError out and strand the
        # rest of the batch in BINDING with no log and no resync).
        c = make_cache()
        c.add_node(build_node("n1", build_resource_list(cpu="4", memory="8Gi")))
        c.add_node(build_node("n2", build_resource_list(cpu="4", memory="8Gi")))
        c.add_pod_group(build_pod_group("pg1", namespace="ns", min_member=2))
        pods = [
            build_pod("ns", f"p{i}", "", PodPhase.PENDING, req(),
                      group_name="pg1")
            for i in range(2)
        ]
        for p in pods:
            c.add_pod(p)
        tasks = [c.jobs["ns/pg1"].tasks[p.metadata.uid] for p in pods]
        infos = [t.clone() for t in tasks]
        infos[0].node_name = "n1"   # this node will vanish
        infos[1].node_name = "n2"   # this group must still bind
        for info in infos:
            info.volume_ready = True

        del c.nodes["n1"]  # simulate the delete landing first
        c.bind_batch(infos)
        assert c.wait_for_bookkeeping(timeout=10)
        assert tasks[0].status == TaskStatus.PENDING
        assert tasks[0].node_name == ""
        assert tasks[1].status == TaskStatus.BINDING
        assert c.nodes["n2"].used.milli_cpu == 1000
        key = c.binder.channel.get(timeout=3)
        assert key == "ns/p1"

    def test_bind_batch_on_accepted_sees_only_accepted(self):
        # Metrics hook: the callback fires with the subset whose
        # bookkeeping succeeded, not everything dispatched.
        c = make_cache()
        c.add_node(build_node("n1", build_resource_list(cpu="1", memory="1Gi")))
        c.add_pod_group(build_pod_group("pg1", namespace="ns", min_member=2))
        pods = [
            build_pod("ns", f"p{i}", "", PodPhase.PENDING, req(),
                      group_name="pg1")
            for i in range(2)
        ]
        for p in pods:
            c.add_pod(p)
        infos = [
            c.jobs["ns/pg1"].tasks[p.metadata.uid].clone() for p in pods
        ]
        for info in infos:
            info.node_name = "n1"  # only one cpu fits
            info.volume_ready = True
        seen = []
        c.bind_batch(infos, on_accepted=lambda acc: seen.append(list(acc)))
        assert c.wait_for_bookkeeping(timeout=10)
        assert len(seen) == 1
        assert len(seen[0]) == 1  # one accepted, one node-rejected

    def test_bind_batch_prewarns_snapshot_pool(self):
        # The deferred bookkeeping re-clones the jobs/nodes it dirtied
        # into the COW pool, so the NEXT snapshot reuses those clones
        # instead of re-cloning the world after a busy cycle (steady
        # open must scale with churn, not cluster size).
        c = make_cache()
        c.add_node(build_node("n1", build_resource_list(cpu="4", memory="8Gi")))
        c.add_pod_group(build_pod_group("pg1", namespace="ns", min_member=1))
        p = build_pod("ns", "p1", "", PodPhase.PENDING, req(),
                      group_name="pg1")
        c.add_pod(p)
        task = c.jobs["ns/pg1"].tasks[p.metadata.uid]
        info = task.clone()
        info.node_name = "n1"
        info.volume_ready = True

        c.bind_batch([info])
        assert c.wait_for_bookkeeping(timeout=10)
        prewarmed_job = c._snap_pool[0]["ns/pg1"][1]
        prewarmed_node = c._snap_pool[1]["n1"][1]
        snap = c.snapshot()
        assert snap.jobs["ns/pg1"] is prewarmed_job
        assert snap.nodes["n1"] is prewarmed_node
        # and the pre-warmed clone reflects the bookkeeping
        assert snap.jobs["ns/pg1"].tasks[task.uid].status \
            == TaskStatus.BINDING
        assert snap.nodes["n1"].used.milli_cpu == 1000


class TestSnapshotPool:
    """COW snapshot pool: unchanged objects are reused across consecutive
    snapshots; any mutation of source OR handed-out clone forces a fresh
    clone (so session state can never leak between cycles)."""

    def _cache(self):
        c = make_cache()
        c.add_queue(build_queue("q1", weight=1))
        for j in range(3):
            c.add_node(build_node(
                f"n{j}", build_resource_list(cpu="4", memory="8Gi")))
        for g in range(2):
            c.add_pod_group(build_pod_group(
                f"pg{g}", namespace="ns", queue="q1"))
            for i in range(2):
                c.add_pod(build_pod(
                    "ns", f"pg{g}-p{i}", "", PodPhase.PENDING, req(),
                    group_name=f"pg{g}"))
        return c

    def test_unchanged_objects_reused(self):
        c = self._cache()
        s1 = c.snapshot()
        s2 = c.snapshot()
        assert s2.jobs["ns/pg0"] is s1.jobs["ns/pg0"]
        assert s2.nodes["n0"] is s1.nodes["n0"]

    def test_clone_mutation_forces_fresh_clone(self):
        c = self._cache()
        s1 = c.snapshot()
        job = s1.jobs["ns/pg0"]
        task = next(iter(job.tasks.values()))
        job.update_task_status(task, TaskStatus.ALLOCATED)  # session-like
        s2 = c.snapshot()
        assert s2.jobs["ns/pg0"] is not job
        # and the fresh clone reflects CACHE truth, not the session edit
        t2 = s2.jobs["ns/pg0"].tasks[task.uid]
        assert t2.status == TaskStatus.PENDING

    def test_source_mutation_forces_fresh_clone(self):
        c = self._cache()
        s1 = c.snapshot()
        c.add_pod(build_pod("ns", "pg0-p9", "", PodPhase.PENDING, req(),
                            group_name="pg0"))
        s2 = c.snapshot()
        assert s2.jobs["ns/pg0"] is not s1.jobs["ns/pg0"]
        assert "pg0-p9" in {t.name for t in s2.jobs["ns/pg0"].tasks.values()}
        # untouched job still reused
        assert s2.jobs["ns/pg1"] is s1.jobs["ns/pg1"]

    def test_node_accounting_isolated_across_cycles(self):
        c = self._cache()
        s1 = c.snapshot()
        node = s1.nodes["n0"]
        task = next(iter(s1.jobs["ns/pg0"].tasks.values()))
        s1.jobs["ns/pg0"].update_task_status(task, TaskStatus.ALLOCATED)
        task.node_name = "n0"
        node.add_task(task)
        s2 = c.snapshot()
        assert s2.nodes["n0"] is not node
        assert s2.nodes["n0"].idle.milli_cpu == 4000

    def test_priority_class_change_invalidates(self):
        c = self._cache()
        c.add_pod_group(build_pod_group(
            "pgp", namespace="ns", queue="q1",
            priority_class_name="high"))
        c.add_pod(build_pod("ns", "pgp-p0", "", PodPhase.PENDING, req(),
                            group_name="pgp"))
        s1 = c.snapshot()
        assert s1.jobs["ns/pgp"].priority == 0
        c.add_priority_class(
            PriorityClass(metadata=ObjectMeta(name="high"), value=100)
        )
        s2 = c.snapshot()
        assert s2.jobs["ns/pgp"].priority == 100


class TestBindPathStages:
    """The bind path's stage counters, on a cluster whose bind delivers
    its watch event synchronously (the cache's ingest runs inside the
    bind call)."""

    @pytest.mark.parametrize(
        "cluster_cls", [InProcessCluster, _PooledInProcessCluster],
        ids=["ordered", "pooled"],
    )
    def test_bind_batch_chunk_spans_carry_nested_stages(self, cluster_cls):
        import threading

        from kube_batch_tpu.obs.tracer import TRACER

        cluster = cluster_cls(simulate_kubelet=True)
        cluster.create_queue(build_queue("default", 1))
        cluster.create_node(
            build_node("n1", build_resource_list(cpu="8", memory="16Gi")))
        cluster.create_pod_group(
            build_pod_group("pg1", namespace="ns", min_member=1))
        for i in range(6):
            cluster.create_pod(build_pod(
                "ns", f"p{i}", "", PodPhase.PENDING,
                build_resource_list(cpu="500m", memory="256Mi"),
                group_name="pg1"))
        c = SchedulerCache(cluster=cluster)
        c._BIND_CHUNK = 4
        stop = threading.Event()
        c.run(stop)
        try:
            assert c.wait_for_cache_sync(stop)
            infos = []
            for task in c.jobs["ns/pg1"].tasks.values():
                info = task.clone()
                info.node_name = "n1"
                info.volume_ready = True
                infos.append(info)
            TRACER.reset()
            TRACER.enable()
            try:
                with TRACER.span("cycle"):
                    c.bind_batch(infos)
                assert c.wait_for_side_effects(timeout=10)
            finally:
                TRACER.disable()
            events = TRACER.events()
            TRACER.reset()
        finally:
            stop.set()
        chunks = [e for e in events if e["name"] == "cache_side_effect"]
        if cluster_cls.bind_is_local:
            # one job drains both chunks
            (drain,) = chunks
            assert drain["args"]["ingest_n"] == 6
            assert drain["args"]["drain_chunk_n"] == 2
        else:
            assert sorted(e["args"]["ingest_n"] for e in chunks) == [2, 4]
            assert not any("drain_chunk_n" in e["args"] for e in chunks)
        for e in chunks:
            a = e["args"]
            # the cache's ingest runs inside the cluster's bind call
            assert a["bind_call_n"] == a["event_n"] == a["ingest_n"]
            assert a["ingest_s"] <= a["bind_call_s"]
            assert a["bind_call_s"] + a["ledgers_s"] + a["event_s"] \
                <= e["dur"] / 1e6
            assert a["ingest_cpu_s"] <= a["bind_call_cpu_s"]
            assert a["bind_call_cpu_s"] + a["ledgers_cpu_s"] \
                + a["event_cpu_s"] <= a["cpu_s"]
            assert a["mutex_wait_n"] == a["ingest_n"]
            assert "mutex_wait_cpu_s" not in a  # a lock wait is wall only
            # per bind, plus each chunk's journal-mark flush
            assert a["ledgers_n"] == a["ingest_n"] + a.get("drain_chunk_n", 1)
        (book,) = [e for e in events if e["name"] == "cache_bookkeeping"]
        assert book["args"]["cpu_s"] > 0 and book["args"]["ledgers_n"] == 2
        # staging hold, then the prewarm holds (one job, one node)
        assert book["args"]["mutex_wait_n"] == 3


def _gang_of(cluster, n, claim_pod=None):
    """Queue, one roomy node and a PodGroup of ``n`` pods ``p0``..; with
    ``claim_pod``, one more pod of that name holding an unbound claim."""
    cluster.create_queue(build_queue("default", 1))
    cluster.create_node(build_node("n1", build_resource_list(
        cpu="16", memory="32Gi", pods=110)))
    cluster.create_pod_group(
        build_pod_group("pg1", namespace="ns", min_member=1))
    for i in range(n):
        cluster.create_pod(build_pod(
            "ns", f"p{i}", "", PodPhase.PENDING,
            build_resource_list(cpu="500m", memory="256Mi"),
            group_name="pg1"))
    if claim_pod is not None:
        cluster.create_claim("ns", "c1", bound=False)
        pod = build_pod(
            "ns", claim_pod, "", PodPhase.PENDING,
            build_resource_list(cpu="500m", memory="256Mi"),
            group_name="pg1")
        pod.spec.volume_claims = ["c1"]
        cluster.create_pod(pod)


def _bind_infos(cache, names):
    """Session-side copies of the named tasks of ns/pg1, placed on n1, in
    the order given; a task with claims is not volume-ready."""
    with cache.mutex:
        by_name = {t.name: t for t in cache.jobs["ns/pg1"].tasks.values()}
    infos = []
    for name in names:
        info = by_name[name].clone()
        info.node_name = "n1"
        info.volume_ready = not info.pod.spec.volume_claims
        infos.append(info)
    return infos


def _count_submits(cache):
    """Record the ``bookkeeping`` flag of every side-effect job the cache
    submits from now on."""
    submitted = []
    submit = cache._submit_side_effect

    def spy(fn, bookkeeping=False):
        submitted.append(bookkeeping)
        submit(fn, bookkeeping)

    cache._submit_side_effect = spy
    return submitted


class TestOrderedBindDrain:
    """On a cluster whose binds are local CPU work, one side-effect job
    drains a batch's fast binds chunk by chunk, in batch order."""

    def test_fast_binds_drain_in_one_job_in_batch_order(self):
        import threading
        import time

        from kube_batch_tpu.cache.cache import DefaultVolumeBinder

        cluster = InProcessCluster(simulate_kubelet=True)
        _gang_of(cluster, 7, claim_pod="pv")
        cache = SchedulerCache(
            cluster=cluster,
            volume_binder=DefaultVolumeBinder(cluster, bind_timeout=30.0),
        )
        cache._BIND_CHUNK = 2  # 7 fast binds: 4 chunks
        cache.start_ingest()
        binds = []  # (pod name, rv, thread) per bind event, as delivered

        def on_event(kind, event_type, obj, rv):
            if kind == "Pod" and obj.spec.node_name:
                binds.append(
                    (obj.metadata.name, rv, threading.get_ident()))

        cluster.add_watch(on_event)
        # the claim-bearing pod sits mid-batch
        order = ["p5", "p0", "pv", "p3", "p6", "p1", "p4", "p2"]
        fast = [name for name in order if name != "pv"]
        submitted = _count_submits(cache)
        try:
            cache.bind_batch(_bind_infos(cache, order))
            # every fast bind lands while the claim still waits
            deadline = time.monotonic() + 10
            while len(binds) < len(fast) and time.monotonic() < deadline:
                time.sleep(0.01)
            assert [name for name, _, _ in binds] == fast
            assert cluster.get_pod("ns", "pv").spec.node_name == ""
            cluster.set_claim_bound("ns", "c1")
            assert cache.wait_for_side_effects(timeout=10)
        finally:
            cache.shutdown()
        # the bookkeeping job, ONE drain job, the claim's own job
        assert submitted == [True, False, False]
        fast_binds = binds[:len(fast)]
        assert len({tid for _, _, tid in fast_binds}) == 1
        rvs = [rv for _, rv, _ in fast_binds]
        assert rvs == sorted(rvs)
        # every pod bound once, the claim's last
        assert [name for name, _, _ in binds] == fast + ["pv"]
        for name in order:
            assert cluster.get_pod("ns", name).spec.node_name == "n1"
        assert cluster.list_bind_intents() == []

    def test_exception_out_of_one_chunk_leaves_later_chunks_draining(
            self, caplog):
        import logging

        cluster = InProcessCluster(simulate_kubelet=True)
        _gang_of(cluster, 6)
        cache = SchedulerCache(cluster=cluster)
        cache._BIND_CHUNK = 2  # chunks (p0 p1) (p2 p3) (p4 p5)
        cache.start_ingest()
        bind_one = cache._bind_side_effect

        def flaky(pod, *args, **kwargs):
            if pod.metadata.name == "p2":
                raise RuntimeError("injected escape from a chunk")
            return bind_one(pod, *args, **kwargs)

        cache._bind_side_effect = flaky
        try:
            with caplog.at_level(logging.ERROR):
                cache.bind_batch(_bind_infos(
                    cache, [f"p{i}" for i in range(6)]))
                assert cache.wait_for_side_effects(timeout=10)
        finally:
            cache.shutdown()
        assert any(
            "bind chunk" in r.getMessage() and r.exc_info
            for r in caplog.records
        )
        node_of = {
            f"p{i}": cluster.get_pod("ns", f"p{i}").spec.node_name
            for i in range(6)
        }
        # the failed chunk stops at p2; the chunk after it still binds
        assert node_of == {"p0": "n1", "p1": "n1", "p2": "", "p3": "",
                           "p4": "n1", "p5": "n1"}
        assert cache._inflight == 0


class TestPooledBindDrain:
    """Backends that leave ``bind_is_local`` at its default keep the
    pooled fan-out: one side-effect job per chunk."""

    @staticmethod
    def _kube():
        from kube_batch_tpu.cluster import KubeCluster, KubeConfig
        from kube_batch_tpu.utils.fake_kube import (
            FakeKube,
            node_doc,
            pod_doc,
        )

        fake = FakeKube()
        fake.create("Queue", {
            "apiVersion": "scheduling.incubator.k8s.io/v1alpha1",
            "kind": "Queue", "metadata": {"name": "default"},
            "spec": {"weight": 1},
        })
        fake.create("PodGroup", {
            "apiVersion": "scheduling.incubator.k8s.io/v1alpha1",
            "kind": "PodGroup",
            "metadata": {"name": "pg1", "namespace": "ns"},
            "spec": {"minMember": 1, "queue": "default"},
        })
        fake.create("Node", node_doc("n1", cpu="16"))
        for i in range(6):
            fake.create("Pod", pod_doc(f"p{i}", ns="ns", group="pg1"))
        cluster = KubeCluster(KubeConfig(fake.url), reconnect_delay=0.05)

        def bound():
            return sorted(pod for pod, _ in fake.bindings)

        def close():
            cluster.stop()
            fake.close()

        return cluster, bound, close

    @staticmethod
    def _failover():
        from kube_batch_tpu.sim.failover import SimClusterEndpoint

        inner = InProcessCluster(simulate_kubelet=True)
        _gang_of(inner, 6)

        def bound():
            return sorted(
                f"ns/{p.metadata.name}" for p in inner.list_objects("Pod")
                if p.spec.node_name)

        return SimClusterEndpoint(inner, seed=0), bound, lambda: None

    @pytest.mark.parametrize("backend", ["kube", "failover"])
    def test_chunks_fan_over_the_pool(self, backend):
        import threading
        import time

        cluster, bound, close = getattr(self, f"_{backend}")()
        assert type(cluster).bind_is_local is ClusterAPI.bind_is_local
        assert ClusterAPI.bind_is_local is False
        cache = SchedulerCache(cluster=cluster)
        cache._BIND_CHUNK = 4
        stop = threading.Event()
        try:
            cache.run(stop)
            assert cache.wait_for_cache_sync(stop)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                with cache.mutex:
                    job = cache.jobs.get("ns/pg1")
                    if job is not None and len(job.tasks) == 6:
                        break
                time.sleep(0.02)
            submitted = _count_submits(cache)
            cache.bind_batch(_bind_infos(
                cache, [f"p{i}" for i in range(6)]))
            assert cache.wait_for_side_effects(timeout=10)
            # the bookkeeping job, then one job per chunk of 4
            assert submitted == [True, False, False]
            assert bound() == [f"ns/p{i}" for i in range(6)]
        finally:
            stop.set()
            cache.shutdown()
            close()


def _res(r):
    return (r.milli_cpu, r.memory, r.scalar_resources, r.max_task_num)


def _task_state(t, pod):
    return (t.status, t.node_name, t.job, t.priority, t.volume_ready,
            _res(t.resreq), _res(t.init_resreq), t.pod is pod)


def _cache_state(cache, pods):
    """What the cache's pod ingest can leave behind, in no order: each
    job's tasks, status index and aggregates, each node's accounting and
    task clones, and the full and narrow dirty sets. ``pods`` maps a uid
    to the object its tasks should point at."""
    with cache.mutex:
        jobs = {
            uid: (
                {s: sorted(b) for s, b in job.task_status_index.items()},
                _res(job.allocated), _res(job.total_request),
                job.workload_class,
                {u: _task_state(t, pods.get(u)) for u, t in job.tasks.items()},
            )
            for uid, job in cache.jobs.items()
        }
        nodes = {
            name: (
                _res(node.idle), _res(node.used), _res(node.releasing),
                node.state.phase, node.state.reason,
                {k: _task_state(t, pods.get(t.uid))
                 for k, t in node.tasks.items()},
            )
            for name, node in cache.nodes.items()
        }
        dirty = (
            sorted(cache._dirty_jobs), sorted(cache._dirty_nodes),
            sorted(cache._dirty_jobs_alloc - cache._dirty_jobs),
            sorted(cache._dirty_nodes_alloc - cache._dirty_nodes),
        )
    return jobs, nodes, dirty


def _versions(cache):
    with cache.mutex:
        return ({u: j._ver for u, j in cache.jobs.items()},
                {n: node._ver for n, node in cache.nodes.items()})


def _moved(before, after):
    return [{k for k, v in a.items() if before[i].get(k) != v}
            for i, a in enumerate(after)]


def _no_echo_in_place(cache):
    """Send every pod MODIFIED down the full delete plus re-add path."""
    cache._echo_in_place = lambda pod: False


def _spy_echo_in_place(cache):
    """Record what the in-place path answers for each pod MODIFIED."""
    answers = []
    echo = cache._echo_in_place

    def spy(pod):
        answers.append(echo(pod))
        return answers[-1]

    cache._echo_in_place = spy
    return answers


class TestEchoInPlace:
    """The echo of a placement the cache staged (the bind confirmation, the
    kubelet's Running flip) is applied to the stored task in place, and
    leaves the state the full delete plus re-add leaves."""

    KUBELET = {
        "running": dict(simulate_kubelet=True),
        "bound": dict(simulate_kubelet=False),
        "bound-then-running": dict(simulate_kubelet=True,
                                   kubelet_delay=0.01),
    }

    def _drain(self, kubelet, full_path):
        """Bind a gang of six through bind_batch's ordered drain and wait
        for every echo; returns the state, which jobs and nodes bumped
        their versions, and whether each stored task object was kept."""
        import time

        cluster = InProcessCluster(**self.KUBELET[kubelet])
        _gang_of(cluster, 6)
        cache = SchedulerCache(cluster=cluster)
        cache._BIND_CHUNK = 4
        if full_path:
            _no_echo_in_place(cache)
        cache.start_ingest()
        want = (TaskStatus.BOUND if kubelet == "bound"
                else TaskStatus.RUNNING)
        try:
            with cache.mutex:
                stored = dict(cache.jobs["ns/pg1"].tasks)
            cache.snapshot()  # absorb the ingest's stamps
            before = _versions(cache)
            cache.bind_batch(_bind_infos(cache, [f"p{i}" for i in range(6)]))
            assert cache.wait_for_side_effects(timeout=10)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                with cache.mutex:
                    tasks = cache.jobs["ns/pg1"].tasks
                    if all(t.status == want for t in tasks.values()):
                        break
                time.sleep(0.01)
            pods = {p.uid: p for p in cluster.list_objects("Pod")}
            state = _cache_state(cache, pods)
            moved = _moved(before, _versions(cache))
            with cache.mutex:
                kept = {u: cache.jobs["ns/pg1"].tasks[u] is t
                        for u, t in stored.items()}
        finally:
            cache.shutdown()
        return state, moved, kept

    @pytest.mark.parametrize("kubelet", sorted(KUBELET))
    def test_bind_drain_echo_leaves_the_full_path_state(self, kubelet):
        state, moved, kept = self._drain(kubelet, full_path=False)
        ref_state, ref_moved, ref_kept = self._drain(kubelet, full_path=True)
        assert state == ref_state
        jobs, nodes, _ = state
        assert all(t[0] == (TaskStatus.BOUND if kubelet == "bound"
                            else TaskStatus.RUNNING) and t[-1]
                   for t in jobs["ns/pg1"][4].values())
        assert nodes["n1"][1][0] == 6 * 500  # used cpu, counted once
        assert moved == ref_moved == [{"ns/pg1"}, {"n1"}]
        assert all(kept.values()) and len(kept) == 6
        assert not any(ref_kept.values())

    def test_echo_counts_on_the_drain_span(self):
        from kube_batch_tpu.obs.tracer import TRACER

        cluster = InProcessCluster(simulate_kubelet=True)
        _gang_of(cluster, 6)
        cache = SchedulerCache(cluster=cluster)
        cache._BIND_CHUNK = 4
        cache.start_ingest()
        TRACER.reset()
        TRACER.enable()
        try:
            with TRACER.span("cycle"):
                cache.bind_batch(
                    _bind_infos(cache, [f"p{i}" for i in range(6)]))
            assert cache.wait_for_side_effects(timeout=10)
        finally:
            TRACER.disable()
            cache.shutdown()
        events = TRACER.events()
        TRACER.reset()
        (drain,) = [e for e in events if e["name"] == "cache_side_effect"]
        assert drain["args"]["echo_inplace_n"] == drain["args"]["ingest_n"] \
            == 6
        assert drain["args"]["echo_inplace_s"] <= drain["args"]["ingest_s"]

    @staticmethod
    def _pod(name, node, phase="Running", cpu="500m", **kw):
        return build_pod("ns", name, node, phase,
                         build_resource_list(cpu=cpu, memory="256Mi"),
                         group_name="pg1", **kw)

    def _cache(self, full_path):
        """p0 bound to n1 (Bound), p1 Running on n1, p2 pending, their
        volumes ready; n2 empty."""
        c = make_cache()
        for name in ("n1", "n2"):
            c.add_node(build_node(name, build_resource_list(
                cpu="8", memory="16Gi", pods=110)))
        c.add_pod_group(build_pod_group("pg1", namespace="ns"))
        c.add_pod(self._pod("p0", "n1", phase=PodPhase.PENDING))
        c.add_pod(self._pod("p1", "n1"))
        c.add_pod(self._pod("p2", "", phase=PodPhase.PENDING))
        for task in c.jobs["ns/pg1"].tasks.values():
            task.volume_ready = True  # a fresh TaskInfo says False
        c.snapshot()  # absorb the ingest's stamps
        if full_path:
            _no_echo_in_place(c)
        return c

    def _apply(self, make_event, full_path):
        c = self._cache(full_path)
        answers = [] if full_path else _spy_echo_in_place(c)
        with c.mutex:
            stored = {u: t for u, t in c.jobs["ns/pg1"].tasks.items()}
        pod = make_event()
        before = _versions(c)
        c.update_pod(pod, pod)
        with c.mutex:
            kept = c.jobs["ns/pg1"].tasks.get(pod.uid) is stored.get(pod.uid)
        return (_cache_state(c, {pod.uid: pod}),
                _moved(before, _versions(c)), answers, kept)

    def test_new_object_with_equal_requests_takes_the_in_place_path(self):
        """A watch that delivers a new object each time: the Running flip
        of the bound p0, as a copy."""
        event = lambda: self._pod("p0", "n1")  # noqa: E731
        state, moved, answers, kept = self._apply(event, full_path=False)
        ref_state, ref_moved, _, _ = self._apply(event, full_path=True)
        assert answers == [True] and kept
        assert state == ref_state
        assert moved == ref_moved == [{"ns/pg1"}, {"n1"}]
        jobs, _, dirty = state
        assert jobs["ns/pg1"][4]["ns-p0"][0] == TaskStatus.RUNNING
        assert dirty == ([], [], ["ns/pg1"], ["n1"])

    def _deleting(self):
        pod = self._pod("p1", "n1")
        pod.metadata.deletion_timestamp = 1.0
        return pod

    FULL_PATH = {
        "other-node": lambda self: self._pod("p0", "n2"),
        "resized": lambda self: self._pod("p0", "n1", cpu="1000m"),
        "releasing": _deleting,
        "succeeded": lambda self: self._pod("p1", "n1",
                                            phase=PodPhase.SUCCEEDED),
        "failed": lambda self: self._pod("p1", "n1", phase=PodPhase.FAILED),
        "unknown-uid": lambda self: self._pod("p9", "n1"),
        "bound-elsewhere": lambda self: self._pod("p2", "n1"),
    }

    @pytest.mark.parametrize("case", sorted(FULL_PATH))
    def test_other_events_take_the_full_path(self, case):
        event = lambda: self.FULL_PATH[case](self)  # noqa: E731
        state, moved, answers, kept = self._apply(event, full_path=False)
        ref_state, ref_moved, _, _ = self._apply(event, full_path=True)
        assert answers == [False]
        assert state == ref_state and moved == ref_moved
        if case != "unknown-uid":
            assert not kept  # a fresh TaskInfo replaced the stored one
        jobs, nodes, dirty = state
        where = {name: set(n[5]) for name, n in nodes.items()}
        expect = {
            "other-node": {"n1": {"ns-p1"}, "n2": {"ns-p0"}},
            "resized": {"n1": {"ns-p0", "ns-p1"}, "n2": set()},
            "releasing": {"n1": {"ns-p0", "ns-p1"}, "n2": set()},
            "succeeded": {"n1": {"ns-p0"}, "n2": set()},
            "failed": {"n1": {"ns-p0"}, "n2": set()},
            "unknown-uid": {"n1": {"ns-p0", "ns-p1", "ns-p9"}, "n2": set()},
            "bound-elsewhere": {"n1": {"ns-p0", "ns-p1", "ns-p2"},
                                "n2": set()},
        }[case]
        assert where == expect
        assert "ns/pg1" in dirty[0]  # stamped full
