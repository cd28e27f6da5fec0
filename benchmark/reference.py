"""The plain reference for placement semantics, independent of the program.

It imports nothing of ``kube_batch_tpu`` and takes nothing the program
made: only the generated gangs, the node shape, what the client saw on the
cluster watch, and the pods' ``spec.node_name`` as the cluster stores them.
It holds the scheduler to the guarantees the configuration states:

- capacity: replaying every bind and deletion in the cluster's own order
  (resource version), no node ever holds more cpu, memory or pods than it
  allocates;
- binding: no pod is seen bound to a second node, and what the watch saw
  agrees with what the cluster stores;
- gang: no PodGroup ends with some but fewer than minMember pods bound;
- work conservation: a first-fit greedy gang placer (the plain semantics
  of kube-batch's allocate: a queue past its deserved share is skipped, a
  gang is placed if at least minMember of its pods fit, and then every pod
  that fits) finds no pending pod that the free capacity would still
  take.
"""

import numpy as np


def node_index(name):
    return int(name[1:])


def oversubscribed_nodes(binds, deletes, node_of, pod_req, alloc, n_nodes):
    """Nodes that at some point held more than they allocate.

    ``binds``: (rv, t, pod, node); ``deletes``: (rv, t, pod); ``node_of``:
    pod -> node of its bind; ``pod_req``: pod -> (cpu milli, memory MiB)."""
    rows = []
    for rv, _, pod, node in binds:
        cpu, mem = pod_req[pod]
        rows.append((node_index(node), rv, cpu, mem, 1))
    for rv, _, pod in deletes:
        node = node_of.get(pod)
        if node is not None:
            cpu, mem = pod_req[pod]
            rows.append((node_index(node), rv, -cpu, -mem, -1))
    if not rows:
        return 0
    a = np.asarray(rows, dtype=np.int64)
    a = a[np.lexsort((a[:, 1], a[:, 0]))]
    node = a[:, 0]
    over = np.zeros(n_nodes, dtype=bool)
    starts = np.r_[0, np.nonzero(np.diff(node))[0] + 1]
    for col, limit in ((2, alloc[0]), (3, alloc[1]), (4, alloc[2])):
        run = np.cumsum(a[:, col])
        # Per-node running totals: the global total less its value
        # just before the node's first row.
        over[node[(run - _group_base(run, starts)) > limit]] = True
    return int(over.sum())


def _group_base(run, starts):
    """For each row, the cumulative total just before its group began."""
    before = np.zeros(len(starts), dtype=run.dtype)
    before[1:] = run[starts[1:] - 1]
    lengths = np.diff(np.r_[starts, len(run)])
    return np.repeat(before, lengths)


def greedy_place(gangs, bound, free, check_cpu=True, share=None):
    """First-fit gang placement of the pending pods of ``gangs`` onto
    ``free`` = [cpu, mem, pods] arrays (modified in place).

    ``gangs``: (queue, min_member, [(pod, cpu, mem)]); ``bound``: pod ->
    node or None. ``share``: queue -> (cpu, mem) it deserves; a queue whose
    allocation has reached that in both is overused and gets nothing more
    (kube-batch's proportion OverusedFn). Returns [(pod, node index)]
    placed. ``check_cpu=False`` is the control: the same placer with the
    cpu guarantee broken."""
    cpu_free, mem_free, pods_free = free
    used = {}
    for queue, _, pods in gangs:
        u = used.setdefault(queue, [0, 0])
        for p, cpu, mem in pods:
            if bound.get(p):
                u[0] += cpu
                u[1] += mem
    placed = []
    for queue, min_member, pods in gangs:
        if share is not None and all(
                u >= s for u, s in zip(used[queue], share[queue])):
            continue
        have = sum(1 for p, _, _ in pods if bound.get(p))
        trial = []
        for p, cpu, mem in pods:
            if bound.get(p):
                continue
            fit = (mem_free >= mem) & (pods_free >= 1)
            if check_cpu:
                fit &= cpu_free >= cpu
            j = int(np.argmax(fit))
            if not fit[j]:
                continue
            cpu_free[j] -= cpu
            mem_free[j] -= mem
            pods_free[j] -= 1
            trial.append((p, j, cpu, mem))
        if have + len(trial) >= min_member:
            placed.extend((p, j) for p, j, _, _ in trial)
            used[queue][0] += sum(t[2] for t in trial)
            used[queue][1] += sum(t[3] for t in trial)
        else:
            for _, j, cpu, mem in trial:
                cpu_free[j] += cpu
                mem_free[j] += mem
                pods_free[j] += 1
    return placed


def queue_shares(weights, queues, alloc, n_nodes):
    """What each queue with jobs deserves: its weighted share of the
    cluster's cpu and memory. The reference's water-fill would cap a
    queue at its request, but only where ``Resource.Less`` holds, which
    for cpu and memory alone it never does (resource_info.go:232-237, kept
    by the program, tests/unit/test_resource_info.py)."""
    total = sum(weights[q] for q in queues)
    return {q: (n_nodes * alloc[0] * weights[q] / total,
                n_nodes * alloc[1] * weights[q] / total) for q in queues}


def free_capacity(alloc, n_nodes, bound_pods, pod_req):
    """[cpu, mem, pods] free per node given pod -> node of every bound pod."""
    cpu = np.full(n_nodes, alloc[0], dtype=np.int64)
    mem = np.full(n_nodes, alloc[1], dtype=np.int64)
    pods = np.full(n_nodes, alloc[2], dtype=np.int64)
    for pod, node in bound_pods.items():
        j = node_index(node)
        c, m = pod_req[pod]
        cpu[j] -= c
        mem[j] -= m
        pods[j] -= 1
    return [cpu, mem, pods]


def judge_gangs(gangs, stored, seen, alloc, n_nodes, pod_req, weights):
    """The gang, binding and work-conservation checks on one set of gangs.

    ``gangs``: (name, queue, min_member, [pod]) live at the judged moment,
    the only pods on the nodes; ``stored``: pod -> node_name the cluster
    stores ("" if pending); ``seen``: pod -> node of the bind the watch
    delivered; ``weights``: queue -> weight. Returns (partial gangs, bind
    mismatches, pods left that fit, gangs failed)."""
    partial = mismatch = 0
    failed = set()
    for name, _, min_member, pods in gangs:
        n_bound = 0
        for p in pods:
            node = stored.get(p, "")
            if node != (seen.get(p) or ""):
                mismatch += 1
                failed.add(name)
            n_bound += bool(node)
        if 0 < n_bound < min_member:
            partial += 1
            failed.add(name)
    occupied = {p: stored[p] for _, _, _, pods in gangs for p in pods
                if stored.get(p)}
    free = free_capacity(alloc, n_nodes, occupied, pod_req)
    share = queue_shares(weights, {q for _, q, _, _ in gangs}, alloc, n_nodes)
    placeable = greedy_place(
        [(q, mm, [(p, *pod_req[p]) for p in pods])
         for _, q, mm, pods in gangs],
        stored, free, share=share,
    )
    gang_of = {p: name for name, _, _, pods in gangs for p in pods}
    failed.update(gang_of[p] for p, _ in placeable)
    return partial, mismatch, len(placeable), failed
