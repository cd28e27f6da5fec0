"""Client-side watch recorder: what a user of the cluster sees.

The idea of ``kube_batch_tpu/perf.py``'s ``PodWatchRecorder`` (kubemark's
watch-based timing), kept here so the program cannot move it. Every pod
event of the benchmark's namespace is stamped on the host clock when the
cluster delivers it, with the cluster's resource version, so binds and
deletions can later be replayed in the order the cluster applied them.
"""

import threading
import time

from gen import NS


class WatchRecorder:
    def __init__(self, cluster):
        self._lock = threading.Lock()
        self.node_of = {}      # pod name -> node of its first bind seen
        self.bind_time = {}    # pod name -> host time of that bind
        self.binds = []        # (rv, t, pod, node) in delivery order
        self.deletes = []      # (rv, t, pod)
        self.rebinds = []      # (pod, first node, later node)
        cluster.add_watch(self._on_event)

    def _on_event(self, kind, event_type, obj, rv):
        if kind != "Pod" or obj.metadata.namespace != NS:
            return
        now = time.perf_counter()
        name = obj.metadata.name
        with self._lock:
            if event_type == "DELETED":
                self.deletes.append((rv, now, name))
                return
            node = obj.spec.node_name
            if not node:
                return
            first = self.node_of.get(name)
            if first is None:
                self.node_of[name] = node
                self.bind_time[name] = now
                self.binds.append((rv, now, name, node))
            elif first != node:
                self.rebinds.append((name, first, node))

    def bound_count(self):
        with self._lock:
            return len(self.binds)
