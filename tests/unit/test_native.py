"""Native greedy baseline (native/greedy.cpp via ctypes).

Parity is asserted against a pure-numpy transcription of the same loop
(per-task sequential best-node scan with LeastRequested+Balanced scores,
epsilon fit, queue Overused gating) — the shared contract both mirror is
the reference allocate loop (allocate.go:43-191)."""

import numpy as np
import pytest

try:
    from kube_batch_tpu.native import greedy_allocate, native_available
    HAVE_NATIVE = native_available()
except Exception:  # pragma: no cover - no toolchain
    HAVE_NATIVE = False

pytestmark = pytest.mark.skipif(
    not HAVE_NATIVE, reason="native toolchain unavailable"
)


def numpy_greedy(task_req, task_queue, node_idle, node_cap, qd, qa, eps,
                 lr_w=1.0, br_w=1.0):
    idle = node_idle.astype(np.float64).copy()
    qalloc = qa.astype(np.float64).copy()
    cap = node_cap.astype(np.float64)
    out = np.full(len(task_req), -1, np.int32)
    for t in range(len(task_req)):
        req = task_req[t].astype(np.float64)
        q = int(task_queue[t])
        if 0 <= q < len(qd) and np.all(qd[q] - qalloc[q] < eps):
            continue
        best, best_s = -1, -1.0
        for n in range(len(idle)):
            if not np.all(req - idle[n] < eps):
                continue
            rem = idle[n] - req
            cm = cap[n][:2]
            safe = np.where(cm > 0, cm, 1.0)
            lr = float(np.mean(
                np.where(cm > 0, np.maximum(rem[:2], 0) * 10.0 / safe, 0.0)
            ))
            frac = np.where(cm > 0, 1.0 - rem[:2] / safe, 1.0)
            br = 0.0 if np.any(frac >= 1.0) else (
                10.0 - abs(frac[0] - frac[1]) * 10.0
            )
            s = lr_w * lr + br_w * br
            if s > best_s:
                best_s, best = s, n
        if best >= 0:
            idle[best] -= req
            if 0 <= q < len(qd):
                qalloc[q] += req
            out[t] = best
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_native_matches_numpy_reference(seed):
    rng = np.random.RandomState(seed)
    T, N, Q, R = 120, 10, 2, 2
    task_req = np.c_[
        rng.choice([250, 500, 1000, 2000], T),
        rng.choice([256, 1024, 4096], T),
    ].astype(np.float32)
    task_queue = rng.randint(0, Q, T).astype(np.int32)
    node_idle = np.c_[
        rng.choice([4000, 8000, 16000], N), np.full(N, 32768)
    ].astype(np.float32)
    eps = np.asarray([10.0, 10.0], np.float32)
    qd = np.asarray([[20000.0, 0.0], [np.inf, np.inf]], np.float32)
    qa = np.zeros((Q, R), np.float32)

    got, placed = greedy_allocate(
        task_req, task_queue, node_idle, node_idle, qd, qa, eps
    )
    want = numpy_greedy(task_req, task_queue, node_idle, node_idle, qd, qa,
                        eps)
    np.testing.assert_array_equal(got, want)
    assert placed == int((want >= 0).sum())


def test_queue_overused_gates_tasks():
    # Queue 0 already at deserved: its task skipped; queue 1 placed.
    task_req = np.asarray([[100.0, 0.0], [100.0, 0.0]], np.float32)
    task_queue = np.asarray([0, 1], np.int32)
    node_idle = np.asarray([[1000.0, 1e9]], np.float32)
    eps = np.asarray([10.0, 10.0], np.float32)
    qd = np.asarray([[500.0, 0.0], [np.inf, np.inf]], np.float32)
    qa = np.asarray([[500.0, 0.0], [0.0, 0.0]], np.float32)
    out, placed = greedy_allocate(
        task_req, task_queue, node_idle, node_idle, qd, qa, eps
    )
    assert out[0] == -1 and out[1] == 0 and placed == 1


class TestSolveNative:
    """greedy_allocate_masked via solve_native: the production CPU
    fallback consuming the full factorized snapshot (VERDICT r1 item 7)."""

    def _session_inputs(self, n_groups=4, per_group=8, n_nodes=4):
        import kube_batch_tpu.actions  # noqa: F401
        import kube_batch_tpu.plugins  # noqa: F401
        from kube_batch_tpu.api import PodPhase, build_resource_list
        from kube_batch_tpu.framework import open_session
        from kube_batch_tpu.solver import tensorize
        from kube_batch_tpu.utils.test_utils import (
            FakeBinder, FakeEvictor, FakeStatusUpdater, FakeVolumeBinder,
            build_node, build_pod, build_pod_group, build_queue,
        )
        from kube_batch_tpu.cache import SchedulerCache
        from tests.actions.test_actions import DEFAULT_TIERS_ARGS, make_tiers

        cache = SchedulerCache(
            binder=FakeBinder(), evictor=FakeEvictor(),
            status_updater=FakeStatusUpdater(),
            volume_binder=FakeVolumeBinder(),
        )
        cache.add_queue(build_queue("q0", weight=1))
        for j in range(n_nodes):
            cache.add_node(build_node(
                f"n{j}", build_resource_list(cpu="8", memory="32Gi", pods=110)
            ))
        for g in range(n_groups):
            cache.add_pod_group(build_pod_group(
                f"pg{g}", namespace="ns", min_member=1, queue="q0"
            ))
            for i in range(per_group):
                cache.add_pod(build_pod(
                    "ns", f"pg{g}-p{i}", "", PodPhase.PENDING,
                    build_resource_list(cpu="500m", memory="512Mi"),
                    group_name=f"pg{g}",
                ))
        ssn = open_session(cache, make_tiers(*DEFAULT_TIERS_ARGS))
        inputs, ctx = tensorize(ssn)
        return ssn, inputs, ctx

    def test_native_respects_capacity_and_mask(self):
        from kube_batch_tpu.native import solve_native

        ssn, inputs, ctx = self._session_inputs()
        assigned, placed = solve_native(inputs)
        T, N = len(ctx.tasks), len(ctx.nodes)
        # Padded rows never receive assignments; real rows only go to
        # real, feasible nodes.
        assert (assigned[T:] == -1).all()
        s = inputs.unpack()
        req = np.asarray(s.task_req)
        idle0 = np.asarray(s.node_idle)
        eps = np.asarray(s.eps)
        used = np.zeros_like(idle0)
        for i in range(T):
            j = int(assigned[i])
            if j < 0:
                continue
            assert j < N
            assert ctx.mask.row(i)[j]
            used[j] += req[i]
        assert np.all(used - idle0 < eps[None, :] + 1e-3)
        # Uncontended cluster (32 cpu vs 16 requested): everything places.
        assert placed == T

    def test_native_matches_jax_solver_placement_count(self):
        from kube_batch_tpu.native import solve_native
        from kube_batch_tpu.solver import solve_jit

        ssn, inputs, ctx = self._session_inputs(
            n_groups=3, per_group=10, n_nodes=2
        )
        native_assigned, native_placed = solve_native(inputs)
        jax_assigned = np.asarray(solve_jit(inputs).assigned)
        # Different algorithms (sequential greedy vs round auction) may
        # pick different nodes, but on a uniform-request instance the
        # placement count is determined by capacity alone.
        assert native_placed == int((jax_assigned >= 0).sum())

    def test_allocate_tpu_native_route_end_to_end(self, monkeypatch):
        """KBT_SOLVER=native drives the whole action through greedy.cpp;
        outcomes must match the pure-greedy action's bind count."""
        import kube_batch_tpu.actions  # noqa: F401
        import kube_batch_tpu.plugins  # noqa: F401
        from kube_batch_tpu.api import PodPhase, build_resource_list
        from kube_batch_tpu.utils.test_utils import (
            build_node, build_pod, build_pod_group, build_queue,
        )
        from tests.actions.test_actions import drain, make_cache, run_action

        def cluster():
            c = make_cache()
            c.add_queue(build_queue("default"))
            c.add_pod_group(build_pod_group(
                "pg1", namespace="ns", min_member=3
            ))
            for i in range(5):
                c.add_pod(build_pod(
                    "ns", f"p{i}", "", PodPhase.PENDING,
                    build_resource_list(cpu="1", memory="1Gi"),
                    group_name="pg1",
                ))
            c.add_node(build_node(
                "n1", build_resource_list(cpu="4", memory="8Gi", pods=110)
            ))
            c.add_node(build_node(
                "n2", build_resource_list(cpu="2", memory="4Gi", pods=110)
            ))
            return c

        monkeypatch.setenv("KBT_SOLVER", "native")
        c_native = cluster()
        run_action(c_native, "allocate_tpu")
        # Binds apply asynchronously (cache.bind fires the Binder on a
        # worker thread): drain the channel, don't peek at the dict.
        assert len(drain(c_native.binder.channel, 5)) == 5
        monkeypatch.setenv("KBT_SOLVER", "jax")
        c_jax = cluster()
        run_action(c_jax, "allocate_tpu")
        assert len(drain(c_jax.binder.channel, 5)) == 5


def numpy_masked(task_req, task_fit, task_queue, task_job, task_valid,
                 task_group, node_feas, group_feas, pair_idx, pair_feas,
                 score_idx, score_rows, node_idle, node_cap, ntask0,
                 max_tasks, qd, qa, eps, lr_w=1.0, br_w=1.0):
    """Pure-numpy transcription of greedy_allocate_masked's scan semantics
    (the contract the heap fast path must reproduce exactly)."""
    idle = node_idle.astype(np.float64).copy()
    qalloc = qa.astype(np.float64).copy()
    ntask = ntask0.astype(np.int64).copy()
    cap = node_cap.astype(np.float64)
    T, N = len(task_req), len(node_idle)
    out = np.full(T, -1, np.int32)
    job_failed = np.zeros(T, bool)
    pair_map = {int(i): k for k, i in enumerate(pair_idx)}
    score_map = {int(i): k for k, i in enumerate(score_idx)}
    for t in range(T):
        if not task_valid[t]:
            continue
        j = int(task_job[t])
        if 0 <= j < T and job_failed[j]:
            continue
        req = task_req[t].astype(np.float64)
        fit = task_fit[t].astype(np.float64)
        q = int(task_queue[t])
        if 0 <= q < len(qd) and np.all(qd[q] - qalloc[q] < eps):
            continue
        grow = group_feas[task_group[t]] if 0 <= task_group[t] < len(group_feas) else None
        prow = pair_feas[pair_map[t]] if t in pair_map else None
        srow = score_rows[score_map[t]] if t in score_map else None
        best, best_s, any_feas = -1, -1.0e300, False
        for n in range(N):
            if not node_feas[n]:
                continue
            if grow is not None and not grow[n]:
                continue
            if prow is not None and not prow[n]:
                continue
            if max_tasks[n] > 0 and ntask[n] >= max_tasks[n]:
                continue
            any_feas = True
            if not np.all(fit - idle[n] < eps):
                continue
            rem = idle[n] - req
            cm = cap[n][:2]
            safe = np.where(cm > 0, cm, 1.0)
            lr = float(np.mean(
                np.where(cm > 0, np.maximum(rem[:2], 0) * 10.0 / safe, 0.0)
            ))
            frac = np.where(cm > 0, 1.0 - rem[:2] / safe, 1.0)
            br = 0.0 if np.any(frac >= 1.0) else (
                10.0 - abs(frac[0] - frac[1]) * 10.0
            )
            s = lr_w * lr + br_w * br
            if srow is not None:
                s += float(srow[n])
            if s > best_s:
                best_s, best = s, n
        if best < 0:
            if not any_feas and 0 <= j < T:
                job_failed[j] = True
            continue
        idle[best] -= req
        ntask[best] += 1
        if 0 <= q < len(qd):
            qalloc[q] += req
        out[t] = best
    return out


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_masked_heap_path_matches_scan_semantics(seed):
    """Randomized exact-parity: signature classes big enough to take the
    heap fast path must produce byte-identical assignments to the
    sequential scan transcription (same argmax, same job-break)."""
    import ctypes

    from kube_batch_tpu.native.greedy import _load
    lib = _load()
    lib.greedy_set_heap_threshold.argtypes = [ctypes.c_int64]
    lib.greedy_set_heap_threshold(0)  # force the heap path on small shapes
    try:
        _run_masked_parity(lib, seed)
    finally:
        lib.greedy_set_heap_threshold(1 << 20)


def _run_masked_parity(lib, seed):
    rng = np.random.RandomState(seed)
    T, N, Q, R, G = 160, 12, 3, 2, 2
    # few distinct requests -> large signature classes (heap path active)
    reqs = np.asarray([[500, 512], [1000, 1024], [2000, 2048]], np.float32)
    pick = rng.randint(0, 3, T)
    task_req = reqs[pick]
    task_fit = task_req.copy()
    # a few tasks fit-check a larger footprint (init containers)
    grow_fit = rng.rand(T) < 0.1
    task_fit[grow_fit] *= 1.5
    task_queue = rng.randint(0, Q, T).astype(np.int32)
    task_job = (np.arange(T, dtype=np.int32) // 8)  # 8-task gangs
    task_valid = np.ones(T, np.uint8)
    task_valid[rng.rand(T) < 0.05] = 0
    task_group = rng.randint(0, G, T).astype(np.int32)
    node_feas = (rng.rand(N) > 0.1).astype(np.uint8)
    group_feas = (rng.rand(G, N) > 0.2).astype(np.uint8)
    # sparse private predicate rows on ~6% of tasks (ascending idx)
    pidx = np.sort(rng.choice(T, size=max(1, T // 16), replace=False))
    pair_idx = pidx.astype(np.int32)
    pair_feas = (rng.rand(len(pidx), N) > 0.3).astype(np.uint8)
    # sparse static score rows on a few tasks
    sidx = np.sort(rng.choice(T, size=4, replace=False))
    score_idx = sidx.astype(np.int32)
    score_rows = rng.rand(4, N).astype(np.float32) * 5.0
    node_idle = np.c_[
        rng.choice([4000, 8000, 16000], N), rng.choice([8192, 32768], N)
    ].astype(np.float32)
    node_cap = node_idle.copy()
    ntask0 = np.zeros(N, np.int32)
    max_tasks = rng.choice([0, 3, 8], N).astype(np.int32)
    qd = np.full((Q, R), np.inf, np.float32)
    qd[0] = [6000.0, 999999.0]  # queue 0 budget-capped
    qa = np.zeros((Q, R), np.float32)
    eps = np.asarray([10.0, 10.0], np.float32)

    out = np.empty(T, np.int32)
    placed = lib.greedy_allocate_masked(
        np.ascontiguousarray(task_req), np.ascontiguousarray(task_fit),
        task_queue, task_job, task_valid, task_group,
        node_feas, np.ascontiguousarray(group_feas),
        pair_idx, np.ascontiguousarray(pair_feas),
        score_idx, np.ascontiguousarray(score_rows),
        np.ascontiguousarray(node_idle), np.ascontiguousarray(node_cap),
        ntask0, max_tasks, qd, qa, eps, 1.0, 1.0,
        T, N, Q, R, G, len(pair_idx), len(score_idx), out,
    )
    want = numpy_masked(
        task_req, task_fit, task_queue, task_job, task_valid, task_group,
        node_feas, group_feas, pair_idx, pair_feas, score_idx, score_rows,
        node_idle, node_cap, ntask0, max_tasks, qd, qa, eps,
    )
    np.testing.assert_array_equal(out, want)
    assert placed == int((want >= 0).sum())


@pytest.mark.parametrize("forced, platform, native_ok, want_native", [
    ("", "cpu", True, True),      # CPU-only host with a toolchain
    ("", "cpu", False, False),    # no toolchain: the JAX kernel on CPU
    ("", "tpu", True, False),     # an accelerator: the JAX kernel
    ("jax", "cpu", True, False),  # KBT_SOLVER overrides either way
    ("native", "tpu", True, True),
])
def test_use_native_solver_follows_platform(
    monkeypatch, forced, platform, native_ok, want_native
):
    """allocate_tpu routes to native/greedy.cpp exactly when this
    process's first JAX device is the host CPU (and native builds);
    KBT_SOLVER=jax|native overrides."""
    import jax

    import kube_batch_tpu.native as native
    from kube_batch_tpu.actions.allocate_tpu import _use_native_solver

    class _Dev:
        pass

    dev = _Dev()
    dev.platform = platform
    monkeypatch.setenv("KBT_SOLVER", forced)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    monkeypatch.setattr(native, "native_available", lambda: native_ok)
    assert _use_native_solver() is want_native
