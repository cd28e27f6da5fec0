"""Top-K candidate-sparsified solver tests (solver/topk.py +
kernels.solve_sparse + the end-to-end wiring).

Parity contract (doc/design/sparse-candidate-solver.md): when every
class's slab covers its whole eligible set (K >= cand_total, e.g.
K >= N) the sparse solve is BIT-IDENTICAL to the dense solve —
assignment vector and node-idle accounting. With truncated slabs the
refill stage restores full-N fidelity for whatever the slab rounds
could not place, so per-job success, total placements, and capacity
accounting match the dense solve across randomized churn; exact node
identity within score-quantum ties is not a contract (the reference
greedy tie-breaks randomly, scheduler_helper.go:188-208).
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

import kube_batch_tpu.actions  # noqa: F401 (registers actions)
import kube_batch_tpu.plugins  # noqa: F401 (registers plugins)
from kube_batch_tpu.api import PodPhase, build_resource_list
from kube_batch_tpu.solver import (
    jit_compilation_count,
    make_inputs,
    select_candidates,
    solve,
    solve_jit,
    solve_plan,
    solve_sparse,
    tensorize,
)
from kube_batch_tpu.solver.masks import CombinedMask

from tests.actions.test_actions import (
    DEFAULT_TIERS_ARGS,
    make_cache,
    make_tiers,
    req,
    run_action,
)
from kube_batch_tpu.framework import close_session, open_session
from kube_batch_tpu.utils.test_utils import (
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
)


def trivial_mask(T, N, group_rows=None, task_group=None):
    return CombinedMask(
        node_ok=np.ones(N, bool),
        task_group=(
            np.zeros(T, np.int32) if task_group is None else task_group
        ),
        group_rows=(
            np.ones((1, N), bool) if group_rows is None else group_rows
        ),
        pair_idx=np.zeros((0,), np.int32),
        pair_rows=np.zeros((0, N), bool),
    )


def solver_kw(task_req, node_idle, *, jobs_of=10):
    task_req = np.asarray(task_req, np.float32)
    node_idle = np.asarray(node_idle, np.float32)
    T, R = task_req.shape
    N = node_idle.shape[0]
    return dict(
        task_req=jnp.asarray(task_req),
        task_fit=jnp.asarray(task_req),
        task_rank=jnp.arange(T, dtype=jnp.int32),
        task_job=jnp.asarray(np.arange(T) // jobs_of, jnp.int32),
        task_queue=jnp.zeros(T, jnp.int32),
        node_idle=jnp.asarray(node_idle),
        node_releasing=jnp.zeros_like(jnp.asarray(node_idle)),
        node_cap=jnp.asarray(node_idle),
        node_task_count=jnp.zeros(N, jnp.int32),
        node_max_tasks=jnp.zeros(N, jnp.int32),
        queue_deserved=jnp.full((1, R), jnp.inf, jnp.float32),
        queue_allocated=jnp.zeros((1, R), jnp.float32),
        eps=jnp.full((R,), 10.0, jnp.float32),
        lr_weight=jnp.asarray(1.0, jnp.float32),
        br_weight=jnp.asarray(1.0, jnp.float32),
    )


def select_for(task_req, node_idle, k, mask=None, score_rows=None,
               task_valid=None):
    task_req = np.asarray(task_req, np.float32)
    node_idle = np.asarray(node_idle, np.float32)
    T = task_req.shape[0]
    N = node_idle.shape[0]
    if mask is None:
        mask = trivial_mask(T, N)
    return select_candidates(
        mask, score_rows or {}, task_req, task_req,
        node_idle, node_idle, np.zeros_like(node_idle),
        np.zeros(N, np.int32), np.zeros(N, np.int32),
        np.array([10.0, 10.0], np.float32), 1.0, 1.0, k,
    )


def sparse_inputs(kw, cs):
    return make_inputs(
        **kw,
        task_cand=jnp.asarray(cs.task_cand),
        cand_idx=jnp.asarray(cs.cand_idx),
        cand_static=jnp.asarray(cs.cand_static),
        cand_info=jnp.asarray(cs.cand_info),
    )


def random_case(seed, T=60, N=16, cap=6000):
    rng = np.random.RandomState(seed)
    task_req = np.c_[
        rng.choice([250, 500, 1000], T), rng.choice([256, 512], T)
    ].astype(np.float32)
    node_idle = np.c_[
        rng.choice([cap, 2 * cap], N), np.full(N, 1e7)
    ].astype(np.float32)
    return task_req, node_idle


class TestTopkConfig:
    def test_env_forced_and_disabled(self, monkeypatch):
        monkeypatch.setenv("KBT_SOLVER_TOPK", "12")
        plan = solve_plan(10, 10, None)
        assert plan.sparse and plan.k == 16  # pow2-bucketed
        for off in ("0", "off", "dense"):
            monkeypatch.setenv("KBT_SOLVER_TOPK", off)
            assert not solve_plan(10**6, 10**5, None).sparse

    def test_size_policy(self, monkeypatch):
        monkeypatch.delenv("KBT_SOLVER_TOPK", raising=False)
        assert not solve_plan(100, 100, None).sparse     # small problem
        assert not solve_plan(20000, 200, None).sparse   # below node floor
        assert solve_plan(20000, 5000, None).sparse


class TestSelection:
    def test_gang_members_share_one_class(self):
        # 30 tasks of 3 distinct shapes -> 3 classes, slab rows shared.
        task_req = np.tile(
            np.asarray(
                [[250, 256], [500, 256], [1000, 512]], np.float32
            ),
            (10, 1),
        )
        node_idle = np.full((8, 2), 32000.0, np.float32)
        node_idle[:, 1] = 1e7
        cs = select_for(task_req, node_idle, k=4)
        assert cs.stats["classes"] == 3
        assert len(np.unique(cs.task_cand)) == 3
        same = cs.task_cand[0::3]
        assert (same == same[0]).all()

    def test_slabs_ascend_with_sentinel_padding(self):
        task_req, node_idle = random_case(3, T=20, N=6)
        cs = select_for(task_req, node_idle, k=16)  # k > N: padding
        N = node_idle.shape[0]
        for row in cs.cand_idx:
            real = row[row < N]
            assert (np.diff(real) > 0).all()      # strictly ascending
            assert (row[len(real):] == N).all()   # sentinels last

    def test_eligibility_excludes_never_fitting_nodes(self):
        # One tiny node can never hold the 2-cpu tasks: it must not
        # appear in any slab and cand_total must not count it.
        task_req = np.full((8, 2), [2000.0, 256.0], np.float32)
        node_idle = np.full((4, 2), 8000.0, np.float32)
        node_idle[:, 1] = 1e7
        node_idle[2, 0] = 100.0  # never fits
        cs = select_for(task_req, node_idle, k=4)
        assert (cs.cand_idx != 2).all()
        assert (cs.cand_info[0] == 3).all()

    def test_infeasible_group_has_empty_slab(self):
        task_req = np.full((4, 2), [500.0, 256.0], np.float32)
        node_idle = np.full((4, 2), 8000.0, np.float32)
        mask = trivial_mask(
            4, 4, group_rows=np.zeros((1, 4), bool)
        )
        cs = select_for(task_req, node_idle, k=2, mask=mask)
        assert (cs.cand_idx == 4).all()
        assert (cs.cand_info[0] == 0).all()
        assert (cs.cand_info[1] == 0).all()


def job_placed_counts(assigned, jobs_of=10):
    a = np.asarray(assigned)
    placed = a >= 0
    jobs = np.arange(len(a)) // jobs_of
    return np.bincount(jobs[placed], minlength=jobs.max() + 1)


class TestSparseParity:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_bit_equal_when_slab_covers_nodes(self, seed):
        task_req, node_idle = random_case(seed)
        kw = solver_kw(task_req, node_idle)
        cs = select_for(task_req, node_idle, k=16)  # K = pow2(N) >= N
        dense = solve(make_inputs(**kw))
        sparse = solve_sparse(sparse_inputs(kw, cs), tail_bucket=16)
        np.testing.assert_array_equal(
            np.asarray(dense.assigned), np.asarray(sparse.assigned)
        )
        np.testing.assert_array_equal(
            np.asarray(dense.node_idle), np.asarray(sparse.node_idle)
        )
        assert int(sparse.refills) == 0

    @pytest.mark.parametrize("k", [8, 64])
    def test_randomized_churn_parity(self, k):
        """Across churn cycles (placed tasks leave, idle shrinks by the
        dense solve's accounting), sparse and dense place the same
        per-job counts with identical capacity totals."""
        rng = np.random.RandomState(11)
        T, N = 80, 16
        task_req = np.c_[
            rng.choice([250, 500, 1000], T), rng.choice([256, 512], T)
        ].astype(np.float32)
        node_idle = np.c_[
            rng.choice([4000, 8000], N), np.full(N, 1e7)
        ].astype(np.float32)
        valid = np.ones(T, bool)
        for cycle in range(4):
            kw = solver_kw(task_req, node_idle)
            kw["task_valid"] = jnp.asarray(valid)
            cs = select_for(task_req, node_idle, k=k)
            dense = solve(make_inputs(**kw))
            sparse = solve_sparse(sparse_inputs(kw, cs), tail_bucket=16)
            a_d = np.asarray(dense.assigned)
            a_s = np.asarray(sparse.assigned)
            assert (a_d >= 0).sum() == (a_s >= 0).sum(), f"cycle {cycle}"
            np.testing.assert_array_equal(
                job_placed_counts(a_d), job_placed_counts(a_s),
                err_msg=f"per-job success diverged in cycle {cycle}",
            )
            # Capacity: never negative, and total consumption identical.
            idle_s = np.asarray(sparse.node_idle)
            assert (idle_s > -10.0).all()
            np.testing.assert_allclose(
                idle_s.sum(axis=0),
                np.asarray(dense.node_idle).sum(axis=0),
                atol=1e-2,
            )
            # Churn: placed tasks leave; the cluster keeps the DENSE
            # accounting so both paths see the same next snapshot.
            valid = valid & (a_d < 0)
            node_idle = np.asarray(dense.node_idle).copy()
            if not valid.any():
                break

    def test_exhaustion_refill_places_like_dense(self):
        """K=2 slabs on a capacity-tight cluster: slab exhaustion must
        route through refill (never false job breaks) and land the same
        placement count as dense."""
        for seed in range(4):
            rng = np.random.RandomState(seed)
            T, N = 60, 12
            task_req = np.c_[
                rng.choice([250, 500, 1000], T),
                rng.choice([256, 512], T),
            ].astype(np.float32)
            node_idle = np.c_[
                np.full(N, 4000.0), np.full(N, 1e7)
            ].astype(np.float32)
            kw = solver_kw(task_req, node_idle)
            cs = select_for(task_req, node_idle, k=2)
            assert cs.stats["truncated_classes"] > 0
            dense = solve(make_inputs(**kw))
            sparse = solve_sparse(sparse_inputs(kw, cs), tail_bucket=8)
            assert int(sparse.refills) > 0
            assert (
                (np.asarray(sparse.assigned) >= 0).sum()
                == (np.asarray(dense.assigned) >= 0).sum()
            )

    def test_complete_slab_exhaustion_breaks_job_like_dense(self):
        # Job 0: task 0 fits nowhere (too big) -> job break must also
        # gate task 1 (its job-mate); job 1 places. Identical on both
        # paths, including with a COMPLETE slab (cand_total <= K).
        task_req = np.asarray(
            [[50000.0, 256.0], [100.0, 256.0],
             [100.0, 256.0], [100.0, 256.0]],
            np.float32,
        )
        node_idle = np.asarray([[4000.0, 1e7], [4000.0, 1e7]], np.float32)
        kw = solver_kw(task_req, node_idle, jobs_of=2)
        cs = select_for(task_req, node_idle, k=2)
        dense = solve(make_inputs(**kw))
        sparse = solve_sparse(sparse_inputs(kw, cs), tail_bucket=4)
        np.testing.assert_array_equal(
            np.asarray(dense.assigned), np.asarray(sparse.assigned)
        )
        assert int(np.asarray(sparse.assigned)[1]) == -1  # job-broken


class TestSparseActionEndToEnd:
    def _build(self, action, solver, monkeypatch):
        monkeypatch.setenv("KBT_SOLVER", solver)
        c = make_cache()
        c.add_queue(build_queue("default"))
        for j in range(8):
            c.add_node(build_node(
                f"n{j}", build_resource_list(cpu="4", memory="8Gi")
            ))
        for g in range(4):
            c.add_pod_group(build_pod_group(
                f"pg{g}", namespace="ns", min_member=1
            ))
            for i in range(6):
                c.add_pod(build_pod(
                    "ns", f"pg{g}-p{i}", "", PodPhase.PENDING, req(),
                    group_name=f"pg{g}",
                ))
        run_action(c, action)
        assert c.wait_for_side_effects()
        return c

    @pytest.mark.parametrize("solver", ["jax", "native"])
    def test_sparse_cycle_binds_and_reports(self, solver, monkeypatch):
        from kube_batch_tpu.actions import allocate_tpu as atpu
        from kube_batch_tpu.metrics import metrics as m

        if solver == "native":
            from kube_batch_tpu.native import native_available

            if not native_available():
                pytest.skip("no native toolchain")
        monkeypatch.setenv("KBT_SOLVER_TOPK", "4")
        before = m.solver_sparse_solves.get()
        c = self._build("allocate_tpu", solver, monkeypatch)
        stats = dict(atpu.last_stats)
        assert len(c.binder.binds) == 24
        assert stats.get("sparse_engaged") is True
        assert stats.get("sparse_k") == 4
        assert m.solver_sparse_solves.get() == before + 1

    def test_dense_policy_small_cluster_no_sparse(self, monkeypatch):
        from kube_batch_tpu.actions import allocate_tpu as atpu

        monkeypatch.delenv("KBT_SOLVER_TOPK", raising=False)
        c = self._build("allocate_tpu", "jax", monkeypatch)
        stats = dict(atpu.last_stats)
        assert len(c.binder.binds) == 24
        assert stats.get("sparse_engaged") is False
        assert stats.get("sparse_fallback_reason") == "small-problem"


class TestSparseRetraceGuard:
    """Zero new jit compilations across steady/delta SPARSE cycles —
    the sparse twin of tests/solver/test_retrace_guard.py: candidate
    axes (class pow2 buckets, fixed K, task-bucketed task_cand) must
    stay inside their shape buckets under churn."""

    def test_zero_new_compilations_sparse_cycles(self, monkeypatch):
        from tests.solver.test_retrace_guard import one_cycle
        from tests.unit.test_cycle_pipeline import build_cluster

        monkeypatch.setenv("KBT_SOLVER_TOPK", "8")
        monkeypatch.setenv("KBT_SOLVER", "jax")
        c = build_cluster(seed=47, groups=6, per_group=40, nodes=8)
        tiers = make_tiers(*DEFAULT_TIERS_ARGS)
        for _ in range(3):
            one_cycle(c, tiers, churn=2)
        warm = jit_compilation_count()
        assert warm > 0
        for cycle in range(6):
            one_cycle(c, tiers, churn=2)
            now = jit_compilation_count()
            assert now == warm, (
                f"sparse cycle {cycle} minted {now - warm} new jit "
                "compilation(s)"
            )
        c.shutdown()


class TestSparseDeviceCache:
    def test_slab_fields_patch_and_reuse(self, monkeypatch):
        """Candidate slabs ride the device-resident snapshot cache like
        every other field: steady cycles reuse (zero slab bytes), churn
        patches/re-uploads, and the pack reports slab_bytes_shipped."""
        from kube_batch_tpu.solver.device_cache import last_pack_stats
        from tests.unit.test_cycle_pipeline import build_cluster

        monkeypatch.setenv("KBT_SOLVER_TOPK", "8")
        c = build_cluster(seed=51, groups=6, per_group=40, nodes=8)
        tiers = make_tiers(*DEFAULT_TIERS_ARGS)

        ssn = open_session(c, tiers)
        inputs, _ = tensorize(ssn)
        assert inputs is not None
        assert int(inputs.cand_idx.shape[0]) > 0
        stats = dict(last_pack_stats)
        assert stats["field_outcomes"]["cand_idx"] == "upload"  # cold
        assert stats["slab_bytes_shipped"] > 0
        close_session(ssn)

        ssn = open_session(c, tiers)
        inputs2, _ = tensorize(ssn)
        stats2 = dict(last_pack_stats)
        # Nothing changed: every cand field reuses its resident buffer.
        for f in ("cand_idx", "cand_static", "cand_info"):
            assert stats2["field_outcomes"][f] == "reuse", (f, stats2)
        assert stats2["slab_bytes_shipped"] == 0
        # And the solver consumes the resident slabs bit-exactly.
        result = solve_jit(inputs2)
        assert result.refills is not None
        close_session(ssn)
        c.shutdown()


class TestNativeSparse:
    """Native sparse loop parity (greedy_allocate_sparse vs the masked
    loop) — placement counts and capacity on randomized instances,
    including forced exhaustion/widen rounds."""

    @pytest.fixture(autouse=True)
    def _need_native(self):
        from kube_batch_tpu.native import native_available

        if not native_available():
            pytest.skip("no native toolchain")

    def _np_inputs(self, task_req, node_idle, cs=None, jobs_of=10):
        from kube_batch_tpu.solver.kernels import SolverInputs

        T, R = task_req.shape
        N = node_idle.shape[0]
        kw = dict(
            task_req=task_req, task_fit=task_req,
            task_rank=np.arange(T, dtype=np.int32),
            task_job=(np.arange(T) // jobs_of).astype(np.int32),
            task_queue=np.zeros(T, np.int32),
            task_valid=np.ones(T, bool),
            task_group=np.zeros(T, np.int32),
            node_feas=np.ones(N, bool),
            group_feas=np.ones((1, N), bool),
            pair_idx=np.zeros((0,), np.int32),
            pair_feas=np.zeros((0, N), bool),
            score_idx=np.zeros((0,), np.int32),
            score_rows=np.zeros((0, N), np.float32),
            node_idle=node_idle, node_releasing=np.zeros_like(node_idle),
            node_cap=node_idle, node_task_count=np.zeros(N, np.int32),
            node_max_tasks=np.zeros(N, np.int32),
            queue_deserved=np.full((1, R), np.inf, np.float32),
            queue_allocated=np.zeros((1, R), np.float32),
            eps=np.array([10.0, 10.0], np.float32),
            lr_weight=np.float32(1.0), br_weight=np.float32(1.0),
        )
        if cs is not None:
            kw.update(
                task_cand=cs.task_cand, cand_idx=cs.cand_idx,
                cand_static=cs.cand_static, cand_info=cs.cand_info,
            )
        return SolverInputs(**kw)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_sparse_matches_masked_counts(self, seed):
        from kube_batch_tpu.native import last_solve_stats, solve_native

        task_req, node_idle = random_case(seed, T=120, N=20)
        cs = select_for(task_req, node_idle, k=4)
        a_m, p_m = solve_native(self._np_inputs(task_req, node_idle))
        assert last_solve_stats["sparse"] is False
        a_s, p_s = solve_native(
            self._np_inputs(task_req, node_idle, cs)
        )
        assert last_solve_stats["sparse"] is True
        assert p_s == p_m
        # Capacity respected under the sparse assignment.
        used = np.zeros_like(node_idle)
        for t, n in enumerate(a_s):
            if n >= 0:
                used[n] += task_req[t]
        assert (used <= node_idle + 10.0).all()

    def test_cap_saturation_breaks_job_like_masked(self):
        """Pod-count caps saturating MID-SOLVE must break a job exactly
        like the masked loop: snapshot-time feasibility said the class
        had open nodes, but by the time its task arrives every feasible
        node is cap-saturated — the job-mate in another class must NOT
        place (regression: the sparse loop used to consult only the
        snapshot-time census and placed the mate)."""
        from kube_batch_tpu.native import solve_native
        from kube_batch_tpu.solver.kernels import SolverInputs

        N = 3
        # t0/t1: filler singleton jobs that saturate nodes 0/1 (cap 1
        # task each). t2 (job 2, group 0): feasible only on 0/1 — by
        # its turn both are capped. t3 (job 2, group 1): node 2 is free
        # and feasible, but the job is broken by t2.
        task_req = np.asarray(
            [[100.0, 64.0], [100.0, 64.0],
             [200.0, 64.0], [300.0, 64.0]],
            np.float32,
        )
        task_group = np.asarray([0, 0, 0, 1], np.int32)
        group_feas = np.asarray(
            [[True, True, False], [True, True, True]]
        )
        node_idle = np.asarray(
            [[4000.0, 1e6], [4000.0, 1e6], [4000.0, 1e6]], np.float32
        )
        kw = dict(
            task_req=task_req, task_fit=task_req,
            task_rank=np.arange(4, dtype=np.int32),
            task_job=np.asarray([0, 1, 2, 2], np.int32),
            task_queue=np.zeros(4, np.int32),
            task_valid=np.ones(4, bool),
            task_group=task_group,
            node_feas=np.ones(N, bool),
            group_feas=group_feas,
            pair_idx=np.zeros((0,), np.int32),
            pair_feas=np.zeros((0, N), bool),
            score_idx=np.zeros((0,), np.int32),
            score_rows=np.zeros((0, N), np.float32),
            node_idle=node_idle,
            node_releasing=np.zeros_like(node_idle),
            node_cap=node_idle,
            node_task_count=np.zeros(N, np.int32),
            node_max_tasks=np.asarray([1, 1, 0], np.int32),
            queue_deserved=np.full((1, 2), np.inf, np.float32),
            queue_allocated=np.zeros((1, 2), np.float32),
            eps=np.array([10.0, 10.0], np.float32),
            lr_weight=np.float32(1.0), br_weight=np.float32(1.0),
        )
        mask = CombinedMask(
            node_ok=np.ones(N, bool), task_group=task_group,
            group_rows=group_feas, pair_idx=np.zeros((0,), np.int32),
            pair_rows=np.zeros((0, N), bool),
        )
        cs = select_candidates(
            mask, {}, task_req, task_req, node_idle, node_idle,
            np.zeros_like(node_idle), np.zeros(N, np.int32),
            np.asarray([1, 1, 0], np.int32),
            np.array([10.0, 10.0], np.float32), 1.0, 1.0, 4,
        )
        a_m, p_m = solve_native(SolverInputs(**kw))
        a_s, p_s = solve_native(SolverInputs(
            **kw, task_cand=cs.task_cand, cand_idx=cs.cand_idx,
            cand_static=cs.cand_static, cand_info=cs.cand_info,
        ))
        np.testing.assert_array_equal(a_s, a_m)
        assert a_s[3] == -1  # job broken by t2's cap-saturated class
        assert p_s == p_m == 2
        # The jax sparse/dense pair must agree WITH EACH OTHER (caps
        # re-checked against current state inside the rounds on both
        # paths). Note they legitimately differ from the sequential
        # loops here: in batched round 1 t3 wins node 2 BEFORE t2's cap
        # exhaustion materializes in round 2, and a job break cannot
        # retroactively unplace a same-or-earlier-round accept (the
        # documented batched-vs-sequential divergence). The parity
        # contract is sparse == dense per backend, not jax == native.
        kwj = {
            k: jnp.asarray(v) if isinstance(v, np.ndarray) else v
            for k, v in kw.items()
        }
        dense = solve(make_inputs(**kwj))
        sparse = solve_sparse(make_inputs(
            **kwj, task_cand=jnp.asarray(cs.task_cand),
            cand_idx=jnp.asarray(cs.cand_idx),
            cand_static=jnp.asarray(cs.cand_static),
            cand_info=jnp.asarray(cs.cand_info),
        ), tail_bucket=4)
        np.testing.assert_array_equal(
            np.asarray(dense.assigned), np.asarray(sparse.assigned)
        )

    def test_exhaustion_widens_and_still_places(self):
        from kube_batch_tpu.native import last_solve_stats, solve_native

        rng = np.random.RandomState(7)
        T, N = 200, 24
        task_req = np.c_[
            rng.choice([250, 500, 1000], T), rng.choice([256, 512], T)
        ].astype(np.float32)
        node_idle = np.c_[
            np.full(N, 6000.0), np.full(N, 1e7)
        ].astype(np.float32)
        cs = select_for(task_req, node_idle, k=2)
        a_m, p_m = solve_native(self._np_inputs(task_req, node_idle))
        a_s, p_s = solve_native(
            self._np_inputs(task_req, node_idle, cs)
        )
        assert last_solve_stats["refill_rounds"] > 0
        assert p_s == p_m


def test_tensorize_emits_slabs_when_forced(monkeypatch):
    """tensorize builds + pads candidate slabs under KBT_SOLVER_TOPK,
    with the sentinel moved to the PADDED node count."""
    monkeypatch.setenv("KBT_SOLVER_TOPK", "4")
    c = make_cache()
    c.add_queue(build_queue("default"))
    for j in range(5):
        c.add_node(build_node(
            f"n{j}", build_resource_list(cpu="4", memory="8Gi")
        ))
    c.add_pod_group(build_pod_group("pg0", namespace="ns", min_member=1))
    for i in range(10):
        c.add_pod(build_pod(
            "ns", f"p{i}", "", PodPhase.PENDING, req(), group_name="pg0"
        ))
    ssn = open_session(c, make_tiers(*DEFAULT_TIERS_ARGS))
    inputs, ctx = tensorize(ssn)
    s = inputs.unpack()
    Np = int(s.node_idle.shape[0])
    cand = np.asarray(s.cand_idx)
    assert cand.shape[0] > 0
    assert cand.shape[1] == 4
    assert ((cand == Np) | (cand < len(ctx.nodes))).all()
    assert int(np.asarray(s.task_cand).max()) < cand.shape[0]
    close_session(ssn)
    c.shutdown()


def test_env_disabled_stays_dense(monkeypatch):
    monkeypatch.setenv("KBT_SOLVER_TOPK", "off")
    task_req, node_idle = random_case(0, T=20, N=8)
    assert not solve_plan(20, 8, None).sparse
    # os.environ must not leak into other tests (monkeypatch handles it).
    assert os.environ["KBT_SOLVER_TOPK"] == "off"
