"""Sharded sparse solver tests (solver/spmd.py sparse path + the
solve plan's dispatch policy + device-cache/warm composition).

Parity contract (doc/design/sparse-candidate-solver.md, sharded-solve
section): the FLAT task-sharded shard_map solve is BIT-IDENTICAL to
the single-device ``solve_sparse`` — assignment vector, node-idle and
queue accounting, refill/stage counters — on any mesh size, because
every per-row computation is row-independent and the commit consumes
the same full bid vector. The TWO-LEVEL mode is quality-approximate
but invariant-exact (capacity/budget accounting must reconcile to the
truth). The `make shard-smoke` CI target additionally replays a seeded
churn script through the full production cycle on 4 simulated host
devices against a single-device recording.
"""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import kube_batch_tpu.actions  # noqa: F401 (registers actions)
import kube_batch_tpu.plugins  # noqa: F401 (registers plugins)
from kube_batch_tpu.api import PodPhase, build_resource_list
from kube_batch_tpu.solver import (
    default_mesh,
    make_inputs,
    pad_tasks,
    plan_for,
    select_candidates,
    solve_plan,
    solve_sharded,
    solve_sparse,
    solve_sparse_spmd,
)
from kube_batch_tpu.solver import sharding as sharding_mod
from kube_batch_tpu.solver.masks import CombinedMask


def sparse_inputs(T, N, R=3, Q=3, seed=0, k=8, tight=False, gang=True,
                  starve_queue=False):
    """Synthetic slab-carrying SolverInputs through the REAL topk
    selection pass. ``tight`` shrinks node capacity so truncated slabs
    exhaust and the refill/dense-tail stage engages."""
    rng = np.random.RandomState(seed)
    task_req = rng.uniform(400.0, 4000.0, size=(T, R)).astype(np.float32)
    hi = 9000.0 if tight else 32000.0
    node_idle = rng.uniform(3000.0, hi, size=(N, R)).astype(np.float32)
    feas = rng.rand(T, N) < 0.85
    eps = np.full(R, 10.0, np.float32)
    mask = CombinedMask(
        node_ok=np.ones(N, bool),
        task_group=np.arange(T, dtype=np.int32),
        group_rows=feas,
        pair_idx=np.zeros((0,), np.int32),
        pair_rows=np.zeros((0, N), bool),
    )
    cs = select_candidates(
        mask, {}, task_req, task_req, node_idle, node_idle,
        np.zeros_like(node_idle), np.zeros(N, np.int32),
        np.zeros(N, np.int32), eps, 1.0, 1.0, k,
    )
    assert cs is not None
    deserved = np.full((Q, R), np.inf, np.float32)
    if starve_queue:
        deserved[0] = 9000.0
    jobs = (
        np.sort(rng.randint(0, max(T // 6, 1), size=T)).astype(np.int32)
        if gang else np.arange(T, dtype=np.int32)
    )
    return make_inputs(
        feas=jnp.asarray(feas),
        task_req=jnp.asarray(task_req),
        task_fit=jnp.asarray(task_req),
        task_rank=jnp.arange(T, dtype=jnp.int32),
        task_job=jnp.asarray(jobs),
        task_queue=jnp.asarray(rng.randint(0, Q, size=T), jnp.int32),
        node_idle=jnp.asarray(node_idle),
        node_releasing=jnp.zeros((N, R), jnp.float32),
        node_cap=jnp.asarray(node_idle),
        node_task_count=jnp.zeros(N, jnp.int32),
        node_max_tasks=jnp.asarray(
            rng.randint(0, 4, size=N), jnp.int32
        ),
        queue_deserved=jnp.asarray(deserved),
        queue_allocated=jnp.zeros((Q, R), jnp.float32),
        eps=jnp.asarray(eps),
        lr_weight=jnp.asarray(1.0, jnp.float32),
        br_weight=jnp.asarray(1.0, jnp.float32),
        task_cand=jnp.asarray(cs.task_cand),
        cand_idx=jnp.asarray(cs.cand_idx),
        cand_static=jnp.asarray(cs.cand_static),
        cand_info=jnp.asarray(cs.cand_info),
    )


@pytest.fixture(scope="module")
def mesh():
    m = default_mesh()
    if m is None or m.size < 8:
        pytest.skip("needs the 8-device virtual CPU mesh (conftest)")
    return m


def assert_bit_equal(single, sharded, n_tasks):
    a1 = np.asarray(single.assigned)
    a2 = np.asarray(sharded.assigned)[:n_tasks]
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_allclose(
        np.asarray(single.node_idle), np.asarray(sharded.node_idle),
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(single.queue_allocated),
        np.asarray(sharded.queue_allocated), rtol=1e-6,
    )
    assert int(single.refills) == int(sharded.refills)
    assert int(single.stages) == int(sharded.stages)


class TestFlatParity:
    def test_uncontended_bit_equal(self, mesh):
        inputs = sparse_inputs(200, 96, seed=0)
        single = solve_sparse(inputs, max_rounds=64)
        flat = solve_sparse_spmd(
            pad_tasks(inputs, mesh.size), mesh, max_rounds=64
        )
        assert_bit_equal(single, flat, 200)
        assert int((np.asarray(flat.assigned) >= 0).sum()) > 0

    @pytest.mark.parametrize("seed,T,N", [(1, 300, 72), (3, 513, 64)])
    def test_refill_and_caps_bit_equal(self, mesh, seed, T, N):
        # Tight capacity + pod-count caps + a starved queue + gang
        # job-break verdicts: slab exhaustion routes through refill and
        # the shared _dense_tail on BOTH paths (refill/stage counters
        # must agree too). T=513 exercises ragged task padding.
        inputs = sparse_inputs(
            T, N, seed=seed, tight=True, starve_queue=True
        )
        single = solve_sparse(inputs, max_rounds=64)
        flat = solve_sparse_spmd(
            pad_tasks(inputs, mesh.size), mesh, max_rounds=64
        )
        assert_bit_equal(single, flat, T)
        assert int(single.refills) > 0  # the stress actually engaged

    def test_one_device_mesh_degenerate(self):
        # A 1-device "mesh" must dispatch to the single-device sparse
        # jit (the plan's mode -> single) and stay bit-equal.
        sub = Mesh(np.asarray(jax.devices()[:1]), ("nodes",))
        inputs = sparse_inputs(200, 96, seed=0)
        single = solve_sparse(inputs, max_rounds=256)
        via = solve_sharded(inputs, plan_for(inputs, sub))
        np.testing.assert_array_equal(
            np.asarray(single.assigned), np.asarray(via.assigned)
        )
        assert sharding_mod.last_dispatch.get("mode") == "single"

    def test_two_device_submesh(self):
        sub = Mesh(np.asarray(jax.devices()[:2]), ("nodes",))
        inputs = sparse_inputs(160, 64, seed=4, tight=True)
        single = solve_sparse(inputs, max_rounds=64)
        flat = solve_sparse_spmd(
            pad_tasks(inputs, sub.size), sub, max_rounds=64
        )
        assert_bit_equal(single, flat, 160)


class TestDispatch:
    def test_env_forced_flat_through_solve_sharded(self, mesh,
                                                   monkeypatch):
        monkeypatch.setenv("KBT_SPARSE_SHARD_MODE", "flat")
        inputs = sparse_inputs(240, 64, seed=9, tight=True)
        res = solve_sharded(inputs, plan_for(inputs, mesh))
        disp = dict(sharding_mod.last_dispatch)
        assert disp["mode"] == "flat"
        assert disp["sparse_sharded"] is True
        assert disp["shards"] == mesh.size
        single = solve_sparse(inputs, max_rounds=256)
        np.testing.assert_array_equal(
            np.asarray(single.assigned), np.asarray(res.assigned)
        )
        assert int(res.reconcile_rounds) >= 1

    def test_auto_small_problem_stays_single(self, mesh, monkeypatch):
        monkeypatch.delenv("KBT_SPARSE_SHARD_MODE", raising=False)
        inputs = sparse_inputs(240, 64, seed=9)
        single = solve_sparse(inputs, max_rounds=256)
        res = solve_sharded(inputs, plan_for(inputs, mesh))
        assert sharding_mod.last_dispatch.get("mode") == "single"
        np.testing.assert_array_equal(
            np.asarray(single.assigned), np.asarray(res.assigned)
        )

    def test_policy_table(self, monkeypatch):
        monkeypatch.delenv("KBT_SPARSE_SHARD_MODE", raising=False)
        monkeypatch.setenv("KBT_SOLVER_TOPK", "8")  # every case sparse
        m8 = default_mesh()

        def mode(n_tasks, mesh):
            return solve_plan(n_tasks, 4096, mesh).mode

        assert mode(1 << 20, None) == "single"
        assert mode(1 << 10, m8) == "single"
        assert mode(1 << 17, m8) == "flat"
        assert mode(1 << 20, m8) == "two-level"
        monkeypatch.setenv("KBT_SPARSE_SHARD_MODE", "off")
        assert mode(1 << 20, m8) == "single"
        monkeypatch.setenv("KBT_SPARSE_SHARD_MODE", "flat")
        assert mode(16, m8) == "flat"
        monkeypatch.setenv("KBT_SPARSE_SHARD_MODE", "two-level")
        assert mode(16, m8) == "two-level"
        # No mesh wins over any forcing (nothing to shard over).
        assert mode(1 << 20, None) == "single"


class TestTwoLevel:
    def test_invariants_and_determinism(self, mesh, monkeypatch):
        inputs = sparse_inputs(240, 64, seed=9, tight=True,
                               starve_queue=True)
        padded = pad_tasks(inputs, mesh.size)
        two = solve_sparse_spmd(
            padded, mesh, max_rounds=64, two_level=True
        )
        T = 240
        assigned = np.asarray(two.assigned)
        req = np.asarray(padded.task_req)
        n = int(np.asarray(inputs.node_idle).shape[0])
        # Valid node range; padded/invalid tasks never placed.
        assert assigned.max(initial=-1) < n
        assert (assigned[T:] == -1).all()
        # Idle accounting reconciles to the placements (atol: the
        # psum reconcile and this reconstruction sum the same deltas
        # in different f32 orders; 1.0 is 10x under the 10.0 epsilon).
        expect = np.asarray(inputs.node_idle).astype(np.float64).copy()
        for i in np.nonzero(assigned >= 0)[0]:
            expect[assigned[i]] -= req[i]
        np.testing.assert_allclose(
            expect, np.asarray(two.node_idle)[:n], atol=1.0
        )
        # Placements satisfy the predicate mask (the global drain may
        # legitimately place OFF-slab — that is _dense_tail's full-N
        # fidelity — but never on an infeasible node).
        group_feas = np.asarray(inputs.group_feas)
        task_group = np.asarray(inputs.task_group)
        node_feas = np.asarray(inputs.node_feas)
        for i in np.nonzero(assigned[:T] >= 0)[0]:
            j = assigned[i]
            assert node_feas[j] and group_feas[task_group[i], j]
        # Deterministic: a second run is bit-identical.
        again = solve_sparse_spmd(
            padded, mesh, max_rounds=64, two_level=True
        )
        np.testing.assert_array_equal(assigned, np.asarray(again.assigned))
        # Quality sanity: the decomposition must not collapse vs the
        # global solve (spill drain recovers cross-rack placements).
        single_placed = int(
            (np.asarray(solve_sparse(inputs, max_rounds=64).assigned)
             >= 0).sum()
        )
        two_placed = int((assigned >= 0).sum())
        assert two_placed >= single_placed // 2
        assert int(two.reconcile_rounds) >= 1


class TestWarmMeshToken:
    def _fake_ssn(self, token):
        from kube_batch_tpu.solver.warm import warm_state_of

        cache = types.SimpleNamespace()
        ws = warm_state_of(cache)
        ws.valid = True
        ws.snap_gen = 4
        ws.mesh_token = token
        ws.has_releasing = False
        ws.carried = {}
        return types.SimpleNamespace(
            cache=cache, snap_gen=5, dirty_nodes={"n1"},
            dirty_jobs=set(), dirty_jobs_narrow=set(), jobs={}, queues={},
        )

    def test_plan_falls_back_on_layout_change(self, monkeypatch):
        from kube_batch_tpu.solver.warm import plan_warm

        monkeypatch.setitem(sharding_mod._layout_state, "devices", 8)
        monkeypatch.delenv("KBT_SPARSE_SHARD_MODE", raising=False)
        ssn = self._fake_ssn("8dev:two-level")
        outcome, _live = plan_warm(ssn)
        assert outcome == "mesh-changed"

    def test_plan_passes_on_matching_layout(self, monkeypatch):
        from kube_batch_tpu.solver.warm import plan_warm

        monkeypatch.setitem(sharding_mod._layout_state, "devices", 8)
        # A two-level solve earlier in the session may have pinned a
        # rack digest (suffixing the prospective token); this case is
        # about the un-suffixed match, so pin the rack state too.
        monkeypatch.setitem(sharding_mod._layout_state, "rack", None)
        monkeypatch.delenv("KBT_SPARSE_SHARD_MODE", raising=False)
        ssn = self._fake_ssn("8dev:auto")
        # Token matches -> the plan proceeds past the mesh gate (the
        # dirty node then produces the ordinary node-dirty fallback).
        assert plan_warm(ssn)[0] == "node-dirty"

    def test_unknown_layout_never_falls_back(self, monkeypatch):
        from kube_batch_tpu.solver.warm import plan_warm

        monkeypatch.setitem(sharding_mod._layout_state, "devices", None)
        ssn = self._fake_ssn("8dev:auto")
        assert plan_warm(ssn)[0] == "node-dirty"

    def test_plan_falls_back_on_rack_map_change(self, monkeypatch):
        # Same device count, same mode — but the node->rack
        # decomposition the warm state was solved under has moved (the
        # pinned token carries the rack digest suffix). Carrying the
        # old placements into a re-coordinated two-level dispatch would
        # mix rack-local solves from two different partitions.
        from kube_batch_tpu.solver.warm import plan_warm

        monkeypatch.setitem(sharding_mod._layout_state, "devices", 8)
        monkeypatch.setitem(sharding_mod._layout_state, "rack", "1a2b3c4d")
        monkeypatch.delenv("KBT_SPARSE_SHARD_MODE", raising=False)
        ssn = self._fake_ssn("8dev:auto:c8e1f00d")
        outcome, _live = plan_warm(ssn)
        assert outcome == "mesh-changed"


def _packed_arrays(seed=0, T=256, N=256, R=3):
    """A full stacked-field dict like tensorize ships (pack requires
    every PackedInputs field)."""
    rng = np.random.RandomState(seed)
    return {
        "task_f32": rng.rand(2, T, R).astype(np.float32),
        "task_i32": rng.randint(0, 4, size=(6, T)).astype(np.int32),
        "node_f32": rng.rand(3, N, R).astype(np.float32),
        "node_i32": rng.randint(0, 2, size=(3, N)).astype(np.int32),
        "group_feas": np.ones((2, N), bool),
        "pair_idx": np.zeros((0,), np.int32),
        "pair_feas": np.zeros((0, N), bool),
        "score_idx": np.zeros((0,), np.int32),
        "score_rows": np.zeros((0, N), np.float32),
        "queue_f32": rng.rand(2, 2, R).astype(np.float32),
        "misc": np.zeros(R + 2, np.float32),
        "cand_idx": rng.randint(0, N, size=(4, 8)).astype(np.int32),
        "cand_static": rng.rand(4, 8).astype(np.float32),
        "cand_info": rng.randint(0, 9, size=(3, 4)).astype(np.int32),
    }


class TestDeviceCacheLayout:
    def test_layout_flip_forces_labeled_full_reupload(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec
        from kube_batch_tpu.solver.device_cache import (
            DeviceSnapshotCache, last_pack_stats,
        )

        dc = DeviceSnapshotCache()
        arrays = _packed_arrays()
        dc.pack(dict(arrays), placement=None, layout_token="1dev:single")
        assert last_pack_stats["full_reasons"]["node_f32"] == "cold"
        # Same token, same bytes: resident reuse.
        dc.pack(dict(arrays), placement=None, layout_token="1dev:single")
        assert last_pack_stats["uploads"] == 0
        assert last_pack_stats["reuses"] == len(arrays)
        # Layout flip: every buffer re-uploads, labeled, under the new
        # placement.
        rep = NamedSharding(mesh, PartitionSpec())
        out3 = dc.pack(dict(arrays), placement=rep,
                       layout_token=f"{mesh.size}dev:flat")
        assert last_pack_stats.get("layout_change") is True
        assert last_pack_stats["full_reasons"]["node_f32"] == "mesh-change"
        assert last_pack_stats["uploads"] == len(arrays)
        assert out3.node_f32.sharding.is_equivalent_to(
            rep, out3.node_f32.ndim
        )
        np.testing.assert_array_equal(
            np.asarray(out3.node_f32), arrays["node_f32"]
        )

    def test_patch_preserves_replicated_placement(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec
        from kube_batch_tpu.solver.device_cache import (
            DeviceSnapshotCache, last_pack_stats,
        )

        rep = NamedSharding(mesh, PartitionSpec())
        dc = DeviceSnapshotCache()
        arrays = _packed_arrays(seed=1, N=512)
        token = f"{mesh.size}dev:flat"
        dc.pack(dict(arrays), placement=rep, layout_token=token)
        arrays2 = dict(arrays)
        arr2 = arrays["node_f32"].copy()
        arr2[:, 7] += 1.0  # one dirty row -> patch path
        arrays2["node_f32"] = arr2
        out = dc.pack(arrays2, placement=rep, layout_token=token)
        assert last_pack_stats["field_outcomes"]["node_f32"] == "patch"
        np.testing.assert_array_equal(np.asarray(out.node_f32), arr2)


def req():
    return build_resource_list(cpu="1", memory="2Gi")


class TestShardedActionEndToEnd:
    def _build(self, monkeypatch):
        from tests.actions.test_actions import make_cache, run_action
        from kube_batch_tpu.utils.test_utils import (
            build_node, build_pod, build_pod_group, build_queue,
        )

        monkeypatch.setenv("KBT_SOLVER", "jax")
        monkeypatch.setenv("KBT_SOLVER_TOPK", "4")
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
        run_action(c, "allocate_tpu")
        assert c.wait_for_side_effects()
        return c

    def test_forced_flat_binds_and_reports(self, mesh, monkeypatch):
        from kube_batch_tpu.actions import allocate_tpu as atpu
        from kube_batch_tpu.metrics import metrics as m

        monkeypatch.setenv("KBT_SPARSE_SHARD_MODE", "flat")
        before = m.solver_sparse_sharded.get(("flat",))
        c = self._build(monkeypatch)
        stats = dict(atpu.last_stats)
        sharded_binds = sorted(c.binder.binds.items())
        assert len(sharded_binds) == 24
        assert stats.get("sparse_engaged") is True
        assert stats.get("sparse_sharded_engaged") is True
        assert stats.get("sparse_shard_mode") == "flat"
        assert stats.get("sparse_shard_count") == mesh.size
        assert stats.get("sparse_reconcile_rounds") >= 1
        assert m.solver_sparse_sharded.get(("flat",)) == before + 1
        c.shutdown()

        # Bit-parity through the REAL action: the same cluster solved
        # single-device binds the identical (pod, node) set.
        monkeypatch.setenv("KBT_SPARSE_SHARD_MODE", "off")
        c2 = self._build(monkeypatch)
        single_binds = sorted(c2.binder.binds.items())
        assert dict(atpu.last_stats).get("sparse_sharded_engaged") is False
        assert sharded_binds == single_binds
        c2.shutdown()

    @pytest.mark.parametrize("mode,traced", [
        ("flat", True), ("off", True), ("flat", False),
    ])
    def test_shard_spans_and_commit_counters(self, mesh, monkeypatch, mode,
                                             traced):
        """A traced sharded cycle records ``shard_put`` (mode, shards) and
        ``shard_commit`` (the commit collective's counters); a
        single-device cycle records neither; with tracing off nothing is
        recorded."""
        from kube_batch_tpu.actions import allocate_tpu as atpu
        from kube_batch_tpu.obs.tracer import TRACER
        from kube_batch_tpu.solver import spmd

        monkeypatch.setenv("KBT_SPARSE_SHARD_MODE", mode)
        TRACER.reset()
        if traced:
            TRACER.enable()
        try:
            c = self._build(monkeypatch)
        finally:
            TRACER.disable()
        recorded = list(TRACER._events)
        TRACER.reset()
        c.shutdown()
        stats = dict(atpu.last_stats)
        args = {}
        for rec in recorded:
            args.setdefault(rec[0], []).append(rec[7])
        if not traced:
            assert recorded == []
        elif mode == "off":
            assert stats.get("sparse_sharded_engaged") is False
            assert "shard_put" not in args and "shard_commit" not in args
        else:
            assert args["shard_put"] == [
                {"shard_mode": "flat", "shards": mesh.size}
            ]
            assert args["shard_commit"] == [{
                "commit_bytes_per_round":
                    spmd.last_commit_stats["commit_bytes_per_round"],
                "reconcile_rounds": stats["sparse_reconcile_rounds"],
            }]
            assert stats["sparse_reconcile_rounds"] >= 1

