"""The solve plan (solver/plan.py): one decision per cycle of which
device program runs, carried from tensorize to the dispatch as is.

The decision table pins each side of every threshold and both
environment overrides; the end-to-end case checks on a 4-device mesh
that the plan tensorize builds is the mode the action dispatches and
the layout the device cache keeps its buffers under."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

import kube_batch_tpu.actions  # noqa: F401  (registers actions)
import kube_batch_tpu.plugins  # noqa: F401  (registers plugins)
from kube_batch_tpu.api import PodPhase, build_resource_list
from kube_batch_tpu.solver import plan as plan_mod
from kube_batch_tpu.solver import sharding
from kube_batch_tpu.solver.device_cache import device_cache_of
from kube_batch_tpu.utils.test_utils import (
    build_node,
    build_pod,
    build_pod_group,
    build_queue,
)

from tests.actions.test_actions import make_cache, run_action

FLAT = {"KBT_SPARSE_SHARD_MODE": "flat", "KBT_SOLVER_TOPK": "8"}
TWO = {"KBT_SPARSE_SHARD_MODE": "two-level", "KBT_SOLVER_TOPK": "8"}

# (id, env, n_tasks, n_nodes, mesh size (0 = none), padded, expected)
CASES = [
    # Sparse policy: each side of the task, node and cell floors.
    ("tasks-below-floor", {}, 63, 1 << 15, 0, None,
     dict(sparse=False, reason="small-problem", mode="single")),
    ("tasks-at-floor", {}, 64, 1 << 15, 0, None,
     dict(sparse=True, k=64, reason="size-policy", mode="single")),
    ("nodes-below-floor", {}, 8192, 1023, 0, None,
     dict(sparse=False, reason="small-problem")),
    ("nodes-at-floor", {}, 8192, 1024, 0, None,
     dict(sparse=True, reason="size-policy")),
    ("cells-below-floor", {}, 1023, 1025, 0, None,
     dict(sparse=False, reason="small-problem")),
    ("cells-at-floor", {}, 1024, 1024, 0, None,
     dict(sparse=True, reason="size-policy")),
    # Sharded-sparse mode over a mesh.
    ("below-shard-floor", {}, 65528, 4096, 8, None,
     dict(sparse=True, mode="single", placement=None,
          layout_token="8dev:single:c8")),
    ("at-shard-floor", {}, 65536, 4096, 8, None,
     dict(sparse=True, mode="flat", layout_token="8dev:flat:c8")),
    ("below-two-level", {}, (1 << 19) - 8, 4096, 8, None,
     dict(mode="flat")),
    ("at-two-level", {}, 1 << 19, 4096, 8, None,
     dict(mode="two-level", layout_token="8dev:two-level:c8")),
    ("two-level-needs-four", {}, 1 << 19, 4096, 2, None,
     dict(mode="flat", layout_token="2dev:flat:c2")),
    ("dense-over-mesh", {}, 512, 512, 8, None,
     dict(sparse=False, mode="dense-spmd", placement=None,
          layout_token="8dev:single:c8", staged=False)),
    # Staged-or-full rule for dense solves.
    ("staged-at-both", {}, 16384, 768, 0, None,
     dict(sparse=False, staged=True)),
    ("staged-nodes-below", {}, 16384, 767, 0, None, dict(staged=False)),
    ("staged-tasks-below", {}, 16383, 768, 0, None, dict(staged=False)),
    ("staged-counts-mesh-padding", {}, 16384, 761, 8, None,
     dict(mode="dense-spmd", staged=True)),
    # Ragged packed bundles fall back to the single-device jit.
    ("ragged-tasks", FLAT, 100, 64, 8, (100, 64),
     dict(sparse=True, mode="single", fallback="ragged-axes",
          placement=None, layout_token="8dev:single:c8")),
    ("ragged-nodes-two-level", TWO, 256, 100, 8, (256, 100),
     dict(mode="single", fallback="ragged-axes")),
    ("even-nodes-two-level", TWO, 256, 128, 8, (256, 128),
     dict(mode="two-level", fallback=None)),
    # Environment overrides.
    ("topk-env-forced", {"KBT_SOLVER_TOPK": "12"}, 10, 10, 0, None,
     dict(sparse=True, k=16, reason="env-forced")),
    ("topk-env-off", {"KBT_SOLVER_TOPK": "off"}, 1 << 20, 1 << 17, 0, None,
     dict(sparse=False, k=0, reason="env-disabled")),
    ("shard-env-off", {"KBT_SPARSE_SHARD_MODE": "off"}, 1 << 20, 4096, 8,
     None, dict(sparse=True, mode="single")),
    ("shard-env-flat", FLAT, 256, 64, 8, None,
     dict(sparse=True, mode="flat", layout_token="8dev:flat:c8")),
    ("shard-env-without-mesh", FLAT, 256, 64, 0, None,
     dict(mode="single", layout_token="1dev:single")),
]


def _mesh(size):
    if not size:
        return None
    if len(jax.devices()) < size:
        pytest.skip(f"needs {size} devices")
    return Mesh(np.asarray(jax.devices()[:size]), ("nodes",))


@pytest.mark.parametrize(
    "env,n_tasks,n_nodes,shards,padded,expect",
    [c[1:] for c in CASES], ids=[c[0] for c in CASES],
)
def test_decision_table(monkeypatch, env, n_tasks, n_nodes, shards, padded,
                        expect):
    for name in ("KBT_SOLVER_TOPK", "KBT_SPARSE_SHARD_MODE"):
        monkeypatch.delenv(name, raising=False)
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    plan = plan_mod.solve_plan(n_tasks, n_nodes, _mesh(shards), padded)
    got = {f: getattr(plan, f) for f in expect}
    assert got == expect
    # Replicated buffers exactly when the solve shards the sparse path.
    assert (plan.placement is not None) == (
        plan.mode in plan_mod.SPARSE_SHARDED
    )
    assert plan.shards == max(shards, 1)


def test_k_covering_the_nodes_stays_dense(monkeypatch):
    # Unreachable with the default K (4·64 < the 1,024-node floor), so
    # the K side of the rule is pinned with a wider default.
    monkeypatch.delenv("KBT_SOLVER_TOPK", raising=False)
    monkeypatch.setattr(plan_mod, "DEFAULT_K", 512)
    covered = plan_mod.solve_plan(8192, 2048, None)
    assert (covered.sparse, covered.reason) == (False, "k-covers-nodes")
    wider = plan_mod.solve_plan(8192, 2049, None)
    assert (wider.sparse, wider.reason) == (True, "size-policy")


def test_dense_form_and_rungs(monkeypatch):
    for name, value in FLAT.items():
        monkeypatch.setenv(name, value)
    plan = plan_mod.solve_plan(256, 64, _mesh(8))
    assert plan.rungs() == ["sparse", "dense"]
    dense = plan.dense("ladder-degraded")
    assert (dense.sparse, dense.reason, dense.mode) == (
        False, "ladder-degraded", "dense-spmd"
    )
    assert dense.placement is None
    assert dense.layout_token == "8dev:single:c8"
    assert dense.rungs() == ["dense"]
    assert dense.dense("other") is dense


def test_flat_plan_is_what_the_cycle_dispatches(monkeypatch):
    """On a 4-device mesh with flat forced: the shard mode is decided
    once a cycle, tensorize's plan mode is the mode the action
    dispatched, the device cache holds its buffers under the plan's
    residency token, and a second identical cycle re-uploads nothing."""
    from kube_batch_tpu.actions import allocate_tpu as atpu

    mesh4 = _mesh(4)
    monkeypatch.setattr(sharding, "default_mesh", lambda devices=None: mesh4)
    monkeypatch.setenv("KBT_SOLVER", "jax")
    monkeypatch.setenv("KBT_SOLVER_TOPK", "4")
    monkeypatch.setenv("KBT_SPARSE_SHARD_MODE", "flat")
    monkeypatch.setenv("KBT_WARM", "0")  # every cycle tensorizes anew
    plans = []
    tensorize = atpu.tensorize

    def spy(ssn, **kw):
        inputs, ctx = tensorize(ssn, **kw)
        plans.append(ctx.plan)
        return inputs, ctx

    monkeypatch.setattr(atpu, "tensorize", spy)
    decided = []
    shard_mode = plan_mod._shard_mode

    def counted(n_tasks, shards):
        decided.append(n_tasks)
        return shard_mode(n_tasks, shards)

    monkeypatch.setattr(plan_mod, "_shard_mode", counted)
    c = make_cache()
    c.add_queue(build_queue("default"))
    for j in range(8):
        c.add_node(build_node(
            f"n{j}", build_resource_list(cpu="4", memory="8Gi")
        ))
    # Requests no node can hold: every cycle solves the same snapshot.
    c.add_pod_group(build_pod_group("pg0", namespace="ns", min_member=1))
    for i in range(6):
        c.add_pod(build_pod(
            "ns", f"pg0-p{i}", "", PodPhase.PENDING,
            build_resource_list(cpu="64", memory="1Gi"), group_name="pg0",
        ))
    try:
        for cycle in range(2):
            run_action(c, "allocate_tpu")
            plan = plans[-1]
            assert plan.mode == "flat"
            assert sharding.last_dispatch["mode"] == plan.mode
            assert sharding.last_dispatch["shards"] == 4
            assert device_cache_of(c).layout_token == plan.layout_token
            assert plan.layout_token == "4dev:flat:c4"
            assert atpu.last_stats["solve_ladder"] == [
                {"rung": "sparse", "outcome": "ok"}
            ]
            assert len(decided) == cycle + 1  # the mode, once a cycle
        assert len(plans) == 2
        assert "device_full_reasons" not in atpu.last_stats
        assert atpu.last_stats["device_uploads"] == 0
    finally:
        c.shutdown()
