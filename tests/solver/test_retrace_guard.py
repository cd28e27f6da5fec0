"""Retrace-regression guard: steady/delta cycles must not mint new jit
compilations.

The whole device-resident design leans on shape stability — task/node/
group/pair axes are bucketed (snapshot._task_bucket/_pow2/128s) and the
patch row axis is power-of-two bucketed — so a long-running scheduler
compiles a bounded set of programs and then runs trace-free. A shape or
dtype drift anywhere in the pack (a field stacked in a different order,
an un-bucketed axis, a float64 leak) would silently reintroduce
per-cycle tracing: ~seconds of XLA compile inside a ~10 ms cycle
budget. This test pins the invariant with the compilation-cache
counters (``jit_compilation_count``: solve jits + device-cache patch
jits) across churning cycles that stay inside their buckets.
"""

import numpy as np
import pytest

import kube_batch_tpu.actions  # noqa: F401 (registers actions)
import kube_batch_tpu.plugins  # noqa: F401 (registers plugins)
from kube_batch_tpu.framework import close_session, open_session
from kube_batch_tpu.solver import (
    jit_compilation_count,
    solve_jit,
    solve_sharded,
    tensorize,
)

from tests.actions.test_actions import DEFAULT_TIERS_ARGS, make_tiers
from tests.unit.test_cycle_pipeline import build_cluster


WARM_CYCLES = 3   # cold pack + first patch buckets + solve compile
GUARD_CYCLES = 6  # steady/delta cycles that must stay trace-free


def one_cycle(cache, tiers, churn, solver=None):
    """One tensorize → solve → apply-some cycle; churn keeps every axis
    inside its shape bucket (fixed task count per step, fixed node
    fan-out) so no re-jit is legitimate. ``solver(inputs, ctx)``, when
    given, replaces the single-device jit."""
    ssn = open_session(cache, tiers)
    inputs, ctx = tensorize(ssn)
    placed = 0
    if inputs is not None:
        result = solver(inputs, ctx) if solver else solve_jit(inputs)
        assigned = np.asarray(result.assigned)
        # Apply a FIXED-SIZE slice of the assignment through the
        # session so the mirror churns by the same amount every cycle.
        pairs = []
        for i in np.nonzero(assigned[: len(ctx.tasks)] >= 0)[0][:churn]:
            pairs.append((ctx.tasks[i], ctx.nodes[assigned[i]].name))
        if pairs:
            placed = ssn.allocate_batch(pairs)
    assert cache.wait_for_side_effects()
    assert cache.wait_for_bookkeeping()
    close_session(ssn)
    return placed


def test_zero_new_compilations_across_steady_delta_cycles():
    # 240 pending tasks: stays inside the 256-row task bucket for the
    # whole run (churn of 2/cycle drains 18 by the end).
    c = build_cluster(seed=43, groups=6, per_group=40, nodes=8)
    tiers = make_tiers(*DEFAULT_TIERS_ARGS)
    for _ in range(WARM_CYCLES):
        one_cycle(c, tiers, churn=2)
    warm = jit_compilation_count()
    assert warm > 0  # the solve jit at least compiled once
    for cycle in range(GUARD_CYCLES):
        one_cycle(c, tiers, churn=2)
        now = jit_compilation_count()
        assert now == warm, (
            f"cycle {cycle} minted {now - warm} new jit compilation(s) "
            "— a shape/dtype drift reintroduced per-cycle tracing"
        )
    c.shutdown()


def test_zero_new_compilations_with_serving_rows_present():
    """Serving twin (doc/design/serving.md): SLO-constrained jobs add
    feasibility-mask group rows and per-task score rows to the pack.
    With a fixed set of constraint signatures the group axis is as
    shape-stable as every other axis — steady/delta cycles over a mixed
    serving+batch snapshot on a labeled (heterogeneous) node pool must
    stay trace-free after warmup."""
    from kube_batch_tpu.api.serving import (
        CAPACITY_TYPE_LABEL_KEY,
        RESERVED_ONLY_ANNOTATION_KEY,
        SLO_SECONDS_ANNOTATION_KEY,
        TOPOLOGY_TIER_LABEL_KEY,
        WORKLOAD_CLASS_ANNOTATION_KEY,
    )
    from kube_batch_tpu.api import PodPhase, build_resource_list
    from kube_batch_tpu.utils.test_utils import build_node, build_pod

    c = build_cluster(seed=53, groups=6, per_group=40, nodes=6)
    # Heterogeneous extension of the pool: labeled spot + tiered nodes
    # so the serving rows are genuinely non-trivial.
    for j, labels in enumerate((
        {CAPACITY_TYPE_LABEL_KEY: "spot"},
        {TOPOLOGY_TIER_LABEL_KEY: "2"},
    )):
        c.add_node(build_node(
            f"hn{j}",
            build_resource_list(cpu="16", memory="64Gi", pods=110),
            labels=labels,
        ))
    # One serving deployment (shared constraint signature) riding an
    # existing pod group's queue: 8 replicas, reserved-only + SLO.
    for i in range(8):
        pod = build_pod(
            "ns", f"serve-{i}", "", PodPhase.PENDING,
            build_resource_list(cpu="250m", memory="256Mi"),
            group_name="pg0",
        )
        pod.metadata.annotations.update({
            WORKLOAD_CLASS_ANNOTATION_KEY: "serving",
            SLO_SECONDS_ANNOTATION_KEY: "2.0",
            RESERVED_ONLY_ANNOTATION_KEY: "1",
        })
        c.add_pod(pod)
    tiers = make_tiers(
        ["priority", "gang", "conformance"],
        ["drf", "predicates", "proportion", "nodeorder", "serving"],
    )
    for _ in range(WARM_CYCLES):
        one_cycle(c, tiers, churn=2)
    warm = jit_compilation_count()
    assert warm > 0
    for cycle in range(GUARD_CYCLES):
        one_cycle(c, tiers, churn=2)
        now = jit_compilation_count()
        assert now == warm, (
            f"serving cycle {cycle} minted {now - warm} new jit "
            "compilation(s) — the serving mask/score rows broke the "
            "shape-stability contract"
        )
    c.shutdown()


def test_zero_new_compilations_sharded_sparse_cycles(monkeypatch):
    """The sharded-sparse twin: steady/delta cycles through the
    task-sharded shard_map sparse solve (forced slabs + flat mode on
    the 8-device mesh) must compile a bounded step set during warmup
    and then go flat — the sharded step AND the replicated-placement
    patch jits are all in the `jit_compilation_count` census
    (spmd._jitted_steps weakrefs + patch_jit_cache_size). Each cycle
    dispatches the plan tensorize built, as the allocate action does."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device virtual CPU mesh")
    monkeypatch.setenv("KBT_SOLVER_TOPK", "8")
    monkeypatch.setenv("KBT_SPARSE_SHARD_MODE", "flat")
    from kube_batch_tpu.solver import sharding as sharding_mod

    def planned(inputs, ctx):
        return solve_sharded(inputs, ctx.plan)

    c = build_cluster(seed=47, groups=6, per_group=40, nodes=8)
    tiers = make_tiers(*DEFAULT_TIERS_ARGS)
    for _ in range(WARM_CYCLES):
        one_cycle(c, tiers, churn=2, solver=planned)
    assert sharding_mod.last_dispatch.get("mode") == "flat"
    warm = jit_compilation_count()
    assert warm > 0
    for cycle in range(GUARD_CYCLES):
        one_cycle(c, tiers, churn=2, solver=planned)
        now = jit_compilation_count()
        assert now == warm, (
            f"sharded sparse cycle {cycle} minted {now - warm} new jit "
            "compilation(s) — a shape/dtype/layout drift reintroduced "
            "per-cycle tracing on the sharded path"
        )
    c.shutdown()
