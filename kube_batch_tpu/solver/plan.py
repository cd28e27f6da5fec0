"""The solve plan: which device program a cycle runs, decided once.

Tensorize builds one :class:`SolvePlan` per cycle (:func:`solve_plan`)
from the snapshot's task and node counts and the device mesh. Everything
downstream takes its answer from the plan and decides nothing itself:

- the candidate-selection pass and its cache signature (``sparse``,
  ``k``, ``sel_token``);
- the device cache's placement and residency key for the packed
  buffers (``placement``, ``layout_token``);
- ``sharding.solve_sharded``, which carries out ``mode``;
- the allocate action's degradation ladder (:meth:`SolvePlan.rungs`,
  :meth:`SolvePlan.dense`).

This module is the only reader of ``KBT_SOLVER_TOPK`` (an integer forces
that K at any size; ``0``/``off``/``dense`` disables sparsification) and
``KBT_SPARSE_SHARD_MODE`` (``off``/``single``, ``flat``, ``two-level``
force the sharded-sparse mode; unset = the shape policy below).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, List, Optional, Tuple

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import sharding
from .kernels import PackedInputs, staged_rule
from .topk import _pow2

# Sparsification pays off once the dense [T, N] structures dominate and
# the slab is a real subset; below these the dense solvers win outright.
# The task floor is a PRODUCT bound, not a task count: a 500-task
# arrival batch against 5 000 nodes is 2.5 M dense score cells (~13 ms
# native) where selection costs C·N for a handful of classes — exactly
# the warm steady-cycle shape, so small-T/large-N problems sparsify too.
_SPARSE_MIN_TASKS = 64
_SPARSE_MIN_CELLS = 1 << 20
_SPARSE_MIN_NODES = 1024
DEFAULT_K = 64

# Below this task count the single-device sparse jit wins outright: the
# slab rounds do O(T·K) work with no [T, N] structures, and the sharded
# path pays two collectives per commit.
_SPARSE_SHARD_MIN_TASKS = 1 << 16
# Past this task count (and a >=4-device mesh) the per-commit
# collective cadence itself dominates and the policy moves to the
# two-level per-rack solve (collective-free local phase, one psum
# reconcile) — quality-approximate, so deliberately far past every
# parity-suite shape.
_TWO_LEVEL_MIN_TASKS = 1 << 19

SPARSE_SHARDED = ("flat", "two-level")


@dataclass(frozen=True)
class SolvePlan:
    """One cycle's solve path.

    ``mode`` is ``single`` (the single-device ``solve_jit``, sparse or
    dense by the bundle's slabs), ``flat`` / ``two-level`` (the
    task-sharded shard_map sparse solve) or ``dense-spmd`` (the
    node-sharded shard_map dense solve). ``tasks``/``nodes`` are the
    bundle's padded axes; ``staged`` is :func:`kernels.staged_rule` on
    them (``solve_jit`` applies the same rule inside its trace).
    ``layout_token`` keys the device cache's residency;
    ``sel_token`` keys the selection caches and the warm plan. Both
    strings are compared against saved state, so their formats are
    fixed."""

    sparse: bool
    k: int
    reason: str
    mode: str
    mesh: Optional[Mesh]
    placement: Optional[NamedSharding]
    layout_token: str
    sel_token: Optional[str]
    staged: bool
    tasks: int
    nodes: int
    fallback: Optional[str] = None
    tail_bucket: int = 3072

    @property
    def shards(self) -> int:
        return self.mesh.size if self.mesh is not None else 1

    def dense(self, reason: str) -> "SolvePlan":
        """The same cycle without candidate slabs (selection fell back,
        or the ladder left the sparse rung). Touches no device."""
        if not self.sparse:
            return self
        return _lay_out(False, self.k, reason, self.tasks, self.nodes,
                        self.mesh, self.sel_token)

    def rungs(self) -> List[str]:
        """The ladder's device rungs, top first."""
        return (["sparse"] if self.sparse else []) + ["dense"]


def _topk(n_tasks: int, n_nodes: int) -> Tuple[bool, int, str]:
    """Sparse on/off, K and the reason for a (T, N) snapshot. K is
    power-of-two bucketed so a configured K never mints per-value jit
    variants."""
    raw = os.environ.get("KBT_SOLVER_TOPK", "").strip().lower()
    if raw in ("0", "off", "dense", "disable", "disabled", "false"):
        return False, 0, "env-disabled"
    k = DEFAULT_K
    forced = False
    if raw:
        try:
            k = max(1, int(raw))
            forced = True
        except ValueError:
            pass
    k = _pow2(k)
    if forced:
        return True, k, "env-forced"
    if (
        n_tasks < _SPARSE_MIN_TASKS
        or n_nodes < _SPARSE_MIN_NODES
        or n_tasks * n_nodes < _SPARSE_MIN_CELLS
    ):
        return False, k, "small-problem"
    if 4 * k >= n_nodes:
        return False, k, "k-covers-nodes"
    return True, k, "size-policy"


def _shard_mode(n_tasks: int, shards: int) -> str:
    raw = os.environ.get("KBT_SPARSE_SHARD_MODE", "").strip().lower()
    if raw in ("off", "single", "0", "disable", "disabled"):
        return "single"
    if raw in ("flat", "1", "force"):
        return "flat"
    if raw in ("two-level", "two_level", "2", "hierarchical"):
        return "two-level"
    if n_tasks < _SPARSE_SHARD_MIN_TASKS:
        return "single"
    if n_tasks >= _TWO_LEVEL_MIN_TASKS and shards >= 4:
        return "two-level"
    return "flat"


def selection_token() -> Optional[str]:
    """``"{n}dev:{KBT_SPARSE_SHARD_MODE or 'auto'}[:rack]"``, or None
    before the first dispatch has pinned the device count (a process
    that never solved on a device has no layout to drift from). Never
    probes the backend, so the warm plan may call it before tensorize
    on the native route."""
    n = sharding._layout_state["devices"]
    if n is None:
        return None
    mode = os.environ.get("KBT_SPARSE_SHARD_MODE", "").strip().lower()
    token = f"{n}dev:{mode or 'auto'}"
    rack = sharding._layout_state.get("rack")
    # Rack suffix only when the dispatch pinned a rack map — tokens
    # from pre-topology processes (saved warm states) keep comparing
    # equal to themselves.
    return f"{token}:{rack}" if rack else token


def _lay_out(sparse: bool, k: int, reason: str, tasks: int, nodes: int,
             mesh: Optional[Mesh], sel_token: Optional[str]) -> SolvePlan:
    shards = mesh.size if mesh is not None else 1
    mode, fallback = "single", None
    if shards > 1:
        mode = _shard_mode(tasks, shards) if sparse else "dense-spmd"
        if mode in SPARSE_SHARDED and (
            tasks % shards or (mode == "two-level" and nodes % shards)
        ):
            # A packed bundle cannot be re-padded without defeating
            # device residency; production buckets divide every pow2
            # mesh, so ragged axes are a test/tool corner.
            mode, fallback = "single", "ragged-axes"
    sharded = mode in SPARSE_SHARDED
    token = f"{shards}dev:{mode if sharded else 'single'}"
    rack = sharding.rack_digest(mesh) if mesh is not None else None
    if rack:
        # Rack-map changes must re-key device residency: a moved
        # node→rack split invalidates resident selection keys and the
        # packed buffers' layout assumptions together.
        token = f"{token}:{rack}"
    if mode == "dense-spmd":
        nodes_run = -(-nodes // shards) * shards  # sharding.pad_nodes
    else:
        nodes_run = nodes
    return SolvePlan(
        sparse=sparse, k=k, reason=reason, mode=mode, mesh=mesh,
        # Sharded sparse solves read resident buffers replicated on the
        # mesh, so the shard_map step never re-lays them out per cycle.
        placement=NamedSharding(mesh, P()) if sharded else None,
        layout_token=token, sel_token=sel_token,
        staged=staged_rule(tasks, nodes_run), tasks=tasks, nodes=nodes,
        fallback=fallback,
    )


def solve_plan(n_tasks: int, n_nodes: int, mesh: Optional[Mesh],
               padded: Optional[Tuple[int, int]] = None) -> SolvePlan:
    """The plan for a snapshot of ``n_tasks`` x ``n_nodes`` (the sparse
    policy's inputs) whose bundle axes are ``padded`` (default: the
    same), solved over ``mesh`` (None: one device)."""
    sparse, k, reason = _topk(n_tasks, n_nodes)
    tasks, nodes = padded or (n_tasks, n_nodes)
    return _lay_out(sparse, k, reason, tasks, nodes, mesh,
                    selection_token())


def plan_for(inputs: Any, mesh: Optional[Mesh] = None) -> SolvePlan:
    """The plan for an already-built bundle (tests, tools): sparse iff
    it carries candidate slabs. An unpacked bundle's axes count as the
    sharded dispatch pads them."""
    tasks = sharding._task_count(inputs)
    nodes = sharding._node_count(inputs)
    if mesh is not None and not isinstance(inputs, PackedInputs):
        tasks = -(-tasks // mesh.size) * mesh.size
        nodes = -(-nodes // mesh.size) * mesh.size
    cand = getattr(inputs, "cand_idx", None)
    sparse = cand is not None and cand.shape[0] > 0
    k = int(cand.shape[1]) if sparse else 0
    return _lay_out(sparse, k, "bundle", tasks, nodes, mesh,
                    selection_token())
