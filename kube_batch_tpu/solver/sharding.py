"""Multi-chip sharded solve: the production scale-out path.

The reference's only scale mechanism is a 16-goroutine fan-out over nodes
(reference util/scheduler_helper.go:84,137). The TPU-native analog runs
the solve over a 1-D ``jax.sharding.Mesh`` with explicit shard_map
programs (solver/spmd.py): the dense solve shards the NODE axis, the
candidate-sparsified solve shards the TASK axis.

Which program a cycle runs is decided once, by the solve plan
(solver/plan.py); :func:`solve_sharded` carries it out. Used by
``actions/allocate_tpu`` and by ``__graft_entry__.dryrun_multichip``.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .kernels import PackedInputs, SolverInputs, solve_jit

NODE_AXIS = "nodes"

# SolverInputs fields whose FIRST axis is the node axis.
_NODE_MAJOR = (
    "node_feas", "node_idle", "node_releasing", "node_cap",
    "node_task_count", "node_max_tasks",
)
# SolverInputs fields whose SECOND axis is the node axis ([G|P|S, N] rows).
_NODE_MINOR = ("group_feas", "pair_feas", "score_rows")
# PackedInputs stacks node tables as [k, N, ...]: node axis is axis 1.
_PACKED_NODE_MINOR = ("node_f32", "node_i32") + _NODE_MINOR


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None):
    """Join a multi-HOST jax runtime (DCN scale-out) before building the
    mesh. After this, ``jax.devices()`` spans every host's chips and
    ``default_mesh()``/``solve_sharded`` work unchanged — XLA lays intra-
    host collectives on ICI and inter-host legs on DCN; the solver code
    has no host awareness at all.

    SPMD contract: EVERY process of the distributed runtime must execute
    every sharded solve (jax multi-process collectives block until all
    participants arrive). This is therefore an API for symmetric solver
    deployments — e.g. a dedicated solver job whose replicas all call
    ``solve_sharded`` on identical inputs — NOT for scheduler replicas
    behind leader election, where only the leader would solve and the
    first collective would deadlock. The scheduler server deliberately
    does not auto-join a distributed runtime for that reason.

    Parameters default to the JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES
    / JAX_PROCESS_ID environment (the jax.distributed convention). No-op
    when no coordinator is configured (single-host mode)."""
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if not coordinator_address:
        return False
    # Idempotent: a retry path or second defensive join must not crash
    # (jax.distributed.initialize raises if called twice).
    if jax.distributed.is_initialized():
        return True
    if num_processes is None:
        env_n = os.environ.get("JAX_NUM_PROCESSES", "")
        num_processes = int(env_n) if env_n else None
    if process_id is None:
        env_id = os.environ.get("JAX_PROCESS_ID", "")
        process_id = int(env_id) if env_id else None
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def default_mesh(devices=None):
    """A 1-D node-axis mesh over ``devices`` (default: all visible
    devices), or None when only one device exists (single-chip solves
    need no mesh)."""
    devices = jax.devices() if devices is None else list(devices)
    if len(devices) < 2:
        return None
    return Mesh(np.asarray(devices), (NODE_AXIS,))


def pad_nodes(inputs, multiple: int):
    """Pad the node axis up to a multiple of ``multiple`` so shards are
    even. Padded nodes are infeasible (node_feas False) and empty, so the
    solver can never assign to them; padded mask/score rows are
    False/zero.

    On the production path this is an identity: ``tensorize`` buckets the
    node axis to multiples of 256 (snapshot.py), divisible by any
    power-of-two mesh, so the eager pad ops below only run for raw
    unbucketed inputs (tests, tools)."""
    if isinstance(inputs, PackedInputs):
        n = inputs.node_f32.shape[1]
    else:
        n = inputs.node_idle.shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return inputs

    def pad_axis(x, axis):
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths)

    if isinstance(inputs, PackedInputs):
        return inputs._replace(**{
            f: pad_axis(getattr(inputs, f), 1) for f in _PACKED_NODE_MINOR
        })
    repl = {f: pad_axis(getattr(inputs, f), 0) for f in _NODE_MAJOR}
    repl.update(
        {f: pad_axis(getattr(inputs, f), 1) for f in _NODE_MINOR}
    )
    if getattr(inputs, "cand_idx", None) is not None:
        # Candidate slabs use an invalid-node sentinel >= N; after
        # padding, the old sentinel value would alias a (padded, empty)
        # REAL row, so move it past the new node count.
        repl["cand_idx"] = jnp.where(
            inputs.cand_idx >= n, n + pad, inputs.cand_idx
        )
    return inputs._replace(**repl)


def pad_tasks(inputs: SolverInputs, multiple: int) -> SolverInputs:
    """Pad the TASK axis of a SolverInputs bundle up to a multiple of
    ``multiple`` so the sharded sparse solve's row blocks are even.
    Padded rows are invalid (``task_valid`` False), carry no resources,
    isolated job ids, and INT_MAX ranks, so no solver path can act on
    them — callers slice ``assigned[:T]`` back.

    On the production path this is an identity for power-of-two
    meshes: ``tensorize`` buckets the task axis to multiples of
    256/2048 (snapshot._task_bucket)."""
    T = inputs.task_req.shape[0]
    pad = (-T) % multiple
    if pad == 0:
        return inputs

    def pad_axis0(x: jnp.ndarray) -> jnp.ndarray:
        widths = [(0, 0)] * x.ndim
        widths[0] = (0, pad)
        return jnp.pad(x, widths)

    repl = {
        f: pad_axis0(getattr(inputs, f))
        for f in (
            "task_req", "task_fit", "task_queue", "task_group",
            "task_valid",
        )
    }
    repl["task_rank"] = jnp.concatenate([
        jnp.asarray(inputs.task_rank),
        jnp.full((pad,), jnp.iinfo(jnp.int32).max, jnp.int32),
    ])
    # Isolated job ids: padded rows must never join a real job's
    # segment reductions.
    repl["task_job"] = jnp.concatenate([
        jnp.asarray(inputs.task_job),
        jnp.arange(T, T + pad, dtype=jnp.int32),
    ])
    if getattr(inputs, "task_cand", None) is not None:
        repl["task_cand"] = pad_axis0(inputs.task_cand)
    return inputs._replace(**repl)


# Forensics of the most recent solve_sharded dispatch (mode, shard
# count, engagement), read by actions.allocate_tpu for
# last_stats/metrics attribution. Single-threaded by construction,
# like device_cache.last_pack_stats.
last_dispatch: dict = {}

# Device count + rack-map digest witnessed by the first dispatch —
# process-constant once set (a jax process cannot change its device
# set), and deliberately NEVER probed outside a solve path: the
# warm-plan and native paths stay off jax (see plan.selection_token).
_layout_state: dict = {"devices": None, "rack": None}


def rack_perm(mesh: Mesh) -> np.ndarray:
    """Topology-aligned shard→rack map for the two-level solve:
    ``rack_perm(mesh)[shard]`` is the rack (node block) shard ``shard``
    owns. Backends that expose physical placement (TPU: ``slice_index``
    + ICI ``coords``) get racks ordered by (slice, coords) so each rack
    block lands on physically adjacent chips (Tesserae-style); backends
    without coordinates (CPU meshes, older runtimes) fall back to the
    contiguous identity map, which is exactly the pre-topology
    behavior."""
    devs = list(np.asarray(mesh.devices).flat)
    keys = []
    for d in devs:
        coords = getattr(d, "coords", None)
        if coords is None:
            return np.arange(len(devs), dtype=np.int32)
        slice_idx = getattr(d, "slice_index", None)
        keys.append((
            slice_idx if slice_idx is not None else 0, tuple(coords),
        ))
    order = sorted(range(len(devs)), key=lambda i: keys[i])
    perm = np.empty(len(devs), dtype=np.int32)
    for rack, shard in enumerate(order):
        perm[shard] = rack
    return perm


def rack_digest(mesh: Optional[Mesh] = None) -> Optional[str]:
    """Short content token of the mesh's rack map, carried in the
    layout tokens so BOTH the warm-start plan and the selection caches
    invalidate when the node→rack decomposition moves (a topology-
    aligned split reshuffles which node block each shard owns). The
    contiguous identity map hashes to a stable ``c<n>`` token; None
    when no mesh exists."""
    if mesh is None:
        mesh = default_mesh()
    if mesh is None:
        return None
    perm = rack_perm(mesh)
    if np.array_equal(perm, np.arange(len(perm), dtype=np.int32)):
        return f"c{len(perm)}"
    import hashlib

    return hashlib.blake2b(perm.tobytes(), digest_size=4).hexdigest()


def _task_count(inputs) -> int:
    if isinstance(inputs, PackedInputs):
        return int(inputs.task_f32.shape[1])
    return int(inputs.task_req.shape[0])


def _node_count(inputs) -> int:
    if isinstance(inputs, PackedInputs):
        return int(inputs.node_f32.shape[1])
    return int(inputs.node_idle.shape[0])


def _note_dispatch(mode: str, shards: int, reason: str = None) -> None:
    last_dispatch.clear()
    last_dispatch.update(
        mode=mode,
        shards=shards,
        sparse_sharded=mode in ("flat", "two-level"),
    )
    if reason:
        last_dispatch["reason"] = reason
    # First dispatch pins the process's device count + rack-map digest
    # for the selection token (jax is live here by definition).
    if _layout_state["devices"] is None:
        _layout_state["devices"] = jax.device_count()
        _layout_state["rack"] = rack_digest()


def sharded_step(inputs, plan, max_rounds: int = 256):
    """Return ``(step_fn, device_inputs)`` for a mesh ``plan``
    (solver/plan.py): inputs padded and device_put onto the mesh ONCE,
    plus the cached jitted step to run on them. Use this when solving
    the same snapshot repeatedly (benchmarks, re-solve loops) so the
    host→device transfer is not re-paid per call.

    ``dense-spmd`` is the hierarchical shard_map dense solver
    (solver/spmd.py): node columns sharded, node/queue tables
    replicated. ``flat``/``two-level`` are the task-sharded sparse
    solves: inputs replicated, slab rows sharded inside the step."""
    from ..obs.tracer import span
    from .spmd import (
        _spmd_sparse_step,
        _spmd_step,
        note_commit_stats,
        sparse_spmd_shardings_for,
        spmd_shardings_for,
    )

    mesh = plan.mesh
    if plan.mode == "dense-spmd":
        inputs = pad_nodes(inputs, mesh.size)
        inputs = jax.device_put(inputs, spmd_shardings_for(inputs, mesh))
        step = _spmd_step(mesh, plan.staged, max_rounds, plan.tail_bucket)
        return step, inputs
    two_level = plan.mode == "two-level"
    note_commit_stats(inputs)
    with span("shard_put", shard_mode=plan.mode, shards=mesh.size):
        if not isinstance(inputs, PackedInputs):
            inputs = pad_tasks(inputs, mesh.size)
            if two_level:
                inputs = pad_nodes(inputs, mesh.size)
        elif _task_count(inputs) % mesh.size or (
            two_level and _node_count(inputs) % mesh.size
        ):
            # A silent mis-split would simply never solve the remainder
            # rows; refuse loudly (the plan routes ragged packed
            # bundles to the single-device jit).
            raise ValueError(
                f"sparse sharded solve needs task{'/node' if two_level else ''} "
                f"axes divisible by the mesh size {mesh.size}"
            )
        inputs = jax.device_put(
            inputs, sparse_spmd_shardings_for(inputs, mesh)
        )
        step = _spmd_sparse_step(
            mesh, max_rounds, plan.tail_bucket, two_level
        )
    return step, inputs


def solve_sharded(inputs, plan, max_rounds: int = 256):
    """Carry out ``plan`` (solver/plan.py) on ``inputs``: ``single``
    runs the module-level ``solve_jit`` (sparse or dense by the
    bundle's slabs, staged by the same rule the plan records), the
    other modes the cached shard_map steps of :func:`sharded_step`.
    Same semantics and results as the single-device solve, except the
    quality-approximate ``two-level`` mode. Records the dispatch in
    ``last_dispatch``."""
    _note_dispatch(plan.mode, plan.shards, plan.fallback)
    if plan.mode == "single":
        return solve_jit(inputs, max_rounds=max_rounds)
    step, dev_inputs = sharded_step(inputs, plan, max_rounds)
    result = step(dev_inputs)
    T = _task_count(inputs)
    if int(result.assigned.shape[0]) != T:
        result = result._replace(assigned=result.assigned[:T])
    return result
