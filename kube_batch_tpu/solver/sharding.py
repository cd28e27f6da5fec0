"""Multi-chip sharded solve: the production scale-out path.

The reference's only scale mechanism is a 16-goroutine fan-out over nodes
(reference util/scheduler_helper.go:84,137). The TPU-native analog shards
the NODE axis — the cluster-size scale axis — across a 1-D
``jax.sharding.Mesh``: every [T, N] intermediate (feasibility mask, score
matrix, bid keys) partitions by node shard, task-major vectors stay
replicated, and the global per-task argmax over nodes plus the assignment
scatter induce the cross-shard collectives, which XLA emits under GSPMD
(no hand-written collectives; they ride ICI on real hardware).

Used by ``actions/allocate_tpu`` when more than one device is visible and
by ``__graft_entry__.dryrun_multichip`` (the driver's multi-chip check).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .kernels import PackedInputs, SolverInputs, solve, solve_auto, solve_staged

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
    host collectives on ICI and inter-host legs on DCN under GSPMD; the
    solver code has no host awareness at all.

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


def shardings_for(inputs, mesh: Mesh):
    """A pytree of NamedShardings matching ``inputs`` (SolverInputs or
    PackedInputs): node-axis fields partitioned over the mesh, everything
    else replicated."""
    rep = NamedSharding(mesh, P())
    major = NamedSharding(mesh, P(NODE_AXIS))
    minor = NamedSharding(mesh, P(None, NODE_AXIS))
    cls = type(inputs)

    def spec(f, sh):
        # Optional fields (candidate slabs on legacy bundles) may be
        # None; the sharding pytree must mirror that or device_put's
        # treedefs mismatch. Candidate slabs are class-row tables (node
        # IDS, not node columns), so they replicate.
        return None if getattr(inputs, f, None) is None else sh

    if isinstance(inputs, PackedInputs):
        return cls(**{
            f: spec(f, minor if f in _PACKED_NODE_MINOR else rep)
            for f in cls._fields
        })
    return cls(**{
        f: spec(
            f,
            major if f in _NODE_MAJOR
            else minor if f in _NODE_MINOR else rep,
        )
        for f in cls._fields
    })


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


# ---------------------------------------------------------------------------
# Sharded-sparse dispatch policy + layout tokens (PR 12).
# ---------------------------------------------------------------------------

# Below this task count the single-device sparse jit wins outright: the
# slab rounds do O(T·K) work with no [T, N] structures, and the sharded
# path pays two collectives per commit; the crossover mirrors the
# existing K·s<N rationale for keeping slab inputs off the dense mesh.
_SPARSE_SHARD_MIN_TASKS = 1 << 16
# Past this task count (and a >=4-device mesh) the per-commit
# collective cadence itself dominates and the policy moves to the
# two-level per-rack solve (collective-free local phase, one psum
# reconcile) — quality-approximate, so deliberately far past every
# parity-suite shape.
_TWO_LEVEL_MIN_TASKS = 1 << 19

# Forensics of the most recent solve_sharded dispatch (mode, shard
# count, engagement), read by actions.allocate_tpu for
# last_stats/metrics attribution. Single-threaded by construction,
# like device_cache.last_pack_stats.
last_dispatch: dict = {}

# Device count + rack-map digest witnessed by the first sharded
# dispatch — process-constant once set (a jax process cannot change its
# device set), and deliberately NEVER probed outside a solve path: the
# warm-plan and native paths stay off jax (see prospective_layout_token).
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


def sparse_shard_mode(n_tasks: int, mesh: Optional[Mesh]) -> str:
    """Resolve the sharded-sparse dispatch mode for a snapshot:
    ``single`` (single-device sparse jit), ``flat`` (task-sharded
    shard_map, bit-equal to single), or ``two-level`` (per-rack solve +
    global reconciliation, quality-approximate). ``KBT_SPARSE_SHARD_MODE``
    forces a mode (``off``/``single``, ``flat``, ``two-level``); unset
    = the shape policy above."""
    if mesh is None or mesh.size < 2:
        return "single"
    raw = os.environ.get("KBT_SPARSE_SHARD_MODE", "").strip().lower()
    if raw in ("off", "single", "0", "disable", "disabled"):
        return "single"
    if raw in ("flat", "1", "force"):
        return "flat"
    if raw in ("two-level", "two_level", "2", "hierarchical"):
        return "two-level"
    if n_tasks < _SPARSE_SHARD_MIN_TASKS:
        return "single"
    if n_tasks >= _TWO_LEVEL_MIN_TASKS and mesh.size >= 4:
        return "two-level"
    return "flat"


def prospective_layout_token() -> Optional[str]:
    """The solver layout a solve dispatched NOW would run under, or
    None when no sharded dispatch has happened yet (a process that
    never solved on a device has no layout to drift from). Consumed by the warm-start plan: a token change voids
    carried verdicts with the labeled ``mesh-changed`` fallback."""
    n = _layout_state["devices"]
    if n is None:
        return None
    mode = os.environ.get("KBT_SPARSE_SHARD_MODE", "").strip().lower()
    token = f"{n}dev:{mode or 'auto'}"
    rack = _layout_state.get("rack")
    # Rack suffix only when the dispatch pinned a rack map — tokens
    # from pre-topology processes (saved warm states) keep comparing
    # equal to themselves.
    return f"{token}:{rack}" if rack else token


def packed_sparse_placement(n_tasks: int) -> Tuple[Optional[NamedSharding], str]:
    """Device placement + layout token for the packed snapshot
    (consumed by tensorize → device_cache.pack): when the sharded
    sparse path will run, resident buffers are uploaded REPLICATED on
    the mesh so the jitted shard_map step never re-lays them out per
    cycle; otherwise None (default single-device placement). The token
    keys the device cache's residency — a layout flip forces a full
    labeled re-upload."""
    mesh = default_mesh()
    size = mesh.size if mesh is not None else 1
    mode = sparse_shard_mode(n_tasks, mesh) if n_tasks else "single"
    token = f"{size}dev:{mode}"
    rack = rack_digest(mesh)
    if rack:
        # Rack-map changes must re-key device residency: a moved
        # node→rack split invalidates resident selection keys and the
        # packed buffers' layout assumptions together.
        token = f"{token}:{rack}"
    if mesh is None or mode == "single":
        return None, token
    return NamedSharding(mesh, P()), token


# Weakrefs to jitted GSPMD steps for the retrace census (see
# spmd._jitted_steps — weak so eviction still frees the executable).
_jitted_steps: list = []


@functools.lru_cache(maxsize=32)
def _sharded_step(mesh: Mesh, shardings, staged, max_rounds, tail_bucket):
    if staged is None:
        fn = solve_auto
    elif staged:
        fn = functools.partial(solve_staged, tail_bucket=tail_bucket)
    else:
        fn = solve
    import weakref

    step = jax.jit(
        lambda x: fn(x, max_rounds=max_rounds),
        in_shardings=(shardings,),
    )
    _jitted_steps.append(weakref.ref(step))
    return step


def _staged_for_shape(inputs, staged):
    """Resolve the ``staged=None`` shape dispatch (solve_auto's rule)
    statically so both sharded implementations pick the same solver."""
    if staged is not None:
        return staged
    from .kernels import _STAGED_MIN_NODES, _STAGED_MIN_TASKS

    if isinstance(inputs, PackedInputs):
        T, N = inputs.task_f32.shape[1], inputs.node_f32.shape[1]
    else:
        T, N = inputs.task_req.shape[0], inputs.node_idle.shape[0]
    return N >= _STAGED_MIN_NODES and T >= _STAGED_MIN_TASKS


def _slab_classes(inputs) -> int:
    """Candidate-class count of an inputs bundle (0 = dense)."""
    cand = getattr(inputs, "cand_idx", None)
    return int(cand.shape[0]) if cand is not None else 0


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
    # for the warm plan's layout token (jax is live here by
    # definition).
    if _layout_state["devices"] is None:
        _layout_state["devices"] = jax.device_count()
        _layout_state["rack"] = rack_digest()


def _sparse_sharded_step(inputs, mesh: Mesh, mode: str, max_rounds,
                         tail_bucket):
    """(step, device_inputs) for the task-sharded sparse solve: pad
    the task axis (and node axis for two-level) to the mesh multiple,
    device_put replicated, hand back the cached jitted step."""
    from ..obs.tracer import span
    from .spmd import (
        _spmd_sparse_step,
        note_commit_stats,
        sparse_spmd_shardings_for,
    )

    note_commit_stats(inputs)
    with span("shard_put", shard_mode=mode, shards=mesh.size):
        if not isinstance(inputs, PackedInputs):
            inputs = pad_tasks(inputs, mesh.size)
            if mode == "two-level":
                inputs = pad_nodes(inputs, mesh.size)
        elif _task_count(inputs) % mesh.size or (
            mode == "two-level" and _node_count(inputs) % mesh.size
        ):
            # A silent mis-split would simply never solve the remainder
            # rows; refuse loudly (solve_sharded routes ragged packed
            # bundles to the single-device jit before ever getting here).
            raise ValueError(
                f"sparse sharded solve needs task{'/node' if mode == 'two-level' else ''} "
                f"axes divisible by the mesh size {mesh.size}"
            )
        inputs = jax.device_put(
            inputs, sparse_spmd_shardings_for(inputs, mesh)
        )
        step = _spmd_sparse_step(
            mesh, max_rounds, tail_bucket, mode == "two-level"
        )
    return step, inputs


def sharded_step(
    inputs,
    mesh: Mesh,
    max_rounds: int = 256,
    staged=None,
    tail_bucket: int = 3072,
    impl: str = "spmd",
):
    """Return ``(step_fn, device_inputs)``: inputs padded and device_put
    onto the mesh ONCE, plus the cached jitted step to run on them. Use
    this when solving the same snapshot repeatedly (benchmarks, re-solve
    loops) so the host→device transfer is not re-paid per call.

    ``impl='spmd'`` (default) is the hierarchical shard_map solver
    (solver/spmd.py): node columns sharded, node/queue tables
    replicated, per-commit communication limited to a two-[T]-vector
    all_gather. ``impl='gspmd'`` keeps the legacy auto-partitioned
    single-device program (collective-dominated at scale; retained for
    A/B and as the fallback surface). Candidate-slab inputs route to
    the task-sharded SPARSE step when the shape/mesh policy engages it
    (``impl='sparse'`` forces flat, ``'sparse-two-level'`` the
    hierarchical mode)."""
    sparse_mode = None
    if impl == "sparse":
        sparse_mode = "flat"          # forced: ALWAYS the bit-parity mode
    elif impl == "sparse-two-level":
        sparse_mode = "two-level"
    elif impl == "spmd" and staged is None and _slab_classes(inputs) > 0:
        mode = sparse_shard_mode(_task_count(inputs), mesh)
        ragged = isinstance(inputs, PackedInputs) and (
            _task_count(inputs) % mesh.size
            or (mode == "two-level" and _node_count(inputs) % mesh.size)
        )
        if mode != "single" and not ragged:
            # Ragged packed axes keep the pre-existing dense-sharded
            # behavior (same graceful shape handling as solve_sharded).
            sparse_mode = mode
    if sparse_mode is not None:
        return _sparse_sharded_step(
            inputs, mesh, sparse_mode, max_rounds, tail_bucket
        )
    inputs = pad_nodes(inputs, mesh.size)
    if impl == "spmd":
        from .spmd import _spmd_step, spmd_shardings_for

        shardings = spmd_shardings_for(inputs, mesh)
        inputs = jax.device_put(inputs, shardings)
        step = _spmd_step(
            mesh, _staged_for_shape(inputs, staged), max_rounds,
            tail_bucket,
        )
        return step, inputs
    shardings = shardings_for(inputs, mesh)
    inputs = jax.device_put(inputs, shardings)
    step = _sharded_step(mesh, shardings, staged, max_rounds, tail_bucket)
    return step, inputs


def solve_sharded(
    inputs,
    mesh: Mesh = None,
    max_rounds: int = 256,
    staged=None,
    tail_bucket: int = 3072,
    impl: str = "spmd",
):
    """Run the batched solve with the node axis sharded over ``mesh``.

    ``staged``: None dispatches by shape (like ``solve_auto``), True
    forces the staged solver, False the full-width one. Falls back to the
    single-device jitted path when no mesh is available. Same semantics
    and results as the single-device solve — sharding changes layout, not
    the program. ``impl`` selects the hierarchical shard_map solver
    (default) or the legacy GSPMD auto-partitioning (see
    :func:`sharded_step`).

    Candidate-sparsified inputs (topk slabs present) dispatch through
    :func:`sparse_shard_mode`: at parity-suite scale the single-device
    sparse jit wins outright (the slab rounds do O(T·K) work with no
    [T, N] structures — one device beats N/s-sharded dense whenever
    K·s < N), so ``single`` stays the small-shape default; past the
    policy floor the task-sharded shard_map sparse solve (bit-equal
    ``flat``, or the Tesserae-style ``two-level``) takes over.
    ``KBT_SPARSE_SHARD_MODE`` forces a mode. The dense SPMD solvers
    remain the dense scale path.
    """
    if mesh is None:
        mesh = default_mesh()
    noted = False
    if mesh is not None and staged is None:
        # Shape probe only — no unpack() (its eager per-field slices
        # cost real milliseconds outside a jit).
        if _slab_classes(inputs) > 0:
            T = _task_count(inputs)
            mode = sparse_shard_mode(T, mesh)
            reason = None
            if mode != "single" and isinstance(inputs, PackedInputs):
                # A packed bundle cannot be re-padded without defeating
                # device residency; production buckets divide every
                # pow2 mesh, so ragged axes are a test/tool corner —
                # fall back to the single-device jit, labeled.
                if T % mesh.size or (
                    mode == "two-level"
                    and _node_count(inputs) % mesh.size
                ):
                    mode, reason = "single", "ragged-axes"
            _note_dispatch(mode, mesh.size, reason)
            noted = True
            if mode != "single":
                step, dev_inputs = _sparse_sharded_step(
                    inputs, mesh, mode, max_rounds, tail_bucket
                )
                result = step(dev_inputs)
                if int(result.assigned.shape[0]) != T:
                    result = result._replace(
                        assigned=result.assigned[:T]
                    )
                return result
            mesh = None
    if mesh is None:
        if not noted:
            _note_dispatch("single", 1)
        # Single device: reuse the module-level cached jits.
        from .kernels import solve_full_jit, solve_jit, solve_staged_jit

        if staged is None:
            return solve_jit(inputs, max_rounds=max_rounds)
        if staged:
            return solve_staged_jit(
                inputs, max_rounds=max_rounds, tail_bucket=tail_bucket,
            )
        return solve_full_jit(inputs, max_rounds=max_rounds)

    _note_dispatch(f"dense-{impl}", mesh.size)
    step, inputs = sharded_step(
        inputs, mesh, max_rounds=max_rounds, staged=staged,
        tail_bucket=tail_bucket, impl=impl,
    )
    return step(inputs)
