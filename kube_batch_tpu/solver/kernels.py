"""Batched assignment solver: the TPU-native allocate kernel (pure JAX).

This replaces the reference's per-task greedy hot loop
(actions/allocate/allocate.go:43-191 — per task: PredicateNodes →
PrioritizeNodes → SelectBestNode → allocate) with a *round-based batched
greedy with conflict resolution*, expressed entirely in jittable JAX:

  round:
    1. feasibility: all still-pending tasks are masked against the CURRENT
       idle vectors at once — one broadcast compare-reduce over [T, N, R]
       (the vectorized form of the 16-goroutine PredicateNodes fan-out,
       util/scheduler_helper.go:63-87).
    2. scoring: LeastRequested + BalancedResourceAllocation recomputed
       against current idle (nodeorder.go:144-168 semantics), plus a static
       score matrix (node affinity etc.) built host-side.
    3. bidding: every task argmaxes its masked score row — all tasks pick
       their best node simultaneously.
    4. conflict resolution: tasks are sorted by (node, priority-rank) with a
       single lexicographic `lax.sort`; a segmented prefix-sum of requests
       per node accepts bidders in priority order while they still fit.
       The top-priority bidder on each node always fits (it passed step 1),
       so every round makes progress and the loop terminates.
    5. accepted requests are scattered out of node idle / into queue
       allocated via `segment_sum`, and the next round re-bids the rest.

  The loop runs under `lax.while_loop` until no task is accepted. Rounds
  needed ≈ max tasks placed on any single node, NOT total tasks — for a
  balanced 50k-task × 5k-node cluster that is ~10-20 rounds of fully
  parallel [T, N] work instead of 50k sequential Go iterations.

Gang semantics need no in-kernel handling: like the reference, partial gangs
keep their (session-level) allocations and simply do not dispatch until
JobReady (framework/session.go:281-289); the action layer applies the
kernel's assignment through the stock ``ssn.allocate`` path which performs
gang gating, so all-or-nothing binding is preserved exactly.

Queue fair share: proportion's OverusedFn (proportion.go:198, ``deserved
LessEqual allocated``) is evaluated in-kernel every round from the running
per-queue allocated vectors, so a queue stops receiving tasks the moment it
exceeds its deserved share — same cadence as the greedy loop's per-iteration
`ssn.Overused` check (allocate.go:94-95).

Numerics: resource dimension 0 is milliCPU, dimension 1 is memory in MiB
(scaled so f32 prefix sums stay well inside epsilon resolution), remaining
dimensions are milli-scalars. Comparisons use the reference's epsilon
semantics (resource_info.go:253-277): ``a <= b`` ⇔ ``a - b < eps`` per
dimension, with eps = (10 mCPU, 10 MiB, 10 milli-units...).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

# Resource-dimension layout contract (see snapshot.ResourceLayout).
CPU_DIM = 0
MEM_DIM = 1

MAX_PRIORITY = 10.0


class SolverInputs(NamedTuple):
    """Dense snapshot of one scheduling session, ready for the kernel.

    Shapes: T pending tasks, N nodes, R resource dims, Q queues, G
    feasibility groups, P private-row tasks, S static-score rows. T and N
    may include padding; padded tasks have ``task_valid`` False and padded
    nodes have ``node_feas`` False.

    The [T, N] feasibility mask and static score matrix are NOT shipped
    from the host — they are factorized (solver/masks.py) into a node
    column mask, per-group rows (pod templates sharing
    tolerations/selectors), and sparse per-task rows, and materialized
    on-device by :func:`build_feasibility` / :func:`build_static_score`.
    """

    task_req: jnp.ndarray        # f32[T, R] resreq (subtracted on allocate)
    task_fit: jnp.ndarray        # f32[T, R] init_resreq (used for fit checks)
    task_rank: jnp.ndarray       # i32[T] global priority rank, smaller first
    task_job: jnp.ndarray        # i32[T] dense job index (< T)
    task_queue: jnp.ndarray      # i32[T] queue index
    task_valid: jnp.ndarray      # bool[T] False for padding rows
    task_group: jnp.ndarray      # i32[T] feasibility group per task
    node_feas: jnp.ndarray       # bool[N] node-level predicate column
    group_feas: jnp.ndarray      # bool[G, N] per-group node masks
    pair_idx: jnp.ndarray        # i32[P] tasks with private rows
    pair_feas: jnp.ndarray       # bool[P, N]
    score_idx: jnp.ndarray       # i32[S] tasks with static score rows
    score_rows: jnp.ndarray      # f32[S, N]
    node_idle: jnp.ndarray       # f32[N, R]
    node_releasing: jnp.ndarray  # f32[N, R] resources being released
    node_cap: jnp.ndarray        # f32[N, R] allocatable
    node_task_count: jnp.ndarray # i32[N] tasks currently on node
    node_max_tasks: jnp.ndarray  # i32[N] pod-count capacity, 0 = unlimited
    queue_deserved: jnp.ndarray  # f32[Q, R] +inf where proportion is off
    queue_allocated: jnp.ndarray # f32[Q, R]
    eps: jnp.ndarray             # f32[R] per-dimension epsilon
    lr_weight: jnp.ndarray       # f32[] LeastRequested weight
    br_weight: jnp.ndarray       # f32[] BalancedResourceAllocation weight
    # Top-K candidate sparsification (solver/topk.py). None/empty = dense.
    # Tasks sharing (feasibility group, req, fit, private rows) share one
    # candidate CLASS; cand_idx rows hold each class's candidate node ids
    # ascending (>= N entries are padding). cand_info rows: 0 = count of
    # feasible-and-fitting-at-snapshot nodes (refill gauge vs K), 1 = any
    # predicate-feasible node exists, 2 = class fits some Releasing row.
    task_cand: jnp.ndarray = None    # i32[T] candidate class per task
    cand_idx: jnp.ndarray = None     # i32[C, K] candidate node ids
    cand_static: jnp.ndarray = None  # f32[C, K] static score slab
    cand_info: jnp.ndarray = None    # i32[3, C]


class PackedInputs(NamedTuple):
    """Transfer-optimized form of :class:`SolverInputs`.

    Each host→device copy is a round trip and
    each *eager* device op compiles its own tiny XLA program, so the
    snapshot ships a handful of stacked buffers and ``solve`` carves the
    fields out INSIDE the jitted computation, where slicing is free.
    """

    task_f32: jnp.ndarray   # [2, T, R] req, fit
    task_i32: jnp.ndarray   # [6, T] rank, queue, job, group, valid, cand
    node_f32: jnp.ndarray   # [3, N, R] idle, releasing, cap
    node_i32: jnp.ndarray   # [3, N] task_count, max_tasks, feas
    group_feas: jnp.ndarray # bool[G, N]
    pair_idx: jnp.ndarray   # i32[P]
    pair_feas: jnp.ndarray  # bool[P, N]
    score_idx: jnp.ndarray  # i32[S]
    score_rows: jnp.ndarray # f32[S, N]
    queue_f32: jnp.ndarray  # [2, Q, R] deserved, allocated
    misc: jnp.ndarray       # f32[R + 2] eps, lr_weight, br_weight
    # Candidate slabs (see SolverInputs). [0, K]-shaped when dense; None
    # only on legacy hand-built bundles.
    cand_idx: jnp.ndarray = None     # i32[C, K]
    cand_static: jnp.ndarray = None  # f32[C, K]
    cand_info: jnp.ndarray = None    # i32[3, C]

    def unpack(self) -> "SolverInputs":
        R = self.task_f32.shape[2]
        # Row 5 (candidate class) is absent on legacy 5-row bundles.
        task_cand = (
            self.task_i32[5] if self.task_i32.shape[0] > 5 else None
        )
        return SolverInputs(
            task_req=self.task_f32[0],
            task_fit=self.task_f32[1],
            task_rank=self.task_i32[0],
            task_queue=self.task_i32[1],
            task_job=self.task_i32[2],
            task_group=self.task_i32[3],
            task_valid=self.task_i32[4].astype(bool),
            task_cand=task_cand,
            cand_idx=self.cand_idx,
            cand_static=self.cand_static,
            cand_info=self.cand_info,
            node_feas=self.node_i32[2].astype(bool),
            group_feas=self.group_feas,
            pair_idx=self.pair_idx,
            pair_feas=self.pair_feas,
            score_idx=self.score_idx,
            score_rows=self.score_rows,
            node_idle=self.node_f32[0],
            node_releasing=self.node_f32[1],
            node_cap=self.node_f32[2],
            node_task_count=self.node_i32[0],
            node_max_tasks=self.node_i32[1],
            queue_deserved=self.queue_f32[0],
            queue_allocated=self.queue_f32[1],
            eps=self.misc[:R],
            lr_weight=self.misc[R],
            br_weight=self.misc[R + 1],
        )


def make_inputs(
    *,
    feas: jnp.ndarray = None,
    static_score: jnp.ndarray = None,
    **kw,
) -> SolverInputs:
    """Convenience constructor for tests/tools that have dense [T, N]
    mask/score matrices: folds them into the factorized fields."""
    T = kw["task_req"].shape[0]
    N = kw["node_idle"].shape[0]
    kw.setdefault("task_valid", jnp.ones((T,), bool))
    kw.setdefault("node_feas", jnp.ones((N,), bool))
    if feas is not None:
        kw.setdefault("task_group", jnp.arange(T, dtype=jnp.int32))
        kw.setdefault("group_feas", jnp.asarray(feas, bool))
    else:
        kw.setdefault("task_group", jnp.zeros((T,), jnp.int32))
        kw.setdefault("group_feas", jnp.ones((1, N), bool))
    kw.setdefault("pair_idx", jnp.zeros((0,), jnp.int32))
    kw.setdefault("pair_feas", jnp.zeros((0, N), bool))
    if static_score is not None and bool((static_score != 0).any()):
        kw.setdefault("score_idx", jnp.arange(T, dtype=jnp.int32))
        kw.setdefault("score_rows", jnp.asarray(static_score, jnp.float32))
    else:
        kw.setdefault("score_idx", jnp.zeros((0,), jnp.int32))
        kw.setdefault("score_rows", jnp.zeros((0, N), jnp.float32))
    return SolverInputs(**kw)


def build_feasibility(inputs: SolverInputs) -> jnp.ndarray:
    """Materialize the [T, N] static predicate mask on-device."""
    T = inputs.task_req.shape[0]
    N = inputs.node_idle.shape[0]
    feas = (
        inputs.group_feas[inputs.task_group]
        & inputs.node_feas[None, :]
        & inputs.task_valid[:, None]
    )
    P = inputs.pair_idx.shape[0]
    if P:
        # Private rows AND into (not replace) the group/column mask, like
        # CombinedMask.row host-side. Extra row T absorbs padded scatter
        # indices; sliced off after.
        ext = jnp.ones((T + 1, N), bool).at[inputs.pair_idx].set(
            inputs.pair_feas
        )
        feas = feas & ext[:T]
    return feas


def build_static_score(inputs: SolverInputs) -> jnp.ndarray:
    """Materialize the [T, N] static score matrix on-device (0.0 if no
    plugin contributed rows — broadcastable scalar)."""
    T = inputs.task_req.shape[0]
    N = inputs.node_idle.shape[0]
    S = inputs.score_idx.shape[0]
    if not S:
        return jnp.zeros((), jnp.float32)
    ext = jnp.zeros((T + 1, N), jnp.float32).at[inputs.score_idx].add(
        inputs.score_rows
    )
    return ext[:T]


class SolverResult(NamedTuple):
    assigned: jnp.ndarray         # i32[T] node index or -1
    node_idle: jnp.ndarray        # f32[N, R] idle after assignment
    queue_allocated: jnp.ndarray  # f32[Q, R]
    rounds: jnp.ndarray           # i32[] rounds executed
    stages: jnp.ndarray = None    # i32[] tail compaction stages (staged only)
    refills: jnp.ndarray = None   # i32[] tasks routed to candidate refill
                                  # (sparse only; stages counts the refill
                                  # rounds those tasks then ran)
    reconcile_rounds: jnp.ndarray = None  # i32[] cross-shard reconciliation
                                  # rounds (sharded sparse only: global
                                  # commit-collective rounds, spmd.py)


def less_equal(a: jnp.ndarray, b: jnp.ndarray, eps: jnp.ndarray) -> jnp.ndarray:
    """Epsilon-tolerant per-dimension <=, reduced over the last axis
    (resource_info.go:253-277: true iff every dim has a < b or |b-a| < eps,
    which is exactly ``a - b < eps`` elementwise)."""
    return jnp.all(a - b < eps, axis=-1)


def segmented_cumsum(x: jnp.ndarray, is_start: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along axis 0 that resets where is_start is True.

    Implemented with `lax.associative_scan` so per-segment partial sums never
    mix magnitudes across segments (keeps f32 prefix sums accurate against
    the epsilon thresholds).
    """

    def combine(a, b):
        a_flag, a_val = a
        b_flag, b_val = b
        if b_val.ndim > b_flag.ndim:
            keep = b_flag[..., None]
        else:
            keep = b_flag
        return (a_flag | b_flag, jnp.where(keep, b_val, a_val + b_val))

    _, vals = lax.associative_scan(combine, (is_start, x))
    return vals


def segmented_cummin(x: jnp.ndarray, is_start: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix MIN along axis 0 that resets where is_start is
    True (used for within-segment first-failure ranks)."""

    def combine(a, b):
        a_flag, a_val = a
        b_flag, b_val = b
        return (
            a_flag | b_flag,
            jnp.where(b_flag, b_val, jnp.minimum(a_val, b_val)),
        )

    _, vals = lax.associative_scan(combine, (is_start, x))
    return vals


# Bid keys: quantized score in the high bits, a decorrelated per-(task,
# node) hash in the low bits. Greedy picks RANDOMLY among equal-scored
# nodes (scheduler_helper.go:188-208); batched argmax needs an equivalent
# tie-breaker or every equal-scored task herds onto one node and rounds
# serialize. Additive float jitter CANNOT do this at scale: at score ~20
# the f32 ulp is 2.4e-6, so sub-gap jitter collapses to a handful of
# representable values and thousands of ties survive (observed: 50k tasks
# bidding on just ~100 of 5k nodes). Integer keys sidestep float
# resolution entirely. SCORE_QUANTUM=0.02 is half the smallest real
# scorer step for standard weights (one 250m-CPU task on a 32-CPU node
# moves LeastRequested by ~0.04), so a genuine preference is never
# overridden; scores within one quantum tie-break uniformly via the hash
# (the batched analog of the reference's random pick).
SCORE_QUANTUM = 0.02
_KEY_HASH_BITS = 10
_KEY_BIAS = 1 << 19  # centers the quantized range so negative scores rank

# Conflict-resolution commits per score pass (see _solve_round): each
# extra commit costs one [T, N] argmax + two O(T log T) sorts against
# the round's full mask/score/key build, and lets prefix-race losers
# cascade to their next-best node without waiting for the next round.
# Measured at 50k x 5k: 6 commits converge in 3 rounds vs 6 rounds at 3
# commits, identical placement — halving the expensive full-width
# passes.
COMMITS_PER_ROUND = 6


def _bid_hash(t_idx: jnp.ndarray, n_idx: jnp.ndarray) -> jnp.ndarray:
    """Decorrelated per-(task, node) hash in [0, 2^_KEY_HASH_BITS)."""
    x = t_idx.astype(jnp.uint32) * jnp.uint32(2654435761) ^ (
        n_idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    )
    x = x ^ (x >> 13)
    x = x * jnp.uint32(2246822519)
    return ((x >> 8) & jnp.uint32((1 << _KEY_HASH_BITS) - 1)).astype(
        jnp.int32
    )


def bid_keys(
    score: jnp.ndarray, t_idx: jnp.ndarray, n_idx: jnp.ndarray
) -> jnp.ndarray:
    """int32 argmax keys from float scores + hashed tie bits.

    ``t_idx``/``n_idx`` are broadcast-compatible index arrays matching
    ``score``'s layout (full [T, 1]x[1, N] or gathered [T, K])."""
    q = jnp.clip(
        jnp.round(score / SCORE_QUANTUM) + _KEY_BIAS, 0, (1 << 20) - 1
    ).astype(jnp.int32)
    return (q << _KEY_HASH_BITS) | _bid_hash(t_idx, n_idx)


def _dyn_score_core(
    req_cm: jnp.ndarray,
    idle_cm: jnp.ndarray,
    cap_cm: jnp.ndarray,
    lr_weight: jnp.ndarray,
    br_weight: jnp.ndarray,
) -> jnp.ndarray:
    """LeastRequested + Balanced on broadcast-compatible [..., 2] views."""
    safe_cap = jnp.where(cap_cm > 0, cap_cm, 1.0)
    # remaining[..., d] = idle - req  (== cap - (used + req))
    remaining = idle_cm - req_cm
    lr = jnp.where(
        cap_cm > 0,
        jnp.maximum(remaining, 0.0) * MAX_PRIORITY / safe_cap,
        0.0,
    )
    lr_score = jnp.mean(lr, axis=-1)

    frac = jnp.where(cap_cm > 0, 1.0 - remaining / safe_cap, 1.0)
    diff = jnp.abs(frac[..., 0] - frac[..., 1])
    br_score = jnp.where(
        jnp.any(frac >= 1.0, axis=-1),
        0.0,
        MAX_PRIORITY - diff * MAX_PRIORITY,
    )
    return lr_weight * lr_score + br_weight * br_score


def dynamic_scores(
    task_req: jnp.ndarray,
    node_idle: jnp.ndarray,
    node_cap: jnp.ndarray,
    lr_weight: jnp.ndarray,
    br_weight: jnp.ndarray,
) -> jnp.ndarray:
    """[T, N] LeastRequested + BalancedResourceAllocation against CURRENT
    idle. Mirrors plugins/nodeorder.py scalar scorers (k8s formulas, 0..10
    each, both computed from task.resreq like the scalar path):
    - least_requested: mean over {cpu, mem} of (cap - used - req) * 10 / cap
    - balanced: 10 - |cpu_frac - mem_frac| * 10, 0 if either frac >= 1
    where used = cap - idle.
    """
    return _dyn_score_core(
        task_req[:, None, (CPU_DIM, MEM_DIM)],            # [T, 1, 2]
        node_idle[None, :, (CPU_DIM, MEM_DIM)],           # [1, N, 2]
        node_cap[None, :, (CPU_DIM, MEM_DIM)],
        lr_weight,
        br_weight,
    )


def _resolve_bids(
    bid, idle, ntask, qalloc,
    *, task_req, task_fit, task_rank, task_queue,
    node_max_tasks, queue_deserved, eps,
):
    """Conflict resolution only: given each task's bid (node index, N =
    no bid), accept bidders per node in priority order while they fit
    (segmented prefix sums), then enforce per-queue budgets. Returns the
    accept mask in TASK order ([T] bool) so any consumer — the local
    solve or a remote shard receiving a broadcast mask — can apply it
    through :func:`_apply_accepts` with bit-identical arithmetic.
    """
    T, R = task_req.shape
    N = idle.shape[0]
    Q = queue_deserved.shape[0]
    arange_t = jnp.arange(T, dtype=jnp.int32)

    # Conflict resolution: lexicographic sort by (node, priority rank).
    sbid, _, order = lax.sort(
        (bid, task_rank, arange_t), num_keys=2
    )
    sreq = task_req[order]                                    # [T, R]
    sfit = task_fit[order]
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sbid[1:] != sbid[:-1]]
    )
    # Exclusive within-node prefix of requests ahead of each bidder.
    within_excl = segmented_cumsum(sreq, is_start) - sreq     # [T, R]
    seg_pos = segmented_cumsum(
        jnp.ones((T,), jnp.int32), is_start
    )                                                         # 1-based
    idle_pad = jnp.concatenate([idle, jnp.zeros((1, R))], axis=0)
    ntask_pad = jnp.concatenate(
        [ntask, jnp.zeros((1,), jnp.int32)], axis=0
    )
    max_pad = jnp.concatenate(
        [node_max_tasks, jnp.zeros((1,), jnp.int32)], axis=0
    )
    fit_ok = less_equal(within_excl + sfit, idle_pad[sbid], eps)
    count_ok = (max_pad[sbid] == 0) | (
        ntask_pad[sbid] + seg_pos <= max_pad[sbid]
    )
    accept = (sbid < N) & fit_ok & count_ok                   # [T]

    # Queue-budget pass: greedy checks ssn.Overused before every task
    # (allocate.go:94-95), so within one round a queue must stop the
    # moment its running allocation satisfies "deserved <= allocated".
    # Re-sort the node-phase accepts by (queue, rank) and keep each
    # accepted task only while its queue is not yet overused. Dropping
    # a task only frees node capacity, so the node-phase prefix check
    # stays valid.
    srank = task_rank[order]
    squeue = task_queue[order]
    q_sort_ids = jnp.where(accept, squeue, Q)                 # reject → Q
    sq, _, qorder = lax.sort(
        (q_sort_ids, srank, arange_t), num_keys=2
    )
    q_req = jnp.where(accept[qorder][:, None], sreq[qorder], 0.0)
    q_start = jnp.concatenate(
        [jnp.ones((1,), bool), sq[1:] != sq[:-1]]
    )
    q_prefix_excl = segmented_cumsum(q_req, q_start) - q_req
    deserved_pad = jnp.concatenate(
        [queue_deserved, jnp.full((1, R), jnp.inf)], axis=0
    )
    qalloc_pad = jnp.concatenate([qalloc, jnp.zeros((1, R))], axis=0)
    budget_ok = ~less_equal(
        deserved_pad[sq], qalloc_pad[sq] + q_prefix_excl, eps
    )
    accept = jnp.zeros_like(accept).at[qorder].set(
        accept[qorder] & budget_ok
    )
    # Scatter the sorted-space accepts back to task order: the state
    # update below (and every shard of the delta-packed commit) sums
    # floats in TASK order, so one canonical ordering keeps all paths
    # bit-identical.
    return jnp.zeros((T,), bool).at[order].set(accept)


def _apply_accepts(
    accept, bid, assigned, idle, ntask, qalloc,
    *, task_req, task_queue,
):
    """Apply a task-order accept mask to the solver state. All float
    reductions run in task order via segment_sum, so a single device and
    every shard replaying the same (accept, bid) pair land on
    bit-identical idle/qalloc — the invariant the delta-packed commit
    collective (spmd.py) relies on.

    Returns (assigned, idle, ntask, qalloc).
    """
    N = idle.shape[0]
    Q = qalloc.shape[0]
    sbid = jnp.where(accept, bid, N)
    delta = jnp.where(accept[:, None], task_req, 0.0)
    idle = idle - jax.ops.segment_sum(delta, sbid, num_segments=N + 1)[:N]
    ntask = ntask + jax.ops.segment_sum(
        accept.astype(jnp.int32), sbid, num_segments=N + 1
    )[:N]
    q_ids = jnp.where(accept, task_queue, Q)
    qalloc = qalloc + jax.ops.segment_sum(
        delta, q_ids, num_segments=Q + 1
    )[:Q]
    assigned = jnp.where(accept, sbid, assigned)
    return assigned, idle, ntask, qalloc


def _commit_bids(
    bid, assigned, idle, ntask, qalloc,
    *, task_req, task_fit, task_rank, task_queue,
    node_max_tasks, queue_deserved, eps,
):
    """One conflict-resolution + commit step shared by the solver stages
    (:func:`_resolve_bids` then :func:`_apply_accepts`). Task arrays may
    be a compacted subset of the session (the staged tail); ranks are
    global values.

    Returns (assigned, idle, ntask, qalloc, any_accept).
    """
    accept = _resolve_bids(
        bid, idle, ntask, qalloc,
        task_req=task_req, task_fit=task_fit,
        task_rank=task_rank, task_queue=task_queue,
        node_max_tasks=node_max_tasks,
        queue_deserved=queue_deserved, eps=eps,
    )
    assigned, idle, ntask, qalloc = _apply_accepts(
        accept, bid, assigned, idle, ntask, qalloc,
        task_req=task_req, task_queue=task_queue,
    )
    return assigned, idle, ntask, qalloc, jnp.any(accept)


def _solve_round(
    assigned, idle, ntask, qalloc, failed,
    *, task_req, task_fit, task_rank, task_queue, task_sel, task_ids,
    feas, static_score, fits_releasing, blocked_of,
    node_cap, node_max_tasks, queue_deserved,
    lr_weight, br_weight, eps,
):
    """ONE solver round, shared by solve / staged head / staged tail
    (same semantics on full or compacted task arrays):

    1. gate tasks (pending, selectable, queue not overused, job not
       broken — Overused per allocate.go:94-95);
    2. mask feasibility against CURRENT idle + pod-count capacity;
    3. mark permanent failures — a task with no feasible node and no
       Releasing escape hatch breaks its job (allocate.go:144-181), and
       job-mates are re-masked so a same-round accept cannot leapfrog
       the break;
    4. score (LeastRequested/Balanced on current idle + static rows,
       scorers use resreq like nodeorder.py) → integer bid keys → argmax;
    5. conflict-resolve and commit (:func:`_commit_bids`).

    ``blocked_of`` maps the failed vector to the job-blocked vector
    (global segment_min, or the staged tail's local segmented scan).
    Returns (assigned, idle, ntask, qalloc, failed, any_accept).
    """
    N = idle.shape[0]
    pending = assigned < 0
    q_over = less_equal(queue_deserved, qalloc, eps)
    task_ok = (
        pending & task_sel & ~q_over[task_queue] & ~blocked_of(failed)
    )
    cap_ok = (node_max_tasks == 0) | (ntask < node_max_tasks)
    fits = less_equal(task_fit[:, None, :], idle[None, :, :], eps)
    mask = fits & feas & cap_ok[None, :] & task_ok[:, None]
    failed = failed | (
        task_ok & ~jnp.any(mask, axis=1) & ~fits_releasing
    )
    mask = mask & ~blocked_of(failed)[:, None]
    score = (
        dynamic_scores(task_req, idle, node_cap, lr_weight, br_weight)
        + static_score
    )
    key = bid_keys(
        score, task_ids[:, None], jnp.arange(N, dtype=jnp.int32)[None, :]
    )
    key = jnp.where(mask, key, -1)

    # Multi-commit: the [T, N] score/mask pass above is the round's
    # expensive part (O(T*N)); conflict resolution is only O(T log T)
    # sorts. Reusing one score matrix for several commits lets a bidder
    # that lost a node's prefix race cascade to its next-best column in
    # the SAME round — fits, pod counts, and queue budgets are re-checked
    # exactly inside every _commit_bids against the updated idle/qalloc,
    # so staleness only affects choice quality (caught by the fit check),
    # never feasibility. Cuts full-width rounds roughly in proportion.
    #
    # (Measured alternative, r3: capturing per-task top-k candidates once
    # with lax.top_k and advancing a pointer per commit is semantically
    # identical but 2x SLOWER on TPU — top_k lowers poorly at [50k, 5k].
    # The voided-column re-argmax below wins.)
    arange_t = jnp.arange(task_req.shape[0], dtype=jnp.int32)

    def commit_once(_, state):
        assigned, idle, ntask, qalloc, any_acc, key = state
        live = (assigned < 0)
        # One [T, N] argmax over the PERSISTENT key matrix; rows of
        # already-assigned tasks produce garbage bids that the O(T)
        # has_bid gate discards — cheaper than materializing a
        # where(live) copy plus a full-width any() per commit (for live
        # rows the result is identical).
        bid_col = jnp.argmax(key, axis=1).astype(jnp.int32)
        has_bid = live & (key[arange_t, bid_col] >= 0)
        bid = jnp.where(has_bid, bid_col, N)
        assigned, idle, ntask, qalloc, acc = _commit_bids(
            bid, assigned, idle, ntask, qalloc,
            task_req=task_req, task_fit=task_fit,
            task_rank=task_rank, task_queue=task_queue,
            node_max_tasks=node_max_tasks,
            queue_deserved=queue_deserved, eps=eps,
        )
        # Losers stop re-bidding the column they just lost this round
        # (fresh scores next round may still pick it).
        lost = has_bid & (assigned < 0)
        col = jnp.where(has_bid, bid_col, 0)
        key = key.at[arange_t, col].set(
            jnp.where(lost, -1, key[arange_t, col])
        )
        return assigned, idle, ntask, qalloc, any_acc | acc, key

    assigned, idle, ntask, qalloc, any_accept, _ = lax.fori_loop(
        0, COMMITS_PER_ROUND, commit_once,
        (assigned, idle, ntask, qalloc, jnp.asarray(False), key),
    )
    return assigned, idle, ntask, qalloc, failed, any_accept


def solve(inputs: SolverInputs, max_rounds: int = 256) -> SolverResult:
    """Run the round-based batched allocation to a fixed point.

    Jit-safe; wrap with `jax.jit(solve, static_argnames=("max_rounds",))`
    (exported as `solve_jit`). Accepts either :class:`SolverInputs` or the
    transfer-optimized :class:`PackedInputs`.
    """
    if isinstance(inputs, PackedInputs):
        inputs = inputs.unpack()
    T, R = inputs.task_req.shape
    N = inputs.node_idle.shape[0]
    Q = inputs.queue_deserved.shape[0]
    eps = inputs.eps

    # Pad node tables with one dummy row (index N) for tasks with no bid.
    idle0 = inputs.node_idle

    # Materialize the factorized predicate mask / static scores on-device
    # (masks.py): O(T + G·N + P·N) crosses the host↔device boundary, not
    # the 250 MB dense [T, N] mask.
    feas0 = build_feasibility(inputs)
    static_score = build_static_score(inputs)

    # Greedy's resource-fit predicate passes when a task fits Idle OR
    # Releasing (allocate.go:73-87); only a task that fits NEITHER anywhere
    # breaks its job. Releasing never changes during a solve (allocate does
    # not evict), so compute the releasing escape hatch once: tasks with a
    # feasible releasing fit stay pending for the pipeline epilogue instead
    # of failing their job.
    fits_releasing = jnp.any(
        less_equal(
            inputs.task_fit[:, None, :],
            inputs.node_releasing[None, :, :],
            eps,
        )
        & feas0,
        axis=1,
    )                                                             # [T]

    INT_MAX = jnp.iinfo(jnp.int32).max

    def job_blocked(failed):
        """Greedy break semantics (allocate.go:144-148): once a task of a
        job finds no feasible node, every later task of that job is skipped
        for the rest of the cycle. Idle only shrinks during a solve, so a
        no-feasible-node verdict is permanent — gate tasks whose rank is
        above their job's first failure."""
        first_fail = jax.ops.segment_min(
            jnp.where(failed, inputs.task_rank, INT_MAX),
            inputs.task_job,
            num_segments=T,
        )
        return inputs.task_rank > first_fail[inputs.task_job]

    round_kw = dict(
        task_req=inputs.task_req, task_fit=inputs.task_fit,
        task_rank=inputs.task_rank, task_queue=inputs.task_queue,
        # Bid-key tie hashes use the GLOBAL rank, not the row position:
        # identical for full bundles (rank == arange there) and the
        # property that makes warm SUBSET bundles (solver/warm.py) bid
        # exactly like the full problem restricted to their rows.
        task_sel=inputs.task_valid, task_ids=inputs.task_rank,
        feas=feas0, static_score=static_score,
        fits_releasing=fits_releasing, blocked_of=job_blocked,
        node_cap=inputs.node_cap, node_max_tasks=inputs.node_max_tasks,
        queue_deserved=inputs.queue_deserved,
        lr_weight=inputs.lr_weight, br_weight=inputs.br_weight, eps=eps,
    )

    def body(state):
        assigned, idle, ntask, qalloc, failed, _, rnd = state
        assigned, idle, ntask, qalloc, failed, any_accept = _solve_round(
            assigned, idle, ntask, qalloc, failed, **round_kw
        )
        return (
            assigned, idle, ntask, qalloc, failed, any_accept, rnd + 1
        )

    def cond(state):
        _, _, _, _, _, changed, rnd = state
        return changed & (rnd < max_rounds)

    init = (
        jnp.full((T,), -1, jnp.int32),
        idle0,
        inputs.node_task_count,
        inputs.queue_allocated,
        jnp.zeros((T,), bool),
        jnp.array(True),
        jnp.array(0, jnp.int32),
    )
    assigned, idle, _, qalloc, _, _, rounds = lax.while_loop(cond, body, init)
    return SolverResult(assigned, idle, qalloc, rounds)


_INT_MAX = jnp.iinfo(jnp.int32).max


def tail_subset_feas(inputs: SolverInputs, idxs, valid2):
    """Rebuild the factorized predicate-mask rows for a compacted task
    subset. Reads only ``inputs`` fields, so it works identically on
    full node tables and on a shard's local column blocks (the sharded
    tail in solver/spmd.py shares this exact code path — the staged
    solvers' bit-exact-parity contract depends on it)."""
    f2 = (
        inputs.group_feas[inputs.task_group[idxs]]
        & inputs.node_feas[None, :]
        & valid2[:, None]
    )
    P = inputs.pair_idx.shape[0]
    if P:
        pos = jnp.clip(jnp.searchsorted(inputs.pair_idx, idxs), 0, P - 1)
        match = inputs.pair_idx[pos] == idxs
        f2 = f2 & jnp.where(match[:, None], inputs.pair_feas[pos], True)
    return f2


def tail_subset_static(inputs: SolverInputs, idxs):
    """Static score rows for a compacted subset (see tail_subset_feas
    for the shared-with-spmd contract)."""
    S = inputs.score_idx.shape[0]
    if not S:
        return jnp.zeros((), jnp.float32)
    pos = jnp.clip(jnp.searchsorted(inputs.score_idx, idxs), 0, S - 1)
    match = inputs.score_idx[pos] == idxs
    return jnp.where(match[:, None], inputs.score_rows[pos], 0.0)


def tail_local_blocked(inputs: SolverInputs, idxs, B):
    """Subset-local job-break scan for a compacted tail stage.

    Job-break state stays SUBSET-LOCAL during a stage: every eligible
    lower-rank member of a subset task's job is in the subset too
    (compaction is by rank), and tasks outside the subset cannot fail
    mid-stage. Pre-sorts the subset by (job, rank) once; the returned
    ``blocked_from(failed2)`` recomputes blockage with an O(B) segmented
    min-scan instead of an O(T) segment_min. Also returns the subset's
    global ranks (needed by the round body)."""
    arange_b = jnp.arange(B, dtype=jnp.int32)
    job2 = inputs.task_job[idxs]
    rank2 = inputs.task_rank[idxs]
    sjob, srank2, jord = lax.sort((job2, rank2, arange_b), num_keys=2)
    jstart = jnp.concatenate(
        [jnp.ones((1,), bool), sjob[1:] != sjob[:-1]]
    )
    inv_jord = jnp.zeros((B,), jnp.int32).at[jord].set(arange_b)

    def blocked_from(failed2):
        f_rank = jnp.where(failed2[jord], srank2, _INT_MAX)
        prefmin = segmented_cummin(f_rank, jstart)
        return (srank2 > prefmin)[inv_jord]

    return blocked_from, rank2


def _dense_tail(
    inputs: SolverInputs,
    assigned, idle, ntask, qalloc, failed, rounds,
    *,
    fits_releasing, job_blocked, shared_kw,
    max_rounds: int, tail_bucket: int,
):
    """Compacted dense drain stage shared by :func:`solve_staged` (its
    tail) and :func:`solve_sparse` (candidate-refill / dense-fallback
    rounds): repeatedly compact the highest-priority eligible tasks into
    a fixed ``[tail_bucket]`` block and run full-width-over-N rounds on
    it until nothing progresses. Semantics documented at
    :func:`solve_staged`. Returns
    ``(assigned, idle, ntask, qalloc, failed, rounds, stages)``."""
    eps = inputs.eps
    # Clamp to the task axis: the sparse solver drains refills through
    # here at ANY T (solve_staged only enters past T > tail_bucket).
    B = min(tail_bucket, int(inputs.task_req.shape[0]))

    def tail_outer_body(ostate):
        assigned, idle, ntask, qalloc, failed, _, rounds, stages = ostate

        blocked = job_blocked(failed)
        # qalloc only grows during a solve, so an overused queue stays
        # overused — its tasks are permanently gated and must not crowd
        # actionable tasks out of the bucket.
        q_over = less_equal(inputs.queue_deserved, qalloc, eps)
        elig = (
            (assigned < 0)
            & inputs.task_valid
            & ~failed
            & ~blocked
            & ~q_over[inputs.task_queue]
        )
        sel_key = jnp.where(elig, inputs.task_rank, _INT_MAX)
        # Highest-priority (smallest-rank) eligible tasks; stable order.
        _, idxs = lax.top_k(-sel_key, B)
        idxs = idxs.astype(jnp.int32)
        valid2 = sel_key[idxs] != _INT_MAX

        req2 = inputs.task_req[idxs]
        fit2 = inputs.task_fit[idxs]
        queue2 = inputs.task_queue[idxs]
        feas2 = tail_subset_feas(inputs, idxs, valid2)
        static2 = tail_subset_static(inputs, idxs)
        fits_rel2 = fits_releasing[idxs]
        blocked_from, rank2 = tail_local_blocked(inputs, idxs, B)

        tail_kw = dict(
            task_req=req2, task_fit=fit2,
            task_rank=rank2, task_queue=queue2,
            # Global-rank tie hashes (== idxs on full bundles; diverges
            # only on warm subset bundles, where rank is the contract).
            task_sel=valid2, task_ids=rank2,
            feas=feas2, static_score=static2,
            fits_releasing=fits_rel2, blocked_of=blocked_from,
            **shared_kw,
        )

        def tail_body(state):
            (
                sub_assigned, idle, ntask, qalloc, failed2, _, rnd
            ) = state
            (
                sub_assigned, idle, ntask, qalloc, failed2, any_accept
            ) = _solve_round(
                sub_assigned, idle, ntask, qalloc, failed2, **tail_kw
            )
            return (
                sub_assigned, idle, ntask, qalloc, failed2,
                any_accept, rnd + 1,
            )

        def tail_cond(state):
            changed, rnd = state[5], state[6]
            return changed & (rnd < max_rounds)

        tstate = (
            jnp.full((B,), -1, jnp.int32), idle, ntask, qalloc,
            failed[idxs], jnp.array(True), rounds,
        )
        (
            sub_assigned, idle, ntask, qalloc, failed2, _, rounds
        ) = lax.while_loop(tail_cond, tail_body, tstate)

        placed2 = sub_assigned >= 0
        assigned = assigned.at[idxs].set(
            jnp.where(placed2, sub_assigned, assigned[idxs])
        )
        failed = failed.at[idxs].set(failed2)
        return (
            assigned, idle, ntask, qalloc, failed,
            jnp.any(placed2), rounds, stages + 1,
        )

    def tail_outer_cond(ostate):
        progressed, rounds, stages = ostate[5], ostate[6], ostate[7]
        # Continue while the last stage placed something, tasks remain,
        # and budgets allow. A stage that places nothing ends the solve
        # (every remaining task is failed, blocked, over-budget, or
        # waiting on Releasing resources).
        assigned, qalloc, failed = ostate[0], ostate[3], ostate[4]
        q_over = less_equal(inputs.queue_deserved, qalloc, eps)
        remaining = jnp.any(
            (assigned < 0) & inputs.task_valid & ~failed
            & ~job_blocked(failed) & ~q_over[inputs.task_queue]
        )
        return (
            progressed & remaining & (rounds < max_rounds)
            & (stages < 64)
        )

    ostate = (
        assigned, idle, ntask, qalloc, failed,
        jnp.array(True), rounds, jnp.array(0, jnp.int32),
    )
    (
        assigned, idle, ntask, qalloc, failed, _, rounds, stages
    ) = lax.while_loop(tail_outer_cond, tail_outer_body, ostate)
    return assigned, idle, ntask, qalloc, failed, rounds, stages


def solve_staged(
    inputs: SolverInputs,
    max_rounds: int = 256,
    tail_bucket: int = 3072,
) -> SolverResult:
    """Two-stage variant of :func:`solve` for large snapshots.

    The round profile at scale is extremely front-loaded (measured at
    50k x 5k: round 1 places ~76%, round 2 ~13%, then ~20 rounds drain a
    few hundred each — large tasks genuinely fit only the emptiest nodes,
    so the tail is inherent auction dynamics, not tie-herding). Full
    rounds cost O(T·N) compute plus O(T log T) sorts; paying that ~20
    more times for a few-thousand-task tail is the entire gap to the
    latency target. So:

    - HEAD: full-width rounds (identical to :func:`solve`) while more
      than ``tail_bucket`` eligible tasks remain;
    - TAIL: compact the highest-priority pending tasks into a fixed
      [tail_bucket] block (`lax.top_k` on ranks — shapes stay static),
      then run the same round body on [tail_bucket, N] where both the
      mask/score pass and the conflict-resolution sorts are ~T/bucket
      times cheaper. Repeats (rare) if more than ``tail_bucket`` tasks
      remain eligible after a stage stops progressing.

    Semantics match :func:`solve` exactly for any ordering the full
    solver could produce: the tail processes tasks in global priority
    order, job-break (`failed`/blocked) state stays global, and queue
    budgets/idle are shared across stages.
    """
    if isinstance(inputs, PackedInputs):
        inputs = inputs.unpack()
    T, R = inputs.task_req.shape
    N = inputs.node_idle.shape[0]
    Q = inputs.queue_deserved.shape[0]
    if T <= tail_bucket:
        return solve(inputs, max_rounds=max_rounds)
    eps = inputs.eps

    feas0 = build_feasibility(inputs)
    static_score = build_static_score(inputs)

    fits_releasing = jnp.any(
        less_equal(
            inputs.task_fit[:, None, :],
            inputs.node_releasing[None, :, :],
            eps,
        )
        & feas0,
        axis=1,
    )

    INT_MAX = jnp.iinfo(jnp.int32).max

    def job_blocked(failed):
        first_fail = jax.ops.segment_min(
            jnp.where(failed, inputs.task_rank, INT_MAX),
            inputs.task_job,
            num_segments=T,
        )
        return inputs.task_rank > first_fail[inputs.task_job]

    shared_kw = dict(
        node_cap=inputs.node_cap, node_max_tasks=inputs.node_max_tasks,
        queue_deserved=inputs.queue_deserved,
        lr_weight=inputs.lr_weight, br_weight=inputs.br_weight, eps=eps,
    )
    head_kw = dict(
        task_req=inputs.task_req, task_fit=inputs.task_fit,
        task_rank=inputs.task_rank, task_queue=inputs.task_queue,
        # GLOBAL-rank tie hashes, like the tail (== row position on full
        # bundles; the warm subset path depends on the rank form).
        task_sel=inputs.task_valid, task_ids=inputs.task_rank,
        feas=feas0, static_score=static_score,
        fits_releasing=fits_releasing, blocked_of=job_blocked,
        **shared_kw,
    )

    # ---------------- head: full-width rounds --------------------------
    def head_body(state):
        assigned, idle, ntask, qalloc, failed, _, rnd, _ = state
        assigned, idle, ntask, qalloc, failed, any_accept = _solve_round(
            assigned, idle, ntask, qalloc, failed, **head_kw
        )
        # Handoff gauge: tasks the TAIL could still act on. Must mirror
        # the tail's eligibility predicate — counting tasks that are
        # permanently gated (overused queue, broken job) would hold the
        # head at full width forever on a snapshot with a large starved
        # queue.
        q_over = less_equal(inputs.queue_deserved, qalloc, eps)
        still = jnp.sum(
            (
                (assigned < 0)
                & inputs.task_valid
                & ~failed
                & ~q_over[inputs.task_queue]
                & ~job_blocked(failed)
            ).astype(jnp.int32)
        )
        return (
            assigned, idle, ntask, qalloc, failed, any_accept, rnd + 1,
            still,
        )

    def head_cond(state):
        changed, rnd, still = state[5], state[6], state[7]
        return changed & (rnd < max_rounds) & (still > tail_bucket)

    init = (
        jnp.full((T,), -1, jnp.int32),
        inputs.node_idle,
        inputs.node_task_count,
        inputs.queue_allocated,
        jnp.zeros((T,), bool),
        jnp.array(True),
        jnp.array(0, jnp.int32),
        jnp.array(T, jnp.int32),
    )
    (
        assigned, idle, ntask, qalloc, failed, _, rounds, _
    ) = lax.while_loop(head_cond, head_body, init)

    # ---------------- tail: compacted rounds ---------------------------
    (
        assigned, idle, _, qalloc, _, rounds, stages
    ) = _dense_tail(
        inputs, assigned, idle, ntask, qalloc, failed, rounds,
        fits_releasing=fits_releasing, job_blocked=job_blocked,
        shared_kw=shared_kw, max_rounds=max_rounds,
        tail_bucket=tail_bucket,
    )
    return SolverResult(assigned, idle, qalloc, rounds, stages)


def _sparse_round(
    assigned, idle, ntask, qalloc, failed, refill,
    *, task_req, task_fit, task_rank, task_queue, task_sel, task_ids,
    cand_nodes, cand_static, cand_total, fits_releasing, blocked_of,
    node_cap, node_max_tasks, queue_deserved,
    lr_weight, br_weight, eps,
):
    """ONE candidate-sparsified solver round: the dense round's
    gate/mask/fail/score/bid/commit chain (:func:`_solve_round`) run on
    gathered [T, K] candidate slabs instead of [T, N] matrices. Bids
    carry GLOBAL node ids (``cand_nodes``), so conflict resolution and
    node capacity accounting stay dense [N] inside :func:`_commit_bids`
    (segment scatters keyed by node id) — only the mask/score/key pass
    shrinks from O(T·N) to O(T·K).

    Slab exhaustion (no candidate fits CURRENT idle) splits two ways on
    ``cand_total`` (the class's feasible-and-fitting node count at
    snapshot time, solver/topk.py): a slab that held EVERY such node
    reproduces the dense solver's permanent no-fit verdict exactly —
    idle only shrinks during a solve, so a node outside that set can
    never start fitting — while a truncated slab (cand_total > K)
    routes the task to the refill stage (``refill`` flag; drained by
    :func:`_dense_tail`), never to a false job break.

    Returns (assigned, idle, ntask, qalloc, failed, refill, any_accept).
    """
    N = idle.shape[0]
    K = cand_nodes.shape[1]
    T = task_req.shape[0]
    pending = assigned < 0
    q_over = less_equal(queue_deserved, qalloc, eps)
    task_ok = (
        pending & task_sel & ~q_over[task_queue] & ~blocked_of(failed)
        & ~refill
    )
    cap_ok = (node_max_tasks == 0) | (ntask < node_max_tasks)
    valid = cand_nodes < N                               # [T, K]
    safe = jnp.minimum(cand_nodes, N - 1)                # gather-safe ids
    arange_t = jnp.arange(T, dtype=jnp.int32)

    idle_slab = idle[safe]                               # [T, K, R]
    fits = less_equal(task_fit[:, None, :], idle_slab, eps)
    mask = fits & valid & cap_ok[safe] & task_ok[:, None]

    exhausted = task_ok & ~jnp.any(mask, axis=1)
    failed = failed | (
        exhausted & (cand_total <= K) & ~fits_releasing
    )
    refill = refill | (exhausted & (cand_total > K))
    mask = mask & ~(blocked_of(failed) | refill)[:, None]

    dims = (CPU_DIM, MEM_DIM)
    score = _dyn_score_core(
        task_req[:, None, dims],
        idle_slab[..., dims],
        node_cap[safe][..., dims],
        lr_weight, br_weight,
    ) + cand_static
    # GLOBAL task/node ids in the hash bits: a task's tie-break for a
    # node is identical on the sparse and dense paths, so a slab that
    # covers every eligible node (K >= cand_total) reproduces the dense
    # argmax bit-for-bit (candidates are stored ascending by node id,
    # matching argmax's first-max tie rule).
    key = bid_keys(score, task_ids[:, None], cand_nodes)
    key = jnp.where(mask, key, -1)

    def commit_once(_, state):
        assigned, idle, ntask, qalloc, any_acc, key = state
        live = assigned < 0
        bid_col = jnp.argmax(key, axis=1).astype(jnp.int32)
        has_bid = live & (key[arange_t, bid_col] >= 0)
        bid = jnp.where(has_bid, cand_nodes[arange_t, bid_col], N)
        assigned, idle, ntask, qalloc, acc = _commit_bids(
            bid, assigned, idle, ntask, qalloc,
            task_req=task_req, task_fit=task_fit,
            task_rank=task_rank, task_queue=task_queue,
            node_max_tasks=node_max_tasks,
            queue_deserved=queue_deserved, eps=eps,
        )
        # Losers stop re-bidding the slab column they just lost this
        # round (fresh scores next round may still pick it).
        lost = has_bid & (assigned < 0)
        col = jnp.where(has_bid, bid_col, 0)
        key = key.at[arange_t, col].set(
            jnp.where(lost, -1, key[arange_t, col])
        )
        return assigned, idle, ntask, qalloc, any_acc | acc, key

    assigned, idle, ntask, qalloc, any_accept, _ = lax.fori_loop(
        0, COMMITS_PER_ROUND, commit_once,
        (assigned, idle, ntask, qalloc, jnp.asarray(False), key),
    )
    return assigned, idle, ntask, qalloc, failed, refill, any_accept


def _cand_classes(inputs) -> int:
    """Candidate-class count of an inputs bundle (0 = dense)."""
    if getattr(inputs, "cand_idx", None) is None:
        return 0
    if getattr(inputs, "task_cand", None) is None:
        return 0
    return int(inputs.cand_idx.shape[0])


def solve_sparse(
    inputs: SolverInputs,
    max_rounds: int = 256,
    tail_bucket: int = 3072,
) -> SolverResult:
    """Two-phase candidate-sparsified solve.

    Phase 1 ran host-side at snapshot time (solver/topk.py): a fused
    feasibility + static-score pass over each candidate CLASS (tasks
    sharing predicate group, req/fit rows, and private rows — gang
    members dedup to one list) kept the top-K candidate nodes per
    class. Phase 2 here runs the bid/commit rounds over the gathered
    [T, K] slabs (:func:`_sparse_round`) to a fixed point, then drains
    refill-flagged tasks (truncated slab exhausted) and any stragglers
    through the compacted dense stage shared with :func:`solve_staged`
    (:func:`_dense_tail`) — per-job priority order, global job-break
    and queue-budget state, full-N fidelity on exactly the tasks that
    need it. ``result.refills`` counts tasks that needed the refill
    route; ``result.stages`` the dense stages that drained them.

    Memory: the dense path materializes [T, N] mask/score/key
    intermediates (~1 GB f32 at 50k×5k, ~16 GB at 200k×20k — the shape
    this path exists to unlock); the sparse path's largest live tensors
    are [T, K, R] gathers.
    """
    if isinstance(inputs, PackedInputs):
        inputs = inputs.unpack()
    if _cand_classes(inputs) == 0:
        # No candidate slabs on this bundle: dense dispatch.
        return _dense_auto(inputs, max_rounds)
    C, K = inputs.cand_idx.shape
    T, R = inputs.task_req.shape
    eps = inputs.eps

    # Class → task expansion: per-task [K] slab tables.
    cls = jnp.clip(inputs.task_cand, 0, C - 1)
    cand_nodes = inputs.cand_idx[cls]                    # i32[T, K]
    cand_static = inputs.cand_static[cls]                # f32[T, K]
    cand_total = inputs.cand_info[0][cls]                # i32[T]
    # Class-level Releasing escape hatch (tasks of a class share fit
    # rows, so the per-task and per-class verdicts coincide; computed
    # host-side from the same feas/releasing matrices solve() uses).
    fits_releasing = inputs.cand_info[2][cls].astype(bool)

    INT_MAX = jnp.iinfo(jnp.int32).max

    def job_blocked(failed):
        first_fail = jax.ops.segment_min(
            jnp.where(failed, inputs.task_rank, INT_MAX),
            inputs.task_job,
            num_segments=T,
        )
        return inputs.task_rank > first_fail[inputs.task_job]

    shared_kw = dict(
        node_cap=inputs.node_cap, node_max_tasks=inputs.node_max_tasks,
        queue_deserved=inputs.queue_deserved,
        lr_weight=inputs.lr_weight, br_weight=inputs.br_weight, eps=eps,
    )
    head_kw = dict(
        task_req=inputs.task_req, task_fit=inputs.task_fit,
        task_rank=inputs.task_rank, task_queue=inputs.task_queue,
        task_sel=inputs.task_valid,
        task_ids=inputs.task_rank,
        cand_nodes=cand_nodes, cand_static=cand_static,
        cand_total=cand_total,
        fits_releasing=fits_releasing, blocked_of=job_blocked,
        **shared_kw,
    )

    # ---------------- head: slab rounds to a fixed point ---------------
    def head_body(state):
        assigned, idle, ntask, qalloc, failed, refill, _, rnd = state
        (
            assigned, idle, ntask, qalloc, failed, refill, any_accept
        ) = _sparse_round(
            assigned, idle, ntask, qalloc, failed, refill, **head_kw
        )
        return (
            assigned, idle, ntask, qalloc, failed, refill, any_accept,
            rnd + 1,
        )

    def head_cond(state):
        changed, rnd = state[6], state[7]
        return changed & (rnd < max_rounds)

    init = (
        jnp.full((T,), -1, jnp.int32),
        inputs.node_idle,
        inputs.node_task_count,
        inputs.queue_allocated,
        jnp.zeros((T,), bool),
        jnp.zeros((T,), bool),
        jnp.array(True),
        jnp.array(0, jnp.int32),
    )
    (
        assigned, idle, ntask, qalloc, failed, refill, _, rounds
    ) = lax.while_loop(head_cond, head_body, init)
    refills = jnp.sum(refill.astype(jnp.int32))

    # ---------------- refill / drain: compacted dense stages -----------
    # At the head's fixed point every still-eligible pending task is
    # refill-flagged (a fitting candidate would have produced an accept)
    # — the dense tail re-derives eligibility itself, so the flag only
    # needed to stop slab re-bidding.
    (
        assigned, idle, _, qalloc, _, rounds, stages
    ) = _dense_tail(
        inputs, assigned, idle, ntask, qalloc, failed, rounds,
        fits_releasing=fits_releasing, job_blocked=job_blocked,
        shared_kw=shared_kw, max_rounds=max_rounds,
        tail_bucket=tail_bucket,
    )
    return SolverResult(assigned, idle, qalloc, rounds, stages, refills)


# Above this size the per-round O(T·N) compute plus O(T log T) conflict
# sorts make the staged head+compacted-tail structure win.
_STAGED_MIN_NODES = 768
_STAGED_MIN_TASKS = 16384


def staged_rule(n_tasks: int, n_nodes: int) -> bool:
    """The staged-or-full rule for dense solves, on the bundle's padded
    axes: the single-device trace and the solve plan both apply it."""
    return n_nodes >= _STAGED_MIN_NODES and n_tasks >= _STAGED_MIN_TASKS


def _dense_auto(shaped, max_rounds: int) -> SolverResult:
    """Shape dispatch between the full and staged DENSE solvers."""
    if staged_rule(shaped.task_req.shape[0], shaped.node_idle.shape[0]):
        return solve_staged(shaped, max_rounds=max_rounds)
    return solve(shaped, max_rounds=max_rounds)


def solve_auto(inputs, max_rounds: int = 256) -> SolverResult:
    """Dispatch by (static) snapshot shape: candidate-sparsified solve
    when the snapshot carries candidate slabs (tensorize builds them
    when the solve plan says sparse — solver/plan.py), else the
    full/staged dense solver."""
    shaped = inputs.unpack() if isinstance(inputs, PackedInputs) else inputs
    if _cand_classes(shaped) > 0:
        return solve_sparse(shaped, max_rounds=max_rounds)
    return _dense_auto(shaped, max_rounds)


solve_jit = jax.jit(
    solve_auto, static_argnames=("max_rounds",)
)
solve_full_jit = jax.jit(
    solve, static_argnames=("max_rounds",)
)
solve_staged_jit = jax.jit(
    solve_staged,
    static_argnames=("max_rounds", "tail_bucket"),
)
solve_sparse_jit = jax.jit(
    solve_sparse,
    static_argnames=("max_rounds", "tail_bucket"),
)


def jit_compilation_count() -> int:
    """Distinct compiled variants across the module-level solve jits
    plus the device-cache patch jits. A long-running scheduler's count
    must go FLAT once the shape buckets are warm — growth across steady
    cycles means a shape/dtype drift reintroduced per-cycle tracing
    (pinned by tests/solver/test_retrace_guard.py; exported via
    metrics.solver_jit_compilations)."""
    from . import spmd
    from .device_cache import patch_jit_cache_size
    from .select_device import jit_cache_size as select_jit_cache_size

    total = 0
    fns = [solve_jit, solve_full_jit, solve_staged_jit, solve_sparse_jit]
    for ref in spmd._jitted_steps:
        fn = ref()
        if fn is not None:  # dead weakref = lru-evicted step
            fns.append(fn)
    for fn in fns:
        try:
            total += fn._cache_size()
        except Exception:  # pragma: no cover - private-API drift
            pass
    return total + patch_jit_cache_size() + select_jit_cache_size()
