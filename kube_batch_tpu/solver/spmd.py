"""Explicit-SPMD sharded solve: hierarchical conflict resolution.

Letting GSPMD partition the single-device program is correct but
collective-dominated at scale: the per-commit global argmax over a
node-sharded [T, N] key matrix and the scatter that voids lost columns
make it materialize cross-shard gathers of [T, N]-sized intermediates —
measured 1.6x SLOWER than single-device at 10k x 1001 on the 8-device
CPU mesh (MULTICHIP_r04), and since removed.

This module writes the SPMD program explicitly with `shard_map`,
restructuring conflict resolution hierarchically (VERDICT r4 item 2):

- LOCAL bid: each shard owns N/s node columns. The O(T*N) work — fit
  mask, dynamic scores, integer bid keys, per-task argmax — runs on the
  local [T, N/s] block only. Each shard reduces to [T]-sized vectors:
  its best key and best local node per task (per commit), or its
  top-COMMITS_PER_ROUND candidate lists (pool style, once per round).
- GLOBAL reconcile: one `all_gather` ships those [T] vectors (s * T * 8
  bytes total — NOT [T, N]); every shard then computes the same global
  winner per task. Ties break toward the lowest shard then lowest local
  column, which is exactly the single-device argmax's first-max rule, so
  placement parity is bit-exact.
- SHARD-0 commit: node idle/task-count and queue budget tables are tiny
  (O(N*R), O(Q*R)) and kept replicated as VALUES, but the sort-based
  `_commit_bids` itself runs on shard 0 only, which psum-broadcasts its
  packed result (zeros from the other shards). Replicated commit
  compute would be free on real parallel chips but multiplies wall time
  by the shard count on an oversubscribed/emulated mesh — measured
  +0.28 s/device/solve at 10k x 1001. Only the shard that OWNS a lost
  bidder's column voids it locally.

Per commit the only communication is one packed candidate all_gather
and one packed psum broadcast (the pool style amortizes both to once
per ROUND — see `_spmd_round`). Everything else is either node-local or
replicated. On real hardware these collectives ride ICI (scaling-book
recipe: shard the big axis, gather only reductions); on the 1-core
virtual CPU mesh the shards serialize, so the honest target there is
parity with single-device, not speedup — the win is that the sharded
program does no more TOTAL work than the single-device one, which the
GSPMD partitioning could not achieve.

Reference analog being replaced: the 16-worker PredicateNodes fan-out,
util/scheduler_helper.go:84,137 — itself a shard-the-node-axis design.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from .kernels import (
    PackedInputs,
    SolverInputs,
    SolverResult,
    _apply_accepts,
    _commit_bids,
    _dense_tail,
    _resolve_bids,
    _dyn_score_core,
    CPU_DIM,
    MEM_DIM,
    COMMITS_PER_ROUND,
    bid_keys,
    less_equal,
    tail_local_blocked,
    tail_subset_feas,
    tail_subset_static,
)

NODE_AXIS = "nodes"

# SolverInputs fields carrying node COLUMNS (sharded); node TABLES
# (idle/cap/releasing/counts) stay replicated — they are O(N*R) small and
# the replicated commit updates them identically on every shard. The
# field → sharded-dim declaration lives in solver/contracts.py
# (DENSE_SPMD_SHARD_DIMS, cross-checked by kbtlint's shape-contracts
# pass); this derives the PartitionSpecs from it.
from .contracts import DENSE_SPMD_SHARD_DIMS as _DENSE_SHARD_DIMS

_SHARDED_SPECS = {
    f: P(*([None] * dim + [NODE_AXIS]))
    for f, dim in _DENSE_SHARD_DIMS.items()
}

INT_MAX = 2**31 - 1


def spmd_shardings_for(inputs, mesh: Mesh):
    """Device-put layout for the hierarchical solver: node COLUMN fields
    sharded over the mesh, node/queue tables and task vectors replicated.
    (PackedInputs stacks node tables with the feas column in node_i32, so
    its node buffers stay replicated; shard_map lays the unpacked
    node_feas out per-shard at trace time.)"""
    from jax.sharding import NamedSharding

    rep = NamedSharding(mesh, P())
    cls = type(inputs)

    def spec(f, sh):
        # None-able candidate-slab fields mirror as None so device_put
        # treedefs match (slabs replicate: they carry node IDS, and the
        # dense shard_map solve ignores them).
        return None if getattr(inputs, f, None) is None else sh

    if isinstance(inputs, PackedInputs):
        minor = NamedSharding(mesh, P(None, NODE_AXIS))
        sharded = {"group_feas", "pair_feas", "score_rows"}
        return cls(**{
            f: spec(f, minor if f in sharded else rep)
            for f in cls._fields
        })
    return cls(**{
        f: spec(
            f,
            NamedSharding(mesh, _SHARDED_SPECS[f])
            if f in _SHARDED_SPECS else rep,
        )
        for f in cls._fields
    })


def _local_feasibility(inputs, n_local, valid):
    """[T, N/s] static predicate mask from the shard's local columns
    (local form of kernels.build_feasibility)."""
    T = inputs.task_req.shape[0]
    feas = (
        inputs.group_feas[inputs.task_group]
        & inputs.node_feas[None, :]
        & valid[:, None]
    )
    Pn = inputs.pair_idx.shape[0]
    if Pn:
        ext = jnp.ones((T + 1, n_local), bool).at[inputs.pair_idx].set(
            inputs.pair_feas
        )
        feas = feas & ext[:T]
    return feas


def _local_static_score(inputs, n_local):
    """[T, N/s] static score block (local build_static_score)."""
    T = inputs.task_req.shape[0]
    S = inputs.score_idx.shape[0]
    if not S:
        return jnp.zeros((), jnp.float32)
    ext = jnp.zeros((T + 1, n_local), jnp.float32).at[
        inputs.score_idx
    ].add(inputs.score_rows)
    return ext[:T]


# Round style dispatch: the candidate-pool round pays one fixed
# [T, N/s] top-C extraction per round (then commits touch only the tiny
# pool), the per-commit round re-argmaxes [T, N/s] per commit but skips
# the extraction. Measured crossover on the 8-device mesh: pool wins for
# compacted-tail-sized task blocks, per-commit wins at full width.
_POOL_MAX_T = 4096


def _spmd_round(
    assigned, idle, ntask, qalloc, failed,
    *, task_req, task_fit, task_rank, task_queue, task_sel, task_ids,
    feas_l, static_l, fits_releasing, blocked_of,
    node_cap, node_max_tasks, queue_deserved,
    lr_weight, br_weight, eps, n_off, n_local, style,
):
    """One solver round, hierarchical. Mirrors kernels._solve_round's
    semantics exactly (same gating, same job-break rule, same multi-
    commit cascade) with bit-exact placement parity.

    Shared structure: the O(T*N) work — fit mask, dynamic scores,
    integer bid keys — builds on the LOCAL [T, N/s] column block; the
    sort-based conflict-resolution commit runs on shard 0 only against
    replicated node/queue tables and psum-broadcasts its packed result
    (running it replicated would be free on real parallel chips but
    multiplies wall time by the shard count on an oversubscribed/
    emulated mesh — measured +0.28 s/device/solve at 10k x 1001).

    ``style`` picks the reconcile cadence:

    - ``"pool"``: extract each shard's top-COMMITS_PER_ROUND candidates
      once per round by iterative argmax+void, gather them in ONE
      collective, and run every commit against the
      [s*COMMITS_PER_ROUND, T] pool — 2 collectives per round. Within a
      round voids only remove commit winners, which by construction sit
      at the top of their shard's list, and the LAST commit's selection
      sees at most COMMITS_PER_ROUND - 1 voids, so the true global
      argmax always remains inside the pool at every commit: exact
      equivalence with the full-matrix re-argmax.
    - ``"commit"``: re-argmax the local block per commit and reconcile
      with one packed two-[T]-vector gather per commit (2 collectives
      per commit, but no extraction pass). The job-break verdict folds
      into the first commit's gather.

    Row-level gates (task_ok, job-block) are applied at bid time, which
    is equivalent to masking rows before the argmax because both are
    row-independent.
    """
    N = idle.shape[0]
    T = task_req.shape[0]
    # Candidate depth for the pool style: a task voids at most one
    # column per commit, and the LAST commit's selection sees at most
    # COMMITS_PER_ROUND - 1 voids, so top-COMMITS_PER_ROUND per shard
    # is exactly enough for the pool max to equal the full-matrix
    # post-void argmax at every commit.
    C = COMMITS_PER_ROUND
    arange_t = jnp.arange(T, dtype=jnp.int32)
    shard = lax.axis_index(NODE_AXIS)
    nshards = lax.psum(1, NODE_AXIS)

    pending = assigned < 0
    q_over = less_equal(queue_deserved, qalloc, eps)
    task_ok = (
        pending & task_sel & ~q_over[task_queue] & ~blocked_of(failed)
    )

    # Local node slices of the replicated tables.
    idle_l = lax.dynamic_slice_in_dim(idle, n_off, n_local)
    cap_l = lax.dynamic_slice_in_dim(node_cap, n_off, n_local)
    ntask_l = lax.dynamic_slice_in_dim(ntask, n_off, n_local)
    maxt_l = lax.dynamic_slice_in_dim(node_max_tasks, n_off, n_local)
    cap_ok_l = (maxt_l == 0) | (ntask_l < maxt_l)

    # Column-level masks only; row gates apply at the pool. The keys are
    # stale within the round by design (same as the single-device
    # multi-commit): fits/budgets are re-checked exactly in every
    # _commit_bids against the updated idle/qalloc.
    fits_l = less_equal(task_fit[:, None, :], idle_l[None, :, :], eps)
    mask_l = fits_l & feas_l & cap_ok_l[None, :] & task_sel[:, None]

    score_l = _dyn_score_core(
        task_req[:, None, (CPU_DIM, MEM_DIM)],
        idle_l[None, :, (CPU_DIM, MEM_DIM)],
        cap_l[None, :, (CPU_DIM, MEM_DIM)],
        lr_weight, br_weight,
    ) + static_l
    # GLOBAL column ids in the hash so keys match the single-device
    # kernel bit-for-bit.
    key_l = bid_keys(
        score_l,
        task_ids[:, None],
        (n_off + jnp.arange(n_local, dtype=jnp.int32))[None, :],
    )
    key_l = jnp.where(mask_l, key_l, -1)

    Q = qalloc.shape[0]
    Rr = idle.shape[1]

    def broadcast_from_shard0(do_commits):
        """Run ``do_commits`` on shard 0 only and psum-broadcast its
        packed (i32, f32) result buffers (zeros elsewhere)."""

        def skip_commits(_):
            return (
                jnp.zeros((T + N + 1,), jnp.int32),
                jnp.zeros((N * Rr + Q * Rr,), jnp.float32),
            )

        ibuf, fbuf = lax.psum(
            lax.cond(shard == 0, do_commits, skip_commits, None),
            NODE_AXIS,
        )
        return (
            ibuf[:T],                       # assigned
            fbuf[: N * Rr].reshape(N, Rr),  # idle
            ibuf[T:T + N],                  # ntask
            fbuf[N * Rr:].reshape(Q, Rr),   # qalloc
            ibuf[T + N] > 0,                # any_accept
        )

    def pack_commit_result(assigned_, idle_, ntask_, qalloc_, acc_):
        return (
            jnp.concatenate(
                [assigned_, ntask_, acc_.astype(jnp.int32)[None]]
            ),
            jnp.concatenate([idle_.ravel(), qalloc_.ravel()]),
        )

    if style == "pool":
        # Per-shard top-C candidates by iterative argmax+void (lax.top_k
        # lowers poorly at these shapes on both TPU and CPU; argmax
        # chains match the single-device kernel's tie-break exactly:
        # first index of the max). Python-unrolled — C is small and
        # static, and accumulating via .at[i].set inside a fori_loop
        # costs a [C, T] scatter per step (measured ~80 ms/round at
        # 10k) where unrolled collection is a free stack.
        ck_list, cn_list = [], []
        for _ in range(C):
            b = jnp.argmax(key_l, axis=1).astype(jnp.int32)
            ck_list.append(key_l[arange_t, b])
            cn_list.append(n_off + b)
            key_l = key_l.at[arange_t, b].set(-1)
        ck = jnp.stack(ck_list)
        cn = jnp.stack(cn_list)

        # ONE gather -> replicated candidate pool [s*C, T].
        g = lax.all_gather(jnp.stack([ck, cn]), NODE_AXIS)  # [s, 2, C, T]
        pool_k = g[:, 0].reshape(nshards * C, T)
        pool_n = g[:, 1].reshape(nshards * C, T)

        # Job-break verdict: any feasible column anywhere == pool top-1
        # somewhere. (For gated rows any_feas may differ from the
        # single-device value, but ``failed`` is ANDed with task_ok
        # exactly like _solve_round, so the verdict matches.)
        any_feas = jnp.max(pool_k, axis=0) >= 0
        failed = failed | (task_ok & ~any_feas & ~fits_releasing)
        gate = task_ok & ~blocked_of(failed)

        def do_commits(_):
            def commit_once(_, state):
                assigned, idle, ntask, qalloc, any_acc, pool_k = state
                live = gate & (assigned < 0)
                wkey = jnp.max(pool_k, axis=0)
                # Lowest global node among max-key entries == the full
                # matrix argmax's first-max-index rule.
                wnode = jnp.min(
                    jnp.where(pool_k == wkey[None, :], pool_n, INT_MAX),
                    axis=0,
                )
                has_bid = live & (wkey >= 0)
                bid = jnp.where(has_bid, wnode, N)
                assigned, idle, ntask, qalloc, acc = _commit_bids(
                    bid, assigned, idle, ntask, qalloc,
                    task_req=task_req, task_fit=task_fit,
                    task_rank=task_rank, task_queue=task_queue,
                    node_max_tasks=node_max_tasks,
                    queue_deserved=queue_deserved, eps=eps,
                )
                # Losers stop re-bidding the column they just lost:
                # void that (task, node) pool entry (global node ids
                # are unique across shards, so exactly one matches).
                lost = has_bid & (assigned < 0)
                pool_k = jnp.where(
                    lost[None, :] & (pool_n == wnode[None, :]), -1,
                    pool_k,
                )
                return (
                    assigned, idle, ntask, qalloc, any_acc | acc, pool_k
                )

            assigned_, idle_, ntask_, qalloc_, acc_, _ = lax.fori_loop(
                0, COMMITS_PER_ROUND, commit_once,
                (
                    assigned, idle, ntask, qalloc, jnp.asarray(False),
                    pool_k,
                ),
            )
            return pack_commit_result(
                assigned_, idle_, ntask_, qalloc_, acc_
            )

        assigned, idle, ntask, qalloc, any_accept = broadcast_from_shard0(
            do_commits
        )
        return assigned, idle, ntask, qalloc, failed, any_accept

    # ---- style == "commit": per-commit reconcile ----------------------
    # Each commit re-argmaxes the live local [T, N/s] key block and
    # reconciles with one packed two-vector gather; the commit itself
    # runs on shard 0 and broadcasts. 2 collectives per commit. The
    # job-break verdict folds into the FIRST commit's gather (the
    # gathered maxima give any-feasible), so no separate psum.
    def commit_once(c, state):
        assigned, idle, ntask, qalloc, any_acc, key_l, failed, gate = state
        live = assigned < 0
        lbid = jnp.argmax(key_l, axis=1).astype(jnp.int32)
        lkey = key_l[arange_t, lbid]
        gkn = lax.all_gather(
            jnp.stack([lkey, lbid]), NODE_AXIS
        )                                              # [s, 2, T]
        gk, gn = gkn[:, 0, :], gkn[:, 1, :]
        wshard = jnp.argmax(gk, axis=0).astype(jnp.int32)
        wkey = jnp.max(gk, axis=0)
        wnode = jnp.take_along_axis(gn, wshard[None, :], axis=0)[0]
        # First commit: derive the job-break verdict from the gathered
        # maxima (any feasible column anywhere <=> max key >= 0 — the
        # keys are void-free at this point). ``failed``/``gate`` are
        # loop-invariant afterwards, so carry them instead of paying
        # the O(T) job-block scan on every commit on every shard.
        failed = jnp.where(
            c == 0,
            failed | (task_ok & ~(wkey >= 0) & ~fits_releasing),
            failed,
        )
        gate = lax.cond(
            c == 0,
            lambda _: task_ok & ~blocked_of(failed),
            lambda _: gate,
            None,
        )
        has_bid = gate & live & (wkey >= 0)
        bid = jnp.where(has_bid, wshard * n_local + wnode, N)

        def do_commit(_):
            return pack_commit_result(*_commit_bids(
                bid, assigned, idle, ntask, qalloc,
                task_req=task_req, task_fit=task_fit,
                task_rank=task_rank, task_queue=task_queue,
                node_max_tasks=node_max_tasks,
                queue_deserved=queue_deserved, eps=eps,
            ))

        def skip_commit(_):
            return (
                jnp.zeros((T + N + 1,), jnp.int32),
                jnp.zeros((N * Rr + Q * Rr,), jnp.float32),
            )

        ibuf, fbuf = lax.psum(
            lax.cond(shard == 0, do_commit, skip_commit, None),
            NODE_AXIS,
        )
        assigned = ibuf[:T]
        ntask = ibuf[T:T + N]
        acc = ibuf[T + N] > 0
        idle = fbuf[: N * Rr].reshape(N, Rr)
        qalloc = fbuf[N * Rr:].reshape(Q, Rr)
        # Void lost columns — only the owner shard holds that column.
        lost = has_bid & (assigned < 0)
        mine = wshard == shard
        col = jnp.where(has_bid & mine, wnode, 0)
        key_l = key_l.at[arange_t, col].set(
            jnp.where(lost & mine, -1, key_l[arange_t, col])
        )
        return (
            assigned, idle, ntask, qalloc, any_acc | acc, key_l, failed,
            gate,
        )

    (
        assigned, idle, ntask, qalloc, any_accept, _, failed, _
    ) = lax.fori_loop(
        0, COMMITS_PER_ROUND, commit_once,
        (
            assigned, idle, ntask, qalloc, jnp.asarray(False), key_l,
            failed, jnp.zeros((T,), bool),
        ),
    )
    return assigned, idle, ntask, qalloc, failed, any_accept


def _solve_spmd_local(inputs: SolverInputs, max_rounds: int,
                      tail_bucket: int, staged: bool):
    """The per-shard body (runs under shard_map). ``inputs`` fields are
    LOCAL blocks for the four column-factorized fields and full
    replicated arrays for everything else."""
    T, R = inputs.task_req.shape
    if staged and T <= tail_bucket:
        # solve_staged's escape: a snapshot smaller than the tail bucket
        # IS one tail-sized block — the full-width solve is the same
        # program without the compaction scaffolding (lax.top_k would
        # reject k > T).
        staged = False
    n_local = inputs.node_feas.shape[0]          # local column count
    N = inputs.node_idle.shape[0]                # full (replicated) table
    shard = lax.axis_index(NODE_AXIS)
    n_off = shard * n_local
    eps = inputs.eps

    feas_l = _local_feasibility(inputs, n_local, inputs.task_valid)
    static_l = _local_static_score(inputs, n_local)

    rel_l = lax.dynamic_slice_in_dim(inputs.node_releasing, n_off, n_local)
    fits_releasing = lax.psum(
        jnp.any(
            less_equal(inputs.task_fit[:, None, :], rel_l[None, :, :], eps)
            & feas_l,
            axis=1,
        ).astype(jnp.int32),
        NODE_AXIS,
    ) > 0

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
        n_off=n_off,
    )
    head_kw = dict(
        task_req=inputs.task_req, task_fit=inputs.task_fit,
        task_rank=inputs.task_rank, task_queue=inputs.task_queue,
        task_sel=inputs.task_valid,
        # Global-rank tie hashes (== arange on full bundles; warm subset
        # bundles carry non-contiguous ranks — see kernels.solve).
        task_ids=inputs.task_rank,
        feas_l=feas_l, static_l=static_l,
        fits_releasing=fits_releasing, blocked_of=job_blocked,
        n_local=n_local,
        style="pool" if T <= _POOL_MAX_T else "commit",
        **shared_kw,
    )

    init = (
        jnp.full((T,), -1, jnp.int32),
        inputs.node_idle,
        inputs.node_task_count,
        inputs.queue_allocated,
        jnp.zeros((T,), bool),
        jnp.array(True),
        jnp.array(0, jnp.int32),
    )

    if not staged:
        def body(state):
            assigned, idle, ntask, qalloc, failed, _, rnd = state
            out = _spmd_round(
                assigned, idle, ntask, qalloc, failed, **head_kw
            )
            return (*out[:5], out[5], rnd + 1)

        def cond(state):
            return state[5] & (state[6] < max_rounds)

        assigned, idle, _, qalloc, _, _, rounds = lax.while_loop(
            cond, body, init
        )
        return SolverResult(assigned, idle, qalloc, rounds)

    # ---- staged: full-width head + compacted tail (solve_staged's
    # structure with local column blocks) ------------------------------
    B = tail_bucket

    def head_body(state):
        assigned, idle, ntask, qalloc, failed, _, rnd, _ = state
        assigned, idle, ntask, qalloc, failed, any_accept = _spmd_round(
            assigned, idle, ntask, qalloc, failed, **head_kw
        )
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
        return state[5] & (state[6] < max_rounds) & (state[7] > B)

    (
        assigned, idle, ntask, qalloc, failed, _, rounds, _
    ) = lax.while_loop(head_cond, head_body, (*init, jnp.array(T, jnp.int32)))

    def tail_outer_body(ostate):
        assigned, idle, ntask, qalloc, failed, _, rounds, stages = ostate

        blocked = job_blocked(failed)
        q_over = less_equal(inputs.queue_deserved, qalloc, eps)
        elig = (
            (assigned < 0)
            & inputs.task_valid
            & ~failed
            & ~blocked
            & ~q_over[inputs.task_queue]
        )
        sel_key = jnp.where(elig, inputs.task_rank, INT_MAX)
        _, idxs = lax.top_k(-sel_key, B)
        idxs = idxs.astype(jnp.int32)
        valid2 = sel_key[idxs] != INT_MAX

        # Shared with kernels.solve_staged: inside shard_map the four
        # column-factorized inputs fields are the LOCAL blocks, so the
        # same subset builders produce [B, N/s] rows here.
        blocked_from, rank2 = tail_local_blocked(inputs, idxs, B)
        tail_kw = dict(
            task_req=inputs.task_req[idxs], task_fit=inputs.task_fit[idxs],
            task_rank=rank2, task_queue=inputs.task_queue[idxs],
            task_sel=valid2, task_ids=rank2,
            feas_l=tail_subset_feas(inputs, idxs, valid2),
            static_l=tail_subset_static(inputs, idxs),
            fits_releasing=fits_releasing[idxs],
            blocked_of=blocked_from,
            n_local=n_local,
            style="pool" if B <= _POOL_MAX_T else "commit",
            **shared_kw,
        )

        def tail_body(state):
            sub_assigned, idle, ntask, qalloc, failed2, _, rnd = state
            out = _spmd_round(
                sub_assigned, idle, ntask, qalloc, failed2, **tail_kw
            )
            return (*out[:5], out[5], rnd + 1)

        def tail_cond(state):
            return state[5] & (state[6] < max_rounds)

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
        assigned, idle, _, qalloc, _, _, rounds, stages
    ) = lax.while_loop(tail_outer_cond, tail_outer_body, ostate)
    return SolverResult(assigned, idle, qalloc, rounds, stages)


# Weakrefs to the jitted sharded steps, for the retrace census
# (kernels.jit_compilation_count): the multi-chip path must show up in
# the same compilation counters the retrace guard pins flat. Weak so
# the census never pins an executable past its lru_cache eviction —
# it counts LIVE compiled variants, exactly what the cache bounds.
_jitted_steps: list = []


@functools.lru_cache(maxsize=32)
def _spmd_step(mesh: Mesh, staged, max_rounds, tail_bucket):
    """Jitted shard_map solve for a mesh (cached per config)."""

    def run(inputs):
        if isinstance(inputs, PackedInputs):
            inputs = inputs.unpack()  # inside jit: free slicing
        # None-able candidate-slab fields mirror as None (treedef
        # match); present slabs replicate but are IGNORED here — the
        # sharded solvers keep the dense rounds (candidate gathers
        # would force cross-shard node-row collectives per round).
        in_specs = SolverInputs(**{
            f: (
                None if getattr(inputs, f, None) is None
                else _SHARDED_SPECS.get(f, P())
            )
            for f in SolverInputs._fields
        })
        fn = shard_map(
            functools.partial(
                _solve_spmd_local,
                max_rounds=max_rounds,
                tail_bucket=tail_bucket,
                staged=staged,
            ),
            mesh=mesh,
            in_specs=(in_specs,),
            out_specs=P(),
            # Replication of the outputs is by construction (the commit
            # runs on replicated operands on every shard); the static
            # checker cannot see through the while_loop carries.
            check_vma=False,
        )
        return fn(inputs)

    import weakref

    step = jax.jit(run)
    _jitted_steps.append(weakref.ref(step))
    return step


def solve_spmd(
    inputs,
    mesh: Mesh,
    max_rounds: int = 256,
    staged: bool = False,
    tail_bucket: int = 3072,
) -> SolverResult:
    """Run the hierarchical sharded solve on ``mesh``. Same results as
    the single-device ``solve`` (or ``solve_staged`` when ``staged``),
    bit-exact. Node axis must be padded to a multiple of ``mesh.size``
    (sharding.pad_nodes; the production tensorize buckets N to 128s)."""
    return _spmd_step(mesh, staged, max_rounds, tail_bucket)(inputs)


# ---------------------------------------------------------------------------
# Sharded SPARSE solve: slab rows over devices (PR 12).
#
# The dense SPMD solvers above shard the NODE axis because every dense
# intermediate is [T, N]. The candidate-sparsified solve has no [T, N]
# structure at all — its round-dominating tensors are the per-TASK slab
# expansions ([T, K] candidate ids/keys and the [T, K, R] idle gathers)
# — so the scale axis to partition is the TASK axis. Each shard owns a
# contiguous block of T/s slab rows and runs the O(T·K/s) mask → score
# → integer-key → per-row argmax work locally; because every one of
# those computations is ROW-independent, the local block computes
# bit-exactly what the single-device kernel computes for the same rows.
# The only cross-task computation in the sparse solver is conflict
# resolution: bids carry GLOBAL node ids, so `_commit_bids`' dense [N]
# capacity accounting becomes the per-commit cross-shard collective —
# one all_gather assembles the full [T] bid vector (s·T·4 bytes, never
# [T, K]), shard 0 runs the sort-based commit against the replicated
# node/queue tables, and one psum broadcasts the packed result (the
# same shard-0-commit rationale as `_spmd_round`: replicated commit
# compute is free on real parallel chips but multiplies wall time by
# the shard count on an oversubscribed/emulated mesh). Exhaustion
# verdicts gather the same way once per round, so failed/refill/
# job-break state stays replicated [T] and exactly mirrors
# `_sparse_round`'s update order. Refill-flagged tasks drain through
# the SAME `_dense_tail` stage the single-device sparse solve uses —
# run on shard 0 against the replicated full inputs and broadcast —
# which is what makes the whole path bit-equal to `solve_sparse`.
#
# All INPUT fields stay replicated values (task vectors are O(T) small;
# the class-level [C, K] slabs are KB-scale): only the derived per-task
# expansions — the memory that actually grows with T·K — are sharded,
# by never materializing more than the local block of them. The
# declared layout lives in solver/contracts.py (SPARSE_SHARD_DIMS).
#
# The TWO-LEVEL mode (Tesserae, PAPERS.md: scalable placement policies
# decompose into per-sub-cluster solves reconciled globally) trades the
# per-commit collective for collective-FREE local solves: the node
# space splits into s contiguous racks (rack i = rows [i·N/s, (i+1)·N/s)),
# shard i solves its task block against ONLY its rack's candidate
# columns and a 1/s headroom slice of every queue budget — disjoint
# node ownership means zero cross-shard capacity conflicts and the
# budget slice means no global queue overshoot — then one psum of the
# state DELTAS reconciles exactly (disjoint rows sum losslessly), and
# the leftovers (tasks whose rack columns were full or infeasible)
# drain through the flat rounds + dense tail above as the global
# reconciliation. Placement quality approximates the global solve
# (documented in doc/design/sparse-candidate-solver.md); node/queue
# invariants are preserved exactly because every accept still goes
# through `_commit_bids`. Two-level is NOT bit-equal to the
# single-device solve — the shape policy (plan._shard_mode) only
# selects it far past the parity-suite shapes.
# ---------------------------------------------------------------------------


def sparse_spmd_shardings_for(inputs: Any, mesh: Mesh) -> Any:
    """Device-put layout for the sharded sparse solve: every input
    field replicated over the mesh (None-able fields mirror as None so
    device_put treedefs match), per contracts.SPARSE_SHARD_DIMS. The
    [T, K] slab expansions shard inside the shard_map body by
    construction — they are derived, never shipped."""
    from jax.sharding import NamedSharding

    from .contracts import SPARSE_SHARD_DIMS

    axis = mesh.axis_names[0]
    rep = NamedSharding(mesh, P())
    by_field = {
        f: NamedSharding(mesh, P(*([None] * dim + [axis])))
        for f, dim in SPARSE_SHARD_DIMS.items()
    }
    cls = type(inputs)
    return cls(**{
        f: (
            None if getattr(inputs, f, None) is None
            else by_field.get(f, rep)
        )
        for f in cls._fields
    })


def _pack_commit(assigned, idle, ntask, qalloc, acc):
    """Pack one commit's state into (i32, f32) psum buffers."""
    return (
        jnp.concatenate([assigned, ntask, acc.astype(jnp.int32)[None]]),
        jnp.concatenate([idle.ravel(), qalloc.ravel()]),
    )


def _slab_mask(task_fit_l, idle, ntask, node_max_tasks, cand_nodes_l,
               col_ok_l, task_ok_l, eps):
    """[Tl, K] slab eligibility for one sharded round: fit against
    CURRENT idle, pod-count caps, column validity, row gate. ONE
    definition shared by the flat and two-level rounds — this is the
    gating whose exactness the bit-parity contract depends on (mirrors
    kernels._sparse_round's mask construction verbatim). Returns
    (mask_l, idle_slab, safe_l)."""
    N = idle.shape[0]
    cap_ok = (node_max_tasks == 0) | (ntask < node_max_tasks)
    safe_l = jnp.minimum(cand_nodes_l, N - 1)
    idle_slab = idle[safe_l]                             # [Tl, K, R]
    fits_l = less_equal(task_fit_l[:, None, :], idle_slab, eps)
    mask_l = fits_l & col_ok_l & cap_ok[safe_l] & task_ok_l[:, None]
    return mask_l, idle_slab, safe_l


def _slab_keys(task_req_l, task_ids_l, cand_nodes_l, cand_static_l,
               idle_slab, safe_l, node_cap, lr_weight, br_weight,
               mask_l):
    """[Tl, K] masked integer bid keys (kernels._sparse_round's
    score→key chain, GLOBAL task/node ids in the hash bits — the other
    half of the shared parity-critical math)."""
    dims = (CPU_DIM, MEM_DIM)
    score_l = _dyn_score_core(
        task_req_l[:, None, dims],
        idle_slab[..., dims],
        node_cap[safe_l][..., dims],
        lr_weight, br_weight,
    ) + cand_static_l
    key_l = bid_keys(score_l, task_ids_l[:, None], cand_nodes_l)
    return jnp.where(mask_l, key_l, -1)


def _commit_code_dtype(k: int):
    """Static dtype for slab-column commit codes: one byte per task
    while K (the slab width, plus the no-bid sentinel K) fits uint8."""
    return jnp.uint8 if k < 255 else jnp.uint16


def _pack_bits(mask: jnp.ndarray) -> jnp.ndarray:
    """Bit-pack a [T] bool mask into u32[ceil(T/32)] words (bit i of
    word w = element w*32+i) — the commit collective's accept wire
    format: 32× smaller than a bool lane, 128× smaller than i32."""
    T = mask.shape[0]
    Tp = -(-T // 32) * 32
    m = jnp.zeros((Tp,), jnp.uint32).at[:T].set(mask.astype(jnp.uint32))
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(m.reshape(-1, 32) << shifts[None, :], axis=1,
                   dtype=jnp.uint32)


def _unpack_bits(words: jnp.ndarray, n: int) -> jnp.ndarray:
    """Inverse of :func:`_pack_bits`: first ``n`` bits as [n] bool.
    Accepts [W] words (→ [n]) or [S, W] gathered rows (→ [S, n])."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(*words.shape[:-1], -1)
    return flat[..., :n].astype(bool)


def _commit_delta(axis, shard, code_l, cand_flat, cls, assigned, idle,
                  ntask, qalloc, *, slab_k, task_req, task_fit,
                  task_rank, task_queue, node_max_tasks, queue_deserved,
                  eps):
    """Delta-packed capacity-commit collective. Instead of psum-
    broadcasting the full post-commit [T]+[N·R]+[Q·R] state from shard
    0 (~4·(2T+N+(N+Q)·R) bytes per commit), exchange only the round's
    decisions and let EVERY shard replay them locally:

    1. all_gather each shard's [Tl] slab-column codes (uint8/uint16:
       column index into the task's candidate row, ``slab_k`` = no
       bid) and reconstruct the full bid vector from the replicated
       ``cand_flat`` slab — the gather moves T bytes, not 4T;
    2. shard 0 resolves conflicts (`_resolve_bids`) and psum-
       broadcasts the accept mask BIT-PACKED (u32[ceil(T/32)], zeros
       elsewhere);
    3. every shard (including shard 0) applies the accepts through the
       shared `_apply_accepts` task-order reduction, so the replicated
       idle/qalloc stay bit-identical across shards and to the
       single-device solve.

    ~8× fewer exchanged bytes per commit at the 65536×4096 A/B shape
    (tracked by `last_commit_stats` / the `commit_bytes_exchanged`
    bench stat)."""
    T = assigned.shape[0]
    N = idle.shape[0]
    codes = lax.all_gather(code_l, axis).reshape(T).astype(jnp.int32)
    has_bid = codes < slab_k
    bid = jnp.where(
        has_bid,
        cand_flat[cls * slab_k + jnp.minimum(codes, slab_k - 1)],
        N,
    )
    W = -(-T // 32)

    def do_resolve(_: None) -> jnp.ndarray:
        return _pack_bits(_resolve_bids(
            bid, idle, ntask, qalloc,
            task_req=task_req, task_fit=task_fit,
            task_rank=task_rank, task_queue=task_queue,
            node_max_tasks=node_max_tasks,
            queue_deserved=queue_deserved, eps=eps,
        ))

    def skip_resolve(_: None) -> jnp.ndarray:
        return jnp.zeros((W,), jnp.uint32)

    words = lax.psum(
        lax.cond(shard == 0, do_resolve, skip_resolve, None), axis
    )
    accept = _unpack_bits(words, T)
    assigned, idle, ntask, qalloc = _apply_accepts(
        accept, bid, assigned, idle, ntask, qalloc,
        task_req=task_req, task_queue=task_queue,
    )
    return assigned, idle, ntask, qalloc, jnp.any(accept)


def commit_exchange_bytes(
    T: int, N: int, Q: int, R: int, K: int,
) -> Dict[str, int]:
    """Static per-commit-round byte accounting for the sparse commit
    collective (what one shard receives per commit): the delta-packed
    exchange vs the legacy full-state broadcast it replaced. Pure
    shape arithmetic — usable eagerly outside the jit."""
    code_bytes = T * jnp.dtype(_commit_code_dtype(K)).itemsize
    accept_bytes = (-(-T // 32)) * 4
    delta = code_bytes + accept_bytes
    full = T * 4 + (T + N + 1) * 4 + (N * R + Q * R) * 4
    return {
        "commit_bytes_exchanged": int(delta),
        "commit_bytes_full_broadcast": int(full),
        "commit_bytes_per_round": int(delta) * COMMITS_PER_ROUND,
    }


def _spmd_sparse_round(
    assigned, idle, ntask, qalloc, failed, refill,
    *, axis, shard, t_off, n_local_tasks,
    task_req, task_fit, task_rank, task_queue, task_valid,
    cand_nodes_l, cand_static_l, cand_flat, cls, cand_total,
    fits_releasing, blocked_of,
    node_cap, node_max_tasks, queue_deserved,
    lr_weight, br_weight, eps,
):
    """One sharded candidate-sparsified round. Mirrors
    :func:`kernels._sparse_round`'s semantics exactly — same gating,
    same complete-vs-truncated exhaustion split, same multi-commit
    cascade — with the [T, K] work on the local row block and two
    delta-packed collectives per commit (`_commit_delta`) plus one
    bit-packed exhaustion gather per round.
    State (assigned/idle/ntask/qalloc/failed/refill) is replicated;
    ``cand_nodes_l``/``cand_static_l`` are the shard's local slab rows;
    ``cand_flat``/``cls`` are the replicated flat slab + class map the
    commit uses to reconstruct full bids from gathered column codes.

    Returns (assigned, idle, ntask, qalloc, failed, refill, any_accept).
    """
    T = task_req.shape[0]
    N = idle.shape[0]
    Tl = n_local_tasks
    K = cand_nodes_l.shape[1]
    code_dtype = _commit_code_dtype(K)
    arange_l = jnp.arange(Tl, dtype=jnp.int32)

    def loc(v: jnp.ndarray) -> jnp.ndarray:
        return lax.dynamic_slice_in_dim(v, t_off, Tl)

    # Global-RANK tie hashes (== t_off + arange on full bundles; warm
    # subset bundles carry non-contiguous ranks — see kernels.solve).
    task_ids_l = loc(task_rank)

    pending = assigned < 0
    q_over = less_equal(queue_deserved, qalloc, eps)
    task_ok = (
        pending & task_valid & ~q_over[task_queue] & ~blocked_of(failed)
        & ~refill
    )

    mask_l, idle_slab, safe_l = _slab_mask(
        loc(task_fit), idle, ntask, node_max_tasks, cand_nodes_l,
        cand_nodes_l < N, loc(task_ok), eps,
    )

    # Exhaustion verdicts are the round's one non-commit collective:
    # gathered (bit-packed, 1/32 of a bool lane) so the failed/refill/
    # job-break state stays replicated and the job-mate re-mask below
    # sees every shard's verdicts.
    exhausted_l = loc(task_ok) & ~jnp.any(mask_l, axis=1)
    exhausted = _unpack_bits(
        lax.all_gather(_pack_bits(exhausted_l), axis), Tl
    ).reshape(T)
    failed = failed | (exhausted & (cand_total <= K) & ~fits_releasing)
    refill = refill | (exhausted & (cand_total > K))
    mask_l = mask_l & ~loc(blocked_of(failed) | refill)[:, None]

    # GLOBAL task/node ids in the hash bits — identical keys to the
    # single-device slab round, which is what makes the gathered bid
    # vector (and therefore every commit) bit-equal.
    key_l = _slab_keys(
        loc(task_req), task_ids_l, cand_nodes_l, cand_static_l,
        idle_slab, safe_l, node_cap, lr_weight, br_weight, mask_l,
    )

    commit_kw = dict(
        task_req=task_req, task_fit=task_fit,
        task_rank=task_rank, task_queue=task_queue,
        node_max_tasks=node_max_tasks,
        queue_deserved=queue_deserved, eps=eps,
    )

    def commit_once(_: jnp.ndarray, state: Tuple) -> Tuple:
        assigned, idle, ntask, qalloc, any_acc, key_l = state
        live_l = loc(assigned) < 0
        bid_col = jnp.argmax(key_l, axis=1).astype(jnp.int32)
        has_bid_l = live_l & (key_l[arange_l, bid_col] >= 0)
        # Delta-packed wire format: the slab COLUMN index (K = no bid),
        # one byte per task instead of a 4-byte node id — every shard
        # reconstructs the identical full bid vector from the
        # replicated slab.
        code_l = jnp.where(has_bid_l, bid_col, K).astype(code_dtype)
        assigned, idle, ntask, qalloc, acc = _commit_delta(
            axis, shard, code_l, cand_flat, cls, assigned, idle,
            ntask, qalloc, slab_k=K, **commit_kw
        )
        # Losers stop re-bidding the slab column they just lost this
        # round — each shard voids its own rows.
        lost_l = has_bid_l & (loc(assigned) < 0)
        col = jnp.where(has_bid_l, bid_col, 0)
        key_l = key_l.at[arange_l, col].set(
            jnp.where(lost_l, -1, key_l[arange_l, col])
        )
        return assigned, idle, ntask, qalloc, any_acc | acc, key_l

    assigned, idle, ntask, qalloc, any_accept, _ = lax.fori_loop(
        0, COMMITS_PER_ROUND, commit_once,
        (assigned, idle, ntask, qalloc, jnp.asarray(False), key_l),
    )
    return assigned, idle, ntask, qalloc, failed, refill, any_accept


def _solve_sparse_spmd_local(
    inputs: SolverInputs, *, axis, nshards, max_rounds, tail_bucket,
    two_level, rack_of_shard=None,
):
    """Per-shard body of the sharded sparse solve (runs under
    shard_map; every ``inputs`` field is a full replicated array). Task
    axis must be divisible by ``nshards`` (sharding.pad_tasks); for
    ``two_level`` the node axis must be too (sharding.pad_nodes).
    ``rack_of_shard`` is sharding.rack_perm's static shard→rack map
    (the two-level node-block ownership declared by
    contracts.TWO_LEVEL_RACK_DIMS); None = contiguous identity."""
    T, R = inputs.task_req.shape
    N = inputs.node_idle.shape[0]
    C, K = inputs.cand_idx.shape
    Tl = T // nshards
    shard = lax.axis_index(axis)
    t_off = shard * Tl
    eps = inputs.eps

    def loc(v: jnp.ndarray) -> jnp.ndarray:
        return lax.dynamic_slice_in_dim(v, t_off, Tl)

    # Class → task slab expansion, LOCAL rows only: the [T/s, K] block
    # is the largest structure this solver ever materializes per shard.
    cls = jnp.clip(inputs.task_cand, 0, C - 1)
    cls_l = loc(cls)
    cand_nodes_l = inputs.cand_idx[cls_l]                # i32[Tl, K]
    cand_static_l = inputs.cand_static[cls_l]            # f32[Tl, K]
    cand_total = inputs.cand_info[0][cls]                # i32[T]
    fits_releasing = inputs.cand_info[2][cls].astype(bool)

    def job_blocked(failed: jnp.ndarray) -> jnp.ndarray:
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
    round_kw = dict(
        axis=axis, shard=shard, t_off=t_off, n_local_tasks=Tl,
        task_req=inputs.task_req, task_fit=inputs.task_fit,
        task_rank=inputs.task_rank, task_queue=inputs.task_queue,
        task_valid=inputs.task_valid,
        cand_nodes_l=cand_nodes_l, cand_static_l=cand_static_l,
        cand_flat=inputs.cand_idx.ravel(), cls=cls,
        cand_total=cand_total,
        fits_releasing=fits_releasing, blocked_of=job_blocked,
        **shared_kw,
    )

    assigned = jnp.full((T,), -1, jnp.int32)
    idle = inputs.node_idle
    ntask = inputs.node_task_count
    qalloc = inputs.queue_allocated
    local_rounds = jnp.array(0, jnp.int32)

    if two_level:
        # ---- level 1: collective-free per-rack solve ------------------
        # Shard i owns rack ``rack_of_shard[i]``'s node rows
        # [r·N/s, (r+1)·N/s) — topology-aligned when the backend
        # exposes slice/ICI coordinates (sharding.rack_perm), the
        # contiguous identity otherwise — and a 1/s slice of every
        # queue's remaining headroom; the shard places its own task
        # block on its rack's candidate columns only. Disjoint node
        # ownership + sliced budgets make the psum reconcile below
        # exact; anything unplaced spills to the global drain.
        Nl = N // nshards
        if rack_of_shard is not None:
            rack_id = jnp.asarray(rack_of_shard, jnp.int32)[shard]
        else:
            rack_id = shard
        rack_lo = rack_id * Nl
        rack_hi = rack_lo + Nl
        headroom = inputs.queue_deserved - inputs.queue_allocated
        deserved_l = jnp.where(
            jnp.isinf(inputs.queue_deserved),
            inputs.queue_deserved,
            inputs.queue_allocated + headroom / nshards,
        )
        arange_l = jnp.arange(Tl, dtype=jnp.int32)
        req_l = loc(inputs.task_req)
        fit_l = loc(inputs.task_fit)
        rank_l = loc(inputs.task_rank)
        task_ids_l = rank_l
        queue_l = loc(inputs.task_queue)
        valid_task_l = loc(inputs.task_valid)
        in_rack = (cand_nodes_l >= rack_lo) & (cand_nodes_l < rack_hi)

        local_commit_kw = dict(
            task_req=req_l, task_fit=fit_l,
            task_rank=rank_l, task_queue=queue_l,
            node_max_tasks=inputs.node_max_tasks,
            queue_deserved=deserved_l, eps=eps,
        )

        def local_round(state: Tuple) -> Tuple:
            assigned_l, idle, ntask, qalloc, spill_l, _, rnd = state
            pending_l = assigned_l < 0
            q_over = less_equal(deserved_l, qalloc, eps)
            task_ok_l = (
                pending_l & valid_task_l & ~q_over[queue_l] & ~spill_l
            )
            mask_l, idle_slab, safe_l = _slab_mask(
                fit_l, idle, ntask, inputs.node_max_tasks,
                cand_nodes_l, in_rack, task_ok_l, eps,
            )
            # A rack-local exhaustion is a SPILL, never a job break:
            # the global drain holds the complete-slab evidence.
            spill_l = spill_l | (task_ok_l & ~jnp.any(mask_l, axis=1))
            key_l = _slab_keys(
                req_l, task_ids_l, cand_nodes_l, cand_static_l,
                idle_slab, safe_l, inputs.node_cap,
                inputs.lr_weight, inputs.br_weight, mask_l,
            )

            def commit_once(_: jnp.ndarray, cstate: Tuple) -> Tuple:
                assigned_l, idle, ntask, qalloc, any_acc, key_l = cstate
                live_l = assigned_l < 0
                bid_col = jnp.argmax(key_l, axis=1).astype(jnp.int32)
                has_bid = live_l & (key_l[arange_l, bid_col] >= 0)
                bid_l = jnp.where(
                    has_bid, cand_nodes_l[arange_l, bid_col], N
                )
                assigned_l, idle, ntask, qalloc, acc = _commit_bids(
                    bid_l, assigned_l, idle, ntask, qalloc,
                    **local_commit_kw,
                )
                lost = has_bid & (assigned_l < 0)
                col = jnp.where(has_bid, bid_col, 0)
                key_l = key_l.at[arange_l, col].set(
                    jnp.where(lost, -1, key_l[arange_l, col])
                )
                return assigned_l, idle, ntask, qalloc, any_acc | acc, key_l

            assigned_l, idle, ntask, qalloc, any_acc, _ = lax.fori_loop(
                0, COMMITS_PER_ROUND, commit_once,
                (
                    assigned_l, idle, ntask, qalloc, jnp.asarray(False),
                    key_l,
                ),
            )
            return (
                assigned_l, idle, ntask, qalloc, spill_l, any_acc,
                rnd + 1,
            )

        def local_cond(state: Tuple) -> jnp.ndarray:
            return state[5] & (state[6] < max_rounds)

        (
            assigned_l, idle_L, ntask_L, qalloc_L, _, _, lrnd
        ) = lax.while_loop(
            local_cond, local_round,
            (
                jnp.full((Tl,), -1, jnp.int32), idle, ntask, qalloc,
                jnp.zeros((Tl,), bool), jnp.array(True),
                jnp.array(0, jnp.int32),
            ),
        )

        # ---- reconcile: exact psum merge of the disjoint deltas -------
        assigned = lax.all_gather(assigned_l, axis).reshape(T)
        idle = idle + lax.psum(idle_L - idle, axis)
        ntask = ntask + lax.psum(ntask_L - ntask, axis)
        qalloc = qalloc + lax.psum(qalloc_L - qalloc, axis)
        local_rounds = lax.pmax(lrnd, axis)

    # ---- flat sharded rounds to a fixed point -------------------------
    # (two-level enters here as the global reconciliation drain: spilled
    # tasks re-bid their FULL slabs against the merged state.)
    def body(state: Tuple) -> Tuple:
        assigned, idle, ntask, qalloc, failed, refill, _, rnd = state
        (
            assigned, idle, ntask, qalloc, failed, refill, any_accept
        ) = _spmd_sparse_round(
            assigned, idle, ntask, qalloc, failed, refill, **round_kw
        )
        return (
            assigned, idle, ntask, qalloc, failed, refill, any_accept,
            rnd + 1,
        )

    def cond(state: Tuple) -> jnp.ndarray:
        return state[6] & (state[7] < max_rounds)

    (
        assigned, idle, ntask, qalloc, failed, refill, _, grounds
    ) = lax.while_loop(
        cond, body,
        (
            assigned, idle, ntask, qalloc,
            jnp.zeros((T,), bool), jnp.zeros((T,), bool),
            jnp.array(True), jnp.array(0, jnp.int32),
        ),
    )
    refills = jnp.sum(refill.astype(jnp.int32))
    rounds = local_rounds + grounds

    # ---- refill / drain: the SHARED compacted dense stage -------------
    # Same `_dense_tail` the single-device sparse solve drains through,
    # on the replicated full inputs — run on shard 0 and broadcast
    # (same rationale as the commit: replicated tail compute is free on
    # parallel chips, s× wall time on an emulated mesh).
    Q = qalloc.shape[0]

    def do_tail(_: None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        (
            a, i, _nt, q, _f, rr, st
        ) = _dense_tail(
            inputs, assigned, idle, ntask, qalloc, failed, rounds,
            fits_releasing=fits_releasing, job_blocked=job_blocked,
            shared_kw=shared_kw, max_rounds=max_rounds,
            tail_bucket=tail_bucket,
        )
        return (
            jnp.concatenate([a, jnp.stack([rr, st])]),
            jnp.concatenate([i.ravel(), q.ravel()]),
        )

    def skip_tail(_: None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return (
            jnp.zeros((T + 2,), jnp.int32),
            jnp.zeros((N * R + Q * R,), jnp.float32),
        )

    ibuf, fbuf = lax.psum(
        lax.cond(shard == 0, do_tail, skip_tail, None), axis
    )
    assigned = ibuf[:T]
    rounds = ibuf[T]
    stages = ibuf[T + 1]
    idle = fbuf[: N * R].reshape(N, R)
    qalloc = fbuf[N * R:].reshape(Q, R)
    return SolverResult(
        assigned, idle, qalloc, rounds, stages, refills,
        reconcile_rounds=grounds,
    )


@functools.lru_cache(maxsize=32)
def _spmd_sparse_step(mesh: Mesh, max_rounds, tail_bucket, two_level):
    """Jitted shard_map SPARSE solve for a mesh (cached per config;
    weakref-registered in the retrace census like every sharded
    step)."""
    axis = mesh.axis_names[0]
    nshards = mesh.size
    # Static per-mesh shard→rack ownership (topology-aligned when the
    # backend exposes coordinates). Lazy import: sharding.py imports
    # this module inside functions only.
    rack_of_shard = None
    if two_level:
        from .sharding import rack_perm

        perm = rack_perm(mesh)
        if any(int(perm[i]) != i for i in range(len(perm))):
            rack_of_shard = tuple(int(r) for r in perm)

    # Named for what it is: its XLA module, ``jit_solve_sparse_sharded``,
    # is what a device trace finds among the solve's modules.
    def solve_sparse_sharded(inputs: Any) -> SolverResult:
        if isinstance(inputs, PackedInputs):
            inputs = inputs.unpack()  # inside jit: free slicing
        in_specs = SolverInputs(**{
            f: (None if getattr(inputs, f, None) is None else P())
            for f in SolverInputs._fields
        })
        fn = shard_map(
            functools.partial(
                _solve_sparse_spmd_local,
                axis=axis,
                nshards=nshards,
                max_rounds=max_rounds,
                tail_bucket=tail_bucket,
                two_level=two_level,
                rack_of_shard=rack_of_shard,
            ),
            mesh=mesh,
            in_specs=(in_specs,),
            out_specs=P(),
            # Outputs are replicated by construction (every carry is
            # either gathered or psum-broadcast); the static checker
            # cannot see through the while_loop carries.
            check_vma=False,
        )
        return fn(inputs)

    import weakref

    step = jax.jit(solve_sparse_sharded)
    _jitted_steps.append(weakref.ref(step))
    return step


# Byte accounting of the LAST sparse sharded solve's commit collective
# (static shape arithmetic, set eagerly per dispatch — the jit itself
# never sees it). Keys: commit_bytes_exchanged (delta-packed, per
# commit), commit_bytes_full_broadcast (the legacy full-state psum it
# replaced), commit_bytes_per_round.
last_commit_stats: Dict[str, int] = {}


def solve_sparse_spmd(
    inputs: Any,
    mesh: Mesh,
    max_rounds: int = 256,
    tail_bucket: int = 3072,
    two_level: bool = False,
) -> SolverResult:
    """Run the candidate-sparsified solve with slab rows sharded over
    ``mesh``. Flat mode (default) is bit-equal to the single-device
    :func:`kernels.solve_sparse`; ``two_level`` runs the Tesserae-style
    per-rack solve + global reconciliation (quality-approximate,
    invariant-exact). Task axis must be divisible by ``mesh.size``
    (sharding.pad_tasks), and the node axis too for ``two_level``."""
    note_commit_stats(inputs)
    return _spmd_sparse_step(
        mesh, max_rounds, tail_bucket, bool(two_level)
    )(inputs)


def note_commit_stats(inputs: Any) -> None:
    """Record the commit collective's static byte accounting for this
    dispatch into ``last_commit_stats`` (eager shape arithmetic — the
    traced solve never sees it)."""
    if isinstance(inputs, PackedInputs):
        T, R = inputs.task_f32.shape[1], inputs.task_f32.shape[2]
        N = inputs.node_f32.shape[1]
        Q = inputs.queue_f32.shape[1]
    else:
        T, R = inputs.task_req.shape
        N = inputs.node_idle.shape[0]
        Q = inputs.queue_deserved.shape[0]
    K = inputs.cand_idx.shape[1] if inputs.cand_idx is not None else 0
    last_commit_stats.clear()
    last_commit_stats.update(
        commit_exchange_bytes(int(T), int(N), int(Q), int(R), max(int(K), 1))
    )
