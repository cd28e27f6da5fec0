"""Phase 1 of the candidate-sparsified solve: top-K node selection.

At 50k tasks x 5k nodes every dense solver structure is [T, N] — a f32
score matrix alone is ~1 GB — which caps scale far short of the 200k x
20k shapes the roadmap targets (~16 GB, infeasible). But the bid/commit
dynamics only ever LAND a task on one of a handful of best-scoring
feasible nodes (Tesserae's placement policies, PAPERS.md: candidate sets
of a few dozen nodes preserve placement quality; CvxCluster gets its
100-1000x from exactly this granularity structure). So one cheap fused
pass here — host-side NumPy, at snapshot time — scores every candidate
CLASS against the snapshot's initial idle state and keeps its top-K
candidate nodes; the solver's rounds then run on gathered [T, K] slabs
(kernels._sparse_round / native greedy_allocate_sparse).

A candidate CLASS dedups tasks that provably share a score surface:
same predicate feasibility group, same req/fit rows, and no private
pair/score rows (tasks WITH private rows become singleton classes that
keep their rows). Gang members instantiated from one pod template all
land in one class, so selection work scales with the number of DISTINCT
task shapes (dozens to hundreds), not tasks.

Selection eligibility is ``feasible AND fits-at-initial-idle AND
pod-count-capacity-open``: idle only shrinks and pod counts only grow
during a solve, so a node outside that set can NEVER accept the class's
tasks — which yields the solver's exactness invariant: a class whose
eligible set has <= K nodes gets a COMPLETE slab (``cand_info[0]``,
the refill gauge), and slab exhaustion for it is bit-identical to the
dense solver's no-fit verdict. Truncated classes route exhausted tasks
to the refill stage instead (kernels._dense_tail), never to a false
job break.

Whether a snapshot sparsifies, and its K, is the solve plan's call
(solver/plan.py).
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

from .kernels import (
    _KEY_BIAS,
    _KEY_HASH_BITS,
    CPU_DIM,
    MAX_PRIORITY,
    MEM_DIM,
    SCORE_QUANTUM,
)

# Selection itself costs O(C * N); if class dedup degenerates (every
# task a distinct shape) that approaches the dense pass it is meant to
# replace, so the policy falls back to dense past this budget.
_CLASS_BUDGET_FACTOR = 4

# Deterministic top-K tie rule, shared with the device path
# (solver/select_device.py): larger key first, equal keys -> smaller
# node id. The host realizes it by partitioning on an int64 composite
# ``(skey << 31) + (2^31-1 - node_id)`` (skey tops out below 2^30, so
# the composite never overflows and ineligible rows stay negative);
# the device gets the identical rule for free from ``lax.top_k``'s
# lower-index-first preference. Without this, argpartition's choice at
# the k-th boundary was unspecified on quantized-score ties.
_TIE_BITS = 31


def _pow2(n: int) -> int:
    if n <= 0:
        return 1
    return 1 << (n - 1).bit_length()


@dataclass
class CandidateSet:
    """Selection output, pre-padding (node sentinel = N unpadded)."""

    task_cand: np.ndarray    # i32[T] class id per task
    cand_idx: np.ndarray     # i32[C, K] candidate node ids ascending
    cand_static: np.ndarray  # f32[C, K] static score slab
    cand_info: np.ndarray    # i32[3, C] total / any_feas / fits_releasing
    stats: dict


def _sel_hash(c_ids: np.ndarray, n_ids: np.ndarray) -> np.ndarray:
    """Decorrelated per-(class, node) hash in [0, 1024) — the selection
    analog of kernels._bid_hash. Spreads equal-scored classes across
    DIFFERENT slabs so a homogeneous cluster does not herd every class
    onto the same K nodes (the selection-level form of the bid-key
    tie-break rationale)."""
    x = (c_ids.astype(np.uint32) * np.uint32(2654435761)) ^ (
        n_ids.astype(np.uint32) * np.uint32(0x9E3779B9)
    )
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(2246822519)
    return (
        (x >> np.uint32(8)) & np.uint32((1 << _KEY_HASH_BITS) - 1)
    ).astype(np.int64)


def _dyn_score_np(req, idle, cap, lr_w, br_w):
    """[C, N] LeastRequested + Balanced in f32 NumPy — the selection
    mirror of kernels._dyn_score_core (selection quality only; kernel
    rounds rescore against evolving idle on-device). Written as 2-D
    per-dimension passes: the [C, N, 2] broadcast temporaries were most
    of the selection pass's cost at warm steady-cycle shapes (small C,
    large N)."""
    ten = np.float32(MAX_PRIORITY)
    lr_acc = None
    fracs = []
    over = None
    for d in (CPU_DIM, MEM_DIM):
        req_d = req[:, d:d + 1].astype(np.float32)        # [C, 1]
        idle_d = idle[None, :, d].astype(np.float32)      # [1, N]
        cap_d = cap[None, :, d].astype(np.float32)
        pos = cap_d > 0
        safe_cap = np.where(pos, cap_d, np.float32(1.0))
        remaining = idle_d - req_d                        # [C, N]
        lr = np.where(
            pos, np.maximum(remaining, 0.0) * ten / safe_cap,
            np.float32(0.0),
        )
        lr_acc = lr if lr_acc is None else lr_acc + lr
        frac = np.where(pos, 1.0 - remaining / safe_cap, np.float32(1.0))
        fracs.append(frac)
        o = frac >= 1.0
        over = o if over is None else (over | o)
    lr_score = lr_acc * np.float32(0.5)
    diff = np.abs(fracs[0] - fracs[1])
    br_score = np.where(over, np.float32(0.0), ten - diff * ten)
    return (
        np.float32(lr_w) * lr_score + np.float32(br_w) * br_score
    ).astype(np.float32)


class _SelectionCache:
    """Cross-cycle per-class selection-key rows (stored on the
    scheduler cache as ``_topk_sel_cache``).

    A class's [N] integer key row is a pure function of (its feasibility
    row, its req/fit rows, per-node idle/cap/count/max, eps, weights,
    its class index). The feas/req/fit inputs are content-addressed by
    digest; the node inputs by the shared node scan's (identity, _ver)
    fingerprint — so a warm steady cycle recomputes each cached row
    only at the columns whose node actually changed (the placement
    wave), O(C·churn) instead of O(C·N). Any drift — new class shapes,
    changed weights, an unfingerprintable call — misses to the exact
    full computation, so cached and fresh selections are bit-identical
    by construction."""

    __slots__ = ("sig", "node_objs", "node_ids", "node_vers", "rows",
                 "dedup_key", "dedup")

    def __init__(self):
        self.sig = None
        # The fingerprinted node objects are PINNED here (like
        # _TensorizeCache.node_objs): a pinned object's id can never be
        # recycled under a new clone, so the id array stays an exact
        # identity witness even across cycles where selection is
        # skipped (warm-noop, dense-path, deferred micro) and the
        # previous clones would otherwise be freed.
        self.node_objs = None
        self.node_ids = None
        self.node_vers = None
        self.rows: Dict[tuple, np.ndarray] = {}
        # Content-addressed class dedup: digest of the [T, 2+2R] key
        # matrix -> its np.unique decomposition. The lexsort behind
        # np.unique(axis=0) is O(T log T) over 6 columns (seconds at
        # 1M tasks) while a steady cycle's task CONTENT rarely moves —
        # node churn never touches it. An exact digest hit replays the
        # identical (rep_idx, task_cand); any content change misses to
        # the full unique.
        self.dedup_key = None
        self.dedup = None


def _sel_cache_of(holder) -> Optional[_SelectionCache]:
    if holder is None:
        return None
    sc = getattr(holder, "_topk_sel_cache", None)
    if sc is None:
        sc = _SelectionCache()
        try:
            holder._topk_sel_cache = sc
        except Exception:
            return None
    return sc


def _skey_block(req_rows, fit_rows, class_ids, cols,
                idle32, cap32, eps32, cap_ok0, feas_cols,
                lr_w, br_w):
    """Integer selection keys for ``class_ids`` × ``cols`` (global node
    indexes): eligibility-masked quantized score + class/node hash —
    exactly the full pass's math on a column subset (elementwise ops
    only, so subset and full computation are bit-identical)."""
    R = req_rows.shape[1]
    idle_c = idle32[cols]                              # [M, R]
    cap_c = cap32[cols]
    fit_ok = np.ones((req_rows.shape[0], len(cols)), dtype=bool)
    for d in range(R):
        fit_ok &= fit_rows[:, d:d + 1] - idle_c[None, :, d] < eps32[d]
    elig = feas_cols & fit_ok & cap_ok0[cols][None, :]
    score = _dyn_score_np(req_rows, idle_c, cap_c, lr_w, br_w)
    q = np.clip(
        np.round(score / np.float32(SCORE_QUANTUM)).astype(np.int64)
        + _KEY_BIAS,
        0, (1 << 20) - 1,
    )
    skey = (q << _KEY_HASH_BITS) | _sel_hash(
        np.asarray(class_ids, np.int64)[:, None],
        np.asarray(cols, np.int64)[None, :],
    )
    return np.where(elig, skey, -1)


def _skey_priv_row(req_row, fit_row, class_id,
                   idle32, cap32, eps32, cap_ok0, feas_row, srow,
                   lr_w, br_w):
    """One class's key row with its private static score row folded in
    before quantization — the dense ``dynamic + static`` chain."""
    R = req_row.shape[1]
    N = idle32.shape[0]
    fit_ok = np.ones((1, N), dtype=bool)
    for d in range(R):
        fit_ok &= fit_row[:, d:d + 1] - idle32[None, :, d] < eps32[d]
    elig = feas_row & fit_ok & cap_ok0[None, :]
    score = _dyn_score_np(req_row, idle32, cap32, lr_w, br_w) + srow
    q = np.clip(
        np.round(score / np.float32(SCORE_QUANTUM)).astype(np.int64)
        + _KEY_BIAS,
        0, (1 << 20) - 1,
    )
    skey = (q << _KEY_HASH_BITS) | _sel_hash(
        np.asarray([class_id], np.int64)[:, None],
        np.arange(N, dtype=np.int64)[None, :],
    )
    return np.where(elig, skey, -1)[0]


def select_candidates(
    mask: "CombinedMask",         # masks.CombinedMask (unpadded)
    score_rows_map: Dict[int, np.ndarray],
    task_req: np.ndarray,         # f32[T, R] rank-ordered
    task_fit: np.ndarray,         # f32[T, R]
    node_idle: np.ndarray,        # [N, R]
    node_cap: np.ndarray,         # [N, R]
    node_releasing: np.ndarray,   # [N, R]
    node_task_count: np.ndarray,  # i32[N]
    node_max_tasks: np.ndarray,   # i32[N]
    eps: np.ndarray,              # [R]
    lr_weight: float,
    br_weight: float,
    k: int,
    cache_holder: Optional[object] = None,
    # (ids i64[N], vers i64[N], [NodeInfo] pins) or None
    node_fp: Optional[tuple] = None,
    # select_device.SelectionDeviceState or None
    device_state: Optional["SelectionDeviceState"] = None,
    # the solve plan's sel_token (cache signature)
    layout_token: Optional[str] = None,
) -> Optional[CandidateSet]:
    """Run the fused feasibility + static-score selection pass.

    Returns None (→ dense solve, with the reason in the caller's stats)
    when class dedup degenerates past the selection budget."""
    T, R = task_req.shape
    N = node_idle.shape[0]
    k = min(_pow2(k), _pow2(N))

    # ---- class dedup: (feasibility group, private-row id, req, fit) ----
    priv = np.full(T, -1, np.int64)
    if len(mask.pair_idx):
        priv[mask.pair_idx] = mask.pair_idx
    if score_rows_map:
        for i in score_rows_map:
            priv[int(i)] = int(i)
    # Exact float32 keys: group/priv ids stay < 2^24 (tasks per snapshot
    # are far below that), req/fit are already f32 rows.
    key_mat = np.column_stack([
        mask.task_group.astype(np.float32),
        priv.astype(np.float32),
        task_req.astype(np.float32),
        task_fit.astype(np.float32),
    ])
    sc0 = _sel_cache_of(cache_holder)
    dedup_key = None
    if sc0 is not None:
        dedup_key = hashlib.blake2b(
            key_mat.tobytes(), digest_size=16
        ).digest()
    if sc0 is not None and sc0.dedup_key == dedup_key:
        rep_idx, task_cand = sc0.dedup
    else:
        _, rep_idx, task_cand = np.unique(
            key_mat, axis=0, return_index=True, return_inverse=True
        )
        task_cand = task_cand.reshape(-1).astype(np.int32)
        rep_idx = rep_idx.astype(np.int64)
        if sc0 is not None:
            sc0.dedup_key = dedup_key
            sc0.dedup = (rep_idx, task_cand)
    C = len(rep_idx)
    if C * N > max(_CLASS_BUDGET_FACTOR * T * k, 1 << 22):
        return None

    idle32 = np.ascontiguousarray(node_idle, np.float32)
    cap32 = np.ascontiguousarray(node_cap, np.float32)
    eps32 = np.asarray(eps, np.float32)
    cap_ok0 = (node_max_tasks == 0) | (node_task_count < node_max_tasks)
    has_releasing = bool(np.asarray(node_releasing).any())
    rel32 = (
        np.ascontiguousarray(node_releasing, np.float32)
        if has_releasing else None
    )
    rep_fit = task_fit[rep_idx].astype(np.float32)
    rep_req = task_req[rep_idx].astype(np.float32)
    rep_priv = priv[rep_idx]

    cand_idx = np.full((C, k), N, np.int32)
    cand_static = np.zeros((C, k), np.float32)
    cand_info = np.zeros((3, C), np.int32)

    def _mk_stats(cache_hits_, extra):
        slab_bytes = (
            cand_idx.nbytes + cand_static.nbytes + cand_info.nbytes
            + task_cand.nbytes
        )
        stats = {
            "classes": int(C),
            "k": int(k),
            "slab_bytes": int(slab_bytes),
            # What the dense path would materialize per round on device:
            # the [T, N] bool mask and f32 score/key matrices.
            "dense_mask_bytes": int(T) * int(N),
            "dense_score_bytes": int(T) * int(N) * 4,
            "truncated_classes": int((cand_info[0] > k).sum()),
            # Cross-cycle selection-cache effectiveness (classes whose
            # key rows were reused with only churned columns recomputed).
            "sel_cache_hits": int(cache_hits_),
        }
        stats.update(extra)
        return stats

    # --- device-resident selection (solver/select_device.py) ------------
    # Scores, key rows, and the top-K extraction run on the accelerator
    # against the resident node stacks; everything below this branch is
    # the host path, which stays bit-equal by construction and serves
    # as the labeled fallback.
    dev_res = None
    select_path = "host"
    if device_state is not None:
        from .select_device import device_select_enabled, select_rows

        if not device_select_enabled():
            select_path = "host:env-disabled"
        elif has_releasing:
            select_path = "host:releasing"
        else:
            dev_res = select_rows(
                device_state, mask, rep_idx, rep_req, rep_fit, rep_priv,
                score_rows_map, idle32, cap32, eps32, cap_ok0,
                lr_weight, br_weight, k, N, node_fp=node_fp,
                layout_token=layout_token,
            )
            select_path = (
                "device" if dev_res is not None
                else "host:device-unavailable"
            )
    if dev_res is not None:
        cand_idx = dev_res["cand_idx"]
        cand_info[0] = np.minimum(
            dev_res["elig_count"], np.iinfo(np.int32).max
        )
        cand_info[1] = dev_res["any_feas"]
        # Private static rows ride the slab exactly like the host path.
        for ci in np.nonzero(rep_priv >= 0)[0]:
            p = int(rep_priv[ci])
            if p not in score_rows_map:
                continue
            srow = np.asarray(score_rows_map[p], np.float32)
            row = cand_idx[ci]
            sel = row < N
            cand_static[ci, sel] = srow[row[sel]]
        try:
            from .. import metrics

            metrics.register_device_selection()
        except Exception:  # pragma: no cover - metrics must never kill
            pass
        stats = _mk_stats(dev_res["cache_hits"], {
            "select_path": select_path,
            "sel_rows_rebuilt": int(dev_res["rows_rebuilt"]),
            "sel_cols_patched": int(dev_res["cols_patched"]),
        })
        return CandidateSet(
            task_cand, cand_idx, cand_static, cand_info, stats
        )

    # Cross-cycle key-row cache (see _SelectionCache): usable only when
    # the caller provided a node fingerprint and the cluster holds no
    # Releasing capacity (the releasing column is not cached).
    sc = _sel_cache_of(cache_holder) if node_fp is not None else None
    changed_cols = None
    sig = (N, int(k), R, eps32.tobytes(),
           float(lr_weight), float(br_weight), layout_token)
    if sc is not None and not has_releasing:
        ids, vers, node_objs = node_fp
        if (
            sc.sig == sig
            and sc.node_ids is not None
            and len(sc.node_ids) == N
        ):
            changed_cols = np.nonzero(
                (ids != sc.node_ids) | (vers != sc.node_vers)
            )[0]
        else:
            sc.rows = {}
            changed_cols = None
        sc.sig = sig
        sc.node_objs = node_objs
        sc.node_ids = ids
        sc.node_vers = vers
    elif sc is not None:
        sc.rows = {}
        sc.node_objs = None
        sc.node_ids = None

    node_ids = np.arange(N, dtype=np.int64)
    # Composite tie term (see _TIE_BITS): smaller node id -> larger
    # low bits, so equal-skey boundary picks match lax.top_k's.
    tie_lo = (np.int64(1) << _TIE_BITS) - 1 - node_ids
    new_rows: Dict[tuple, np.ndarray] = {}
    cache_hits = 0
    chunk = max(1, min(C, (1 << 22) // max(N, 1)))
    for c0 in range(0, C, chunk):
        c1 = min(c0 + chunk, C)
        rows = c1 - c0
        feas = mask.rows_for(rep_idx[c0:c1])                 # [rows, N]
        fit_chunk = rep_fit[c0:c1]
        req_chunk = rep_req[c0:c1]

        # Per-class cache resolution: digest the content inputs, reuse
        # the cached key row with only the changed columns recomputed.
        skey = None
        row_keys = {}
        misses = list(range(rows))
        if sc is not None and not has_releasing:
            skey = np.empty((rows, N), dtype=np.int64)
            misses = []
            hit_locals = []
            for local in range(rows):
                ci = c0 + local
                if rep_priv[ci] >= 0:
                    misses.append(local)  # private rows: never cached
                    continue
                key = (ci, hashlib.blake2b(
                    feas[local].tobytes()
                    + fit_chunk[local].tobytes()
                    + req_chunk[local].tobytes(),
                    digest_size=16,
                ).digest())
                row_keys[local] = key
                row = (
                    sc.rows.get(key) if changed_cols is not None else None
                )
                if row is None:
                    misses.append(local)
                    continue
                skey[local] = row
                hit_locals.append(local)
            if hit_locals and changed_cols is not None and len(changed_cols):
                sub = _skey_block(
                    req_chunk[hit_locals], fit_chunk[hit_locals],
                    [c0 + lo for lo in hit_locals], changed_cols,
                    idle32, cap32, eps32, cap_ok0,
                    feas[hit_locals][:, changed_cols],
                    lr_weight, br_weight,
                )
                for i, local in enumerate(hit_locals):
                    skey[local][changed_cols] = sub[i]
            cache_hits += len(hit_locals)

        # Singleton classes keep their private static score rows — the
        # slab ships the gathered values so the kernel adds them exactly
        # like the dense `dynamic + static` chain. Their key rows fold
        # the addend into the score before quantization (never cached),
        # computed individually so the bulk block never computes them
        # twice.
        srows = {}
        if misses:
            if skey is None:
                skey = np.empty((rows, N), dtype=np.int64)
            priv_misses = []
            plain = []
            for local in misses:
                p = int(rep_priv[c0 + local])
                if p >= 0 and p in score_rows_map:
                    priv_misses.append((local, p))
                else:
                    plain.append(local)
            if plain:
                # Full computation for the plain miss rows — identical
                # math to the cached path (elementwise ops on the full
                # column set).
                full = _skey_block(
                    req_chunk[plain], fit_chunk[plain],
                    [c0 + lo for lo in plain], node_ids,
                    idle32, cap32, eps32, cap_ok0,
                    feas[plain],
                    lr_weight, br_weight,
                )
                for i, local in enumerate(plain):
                    skey[local] = full[i]
            for local, p in priv_misses:
                srow = np.asarray(score_rows_map[p], np.float32)
                srows[local] = srow
                skey[local] = _skey_priv_row(
                    req_chunk[local:local + 1],
                    fit_chunk[local:local + 1], c0 + local,
                    idle32, cap32, eps32, cap_ok0,
                    feas[local:local + 1], srow,
                    lr_weight, br_weight,
                )

        for local, key in row_keys.items():
            new_rows[key] = skey[local].copy()

        elig_count = (skey >= 0).sum(axis=1)
        cand_info[0, c0:c1] = np.minimum(
            elig_count, np.iinfo(np.int32).max
        )
        cand_info[1, c0:c1] = (feas & cap_ok0[None, :]).any(axis=1)
        if has_releasing:
            rel_ok = np.ones((rows, N), dtype=bool)
            for d in range(R):
                rel_ok &= (
                    fit_chunk[:, d:d + 1] - rel32[None, :, d] < eps32[d]
                )
            cand_info[2, c0:c1] = (rel_ok & feas).any(axis=1)

        if k < N:
            skey2 = (skey << _TIE_BITS) + tie_lo[None, :]
            part = np.argpartition(skey2, N - k, axis=1)[:, N - k:]
            pkey = np.take_along_axis(skey2, part, axis=1)
        else:
            part = np.broadcast_to(node_ids[None, :], (rows, N)).copy()
            pkey = np.take_along_axis(skey, part, axis=1)
        part = part.astype(np.int32)
        part[pkey < 0] = N           # ineligible picks → sentinel
        part.sort(axis=1)            # ascending node id, sentinels last
        cand_idx[c0:c1, : part.shape[1]] = part[:, :k]
        for local, srow in srows.items():
            row = cand_idx[c0 + local]
            sel = row < N
            cand_static[c0 + local, sel] = srow[row[sel]]

    if sc is not None and not has_releasing:
        sc.rows = {
            key: row for key, row in new_rows.items() if row is not None
        }

    stats = _mk_stats(cache_hits, {"select_path": select_path})
    return CandidateSet(task_cand, cand_idx, cand_static, cand_info, stats)
