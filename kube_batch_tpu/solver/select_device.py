"""Device-resident top-K candidate selection (tentpole of PR 16).

``solver/topk.py`` runs phase 1 of the sparse solve — per-class scoring
plus top-K extraction over the [C, N] key matrix — in host NumPy. That
pass is exact and cache-friendly, but at the roadmap's XL shapes
(hundreds of classes against 10^5..10^6 nodes) the host argpartition
and the f32 scoring sweeps dominate the cycle (~26 s at the 1M x 100k
bench point) while the accelerator sits idle between solves. This
module moves the arithmetic onto the device while keeping the HOST
path's bits:

- the integer key rows are computed by a jnp mirror of
  ``topk._skey_block`` that is **bit-equal** to the NumPy original
  (see ``_guard``: XLA's default fp-contraction would otherwise fuse
  ``a*b + c`` into an FMA and drift the f32 scores by 1 ulp);
- the resident [Cp, Np] key matrix reuses ``_SelectionCache``'s
  content-addressing verbatim — per-class blake2b digests over
  (feas, fit, req) plus the node scan's (id, version) fingerprints —
  so a warm steady cycle recomputes only churned columns and missed
  rows on device, O(C·churn) instead of O(C·N), with the same
  hit/miss decisions the host cache would make;
- node state is never re-uploaded for selection: the engine reads the
  device-resident ``PackedInputs`` stacks (``node_f32``/``node_i32``/
  ``group_feas``) that ``device_cache.pack_partial`` placed ahead of
  the selection pass, so per-cycle host->device traffic is the per-class
  req/fit rows and the churned column index vector;
- top-K extraction is a single ``lax.top_k`` + ascending-id sort whose
  selected SET matches the host composite-key argpartition exactly
  (both prefer the smaller node id on quantized-score ties), and the
  key matrix shards over the class axis when the mesh divides it.

``KBT_SELECT_DEVICE`` is the off-switch (``0``/``off``/``host``):
selection then takes the labeled host fallback. Releasing capacity
also routes host-side (the releasing column is not resident-cacheable,
same rule as the host selection cache).
"""

from __future__ import annotations

import functools
import hashlib
import os
from typing import Dict, Optional

import numpy as np

from ..utils.lockdebug import wrap_lock
from .kernels import (
    _KEY_BIAS,
    _KEY_HASH_BITS,
    CPU_DIM,
    MAX_PRIORITY,
    MEM_DIM,
    SCORE_QUANTUM,
)

# Per-chunk cell cap for the miss-row rebuild (i32 keys + f32 score
# temporaries stay ~100s of MB at the XL shapes).
_MISS_CHUNK_CELLS = 1 << 24

SELECT_DEVICE_ENV = "KBT_SELECT_DEVICE"

# (kk, sentinel) / row-bucket variants minted so far, for the retrace
# census (kernels.jit_compilation_count) — same pattern as
# device_cache._patch_axes_used.
_minted_topk: set = set()
_minted_rows: set = set()
_minted_cols: set = set()
_minted_lock = wrap_lock("solver.select_device.minted")


def device_select_enabled() -> bool:
    """Resolve the ``KBT_SELECT_DEVICE`` gate (default: enabled — the
    device path is bit-equal to the host path by construction, so the
    switch exists for forensics and fallback, not correctness)."""
    raw = os.environ.get(SELECT_DEVICE_ENV, "").strip().lower()
    return raw not in ("0", "off", "host", "disable", "disabled", "false")


def _pow2(n: int) -> int:
    if n <= 0:
        return 1
    return 1 << (n - 1).bit_length()


# ---------------------------------------------------------------------------
# Bit-exact jnp mirror of the host scoring/key math (topk._skey_block).
# ---------------------------------------------------------------------------


def _guard(x):
    """Block backend mul-add contraction: wrap a product that feeds an
    add/sub in a runtime select, so the adder's operand is a select
    result rather than a mul and XLA cannot fuse the pair into an FMA.
    The predicate is always true for the solver's finite scores; its
    only job is to be opaque at compile time. This is what keeps the
    device keys bit-equal to the NumPy mirror (pure IEEE f32 mul/add,
    no excess precision)."""
    import jax.numpy as jnp

    return jnp.where(jnp.isfinite(x), x, jnp.float32(0.0))


def _dyn_score_dev(req, idle, cap, lr_w, br_w):
    """jnp twin of ``topk._dyn_score_np`` — same per-dimension 2-D
    passes, same op order, f32 throughout; products feeding adds are
    ``_guard``-wrapped (see above) so the result is bit-equal."""
    import jax.numpy as jnp

    ten = jnp.float32(MAX_PRIORITY)
    lr_acc = None
    fracs = []
    over = None
    for d in (CPU_DIM, MEM_DIM):
        req_d = req[:, d:d + 1]                      # [B, 1]
        idle_d = idle[None, :, d]                    # [1, M]
        cap_d = cap[None, :, d]
        pos = cap_d > 0
        safe_cap = jnp.where(pos, cap_d, jnp.float32(1.0))
        remaining = idle_d - req_d                   # [B, M]
        lr = jnp.where(
            pos, jnp.maximum(remaining, 0.0) * ten / safe_cap,
            jnp.float32(0.0),
        )
        lr_acc = lr if lr_acc is None else lr_acc + lr
        frac = jnp.where(
            pos, jnp.float32(1.0) - remaining / safe_cap, jnp.float32(1.0)
        )
        fracs.append(frac)
        o = frac >= 1.0
        over = o if over is None else (over | o)
    lr_score = lr_acc * jnp.float32(0.5)
    diff = jnp.abs(fracs[0] - fracs[1])
    br_score = jnp.where(
        over, jnp.float32(0.0), ten - _guard(diff * ten)
    )
    return _guard(lr_w * lr_score) + _guard(br_w * br_score)


def _sel_hash_dev(c_ids, n_ids):
    """jnp twin of ``topk._sel_hash`` (uint32 mix, 10-bit output)."""
    import jax.numpy as jnp

    x = (c_ids.astype(jnp.uint32) * jnp.uint32(2654435761)) ^ (
        n_ids.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    )
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(2246822519)
    return (
        (x >> jnp.uint32(8)) & jnp.uint32((1 << _KEY_HASH_BITS) - 1)
    ).astype(jnp.int32)


def _skey_cells_dev(req, fit, class_ids, col_ids, feas_cols,
                    idle_c, cap_c, cap_ok_c, eps, lr_w, br_w):
    """Integer selection keys for a row block x column subset — the
    device twin of ``topk._skey_block`` (i32: q <= 2^20-1 shifted by
    10 hash bits tops out below 2^30)."""
    import jax.numpy as jnp

    R = req.shape[1]
    fit_ok = feas_cols & cap_ok_c[None, :]
    for d in range(R):
        fit_ok &= (fit[:, d:d + 1] - idle_c[None, :, d]) < eps[d]
    score = _dyn_score_dev(req, idle_c, cap_c, lr_w, br_w)
    q = jnp.clip(
        jnp.round(score / jnp.float32(SCORE_QUANTUM)).astype(jnp.int32)
        + jnp.int32(_KEY_BIAS),
        0, (1 << 20) - 1,
    )
    skey = (q << _KEY_HASH_BITS) | _sel_hash_dev(
        class_ids[:, None], col_ids[None, :]
    )
    return jnp.where(fit_ok, skey, jnp.int32(-1))


def _node_views(node_f32, node_i32):
    import jax.numpy as jnp

    idle = node_f32[0]
    cap = node_f32[2]
    cnt = node_i32[0]
    maxt = node_i32[1]
    nfeas = node_i32[2].astype(bool)
    cap_ok = (maxt == 0) | (cnt < maxt)
    del jnp
    return idle, cap, nfeas, cap_ok


@functools.lru_cache(maxsize=None)
def _miss_jit():
    """Jitted miss-row rebuild: compute full key rows for a (bucketed)
    class-row block against ALL resident node columns and scatter them
    into the donated resident key matrix (padded row ids point one past
    the end and drop)."""
    import jax

    def run(keys: jax.Array, rows: jax.Array, req: jax.Array,
            fit: jax.Array, class_ids: jax.Array, group_ids: jax.Array,
            node_f32: jax.Array, node_i32: jax.Array,
            group_feas: jax.Array, eps: jax.Array, lr_w: jax.Array,
            br_w: jax.Array) -> jax.Array:
        import jax.numpy as jnp

        idle, cap, nfeas, cap_ok = _node_views(node_f32, node_i32)
        Np = idle.shape[0]
        feas = group_feas[group_ids] & nfeas[None, :]
        cols = jnp.arange(Np, dtype=jnp.int32)
        block = _skey_cells_dev(
            req, fit, class_ids, cols, feas, idle, cap, cap_ok,
            eps, lr_w, br_w,
        )
        return keys.at[rows].set(block, mode="drop")

    return jax.jit(run, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _col_patch_jit():
    """Jitted churned-column patch: recompute EVERY resident row at a
    (bucketed) column subset and scatter along the node axis (padded
    column ids drop). Miss/private rows get garbage here and are fully
    overwritten by the subsequent scatters — order is col-patch ->
    miss rebuild -> private-row scatter."""
    import jax

    def run(keys: jax.Array, cols: jax.Array, req: jax.Array,
            fit: jax.Array, class_ids: jax.Array, group_ids: jax.Array,
            node_f32: jax.Array, node_i32: jax.Array,
            group_feas: jax.Array, eps: jax.Array, lr_w: jax.Array,
            br_w: jax.Array) -> jax.Array:
        import jax.numpy as jnp

        idle, cap, nfeas, cap_ok = _node_views(node_f32, node_i32)
        csafe = jnp.minimum(cols, idle.shape[0] - 1)
        # Column-slice the group table BEFORE the per-class gather so
        # the temporary is [G, M] + [Cp, M], never [Cp, Np].
        feas = group_feas[:, csafe][group_ids] & nfeas[csafe][None, :]
        block = _skey_cells_dev(
            req, fit, class_ids, cols, feas,
            idle[csafe], cap[csafe], cap_ok[csafe],
            eps, lr_w, br_w,
        )
        return keys.at[:, cols].set(block, mode="drop")

    return jax.jit(run, donate_argnums=(0,))


# Hierarchical-extraction block widths. XLA's CPU TopK (and Sort, on
# wide rows) lowers to a scalar per-row loop — ~0.3 us/element, which
# is 60+ s at [2048, 100000] — so the wide key matrix must never meet
# top_k/sort directly. Per-block max is a vectorized reduce and sorts
# of NARROW rows vectorize well, so extraction funnels through those.
# 256/64 measured best at [2048, 100000] (level-2 composite width
# kk·256 balances the level-1 top_k area against the i64 passes).
_EXTRACT_BLOCK1 = 256
_EXTRACT_BLOCK2 = 64


@functools.lru_cache(maxsize=None)
def _topk_jit(kk: int, sentinel: int):
    """Jitted exact top-K extraction over the resident key matrix,
    bit-equal to the host composite-key argpartition.

    Level 1 reduces M1-column blocks to their maxima and picks the top
    ``kk`` BLOCKS with lax.top_k on the tiny [Cp, B1] matrix. Blocks
    are contiguous column ranges and lax.top_k prefers the lower index
    on equal keys, so the selected blocks provably contain the exact
    composite-key top-kk: were an element's block displaced, every one
    of the >= kk displacing blocks would hold an element beating it on
    (skey, smaller-col) — greater max, or equal max in an
    all-smaller-column block. Level 2 gathers the survivors, switches
    to the host composite key ``(skey << _TIE_BITS) + (2^31-1 - col)``
    (unique per cell — the same argument with no tie care), and
    repeats with M2-column blocks. Level 3 sorts the narrow remnant,
    slices the top kk, decodes columns, maps ineligible picks (skey
    -1 -> negative composite) to the sentinel, and ascending-sorts —
    exactly the host epilogue."""
    import jax

    def run(keys: jax.Array) -> tuple:
        import jax.numpy as jnp
        from jax import lax

        from .topk import _TIE_BITS

        cp, np_ = keys.shape
        count = jnp.sum((keys >= 0).astype(jnp.int32), axis=1)

        m1, m2 = _EXTRACT_BLOCK1, _EXTRACT_BLOCK2
        b1 = -(-np_ // m1)
        kpad = jnp.pad(
            keys, ((0, 0), (0, b1 * m1 - np_)), constant_values=-1
        ).reshape(cp, b1, m1)
        p1 = min(kk, b1)
        _, blk1 = lax.top_k(jnp.max(kpad, axis=2), p1)
        rows = jnp.arange(cp, dtype=jnp.int32)[:, None]
        col1 = (
            blk1[:, :, None] * m1
            + jnp.arange(m1, dtype=jnp.int32)[None, None, :]
        )
        tie_lo = jnp.int64((1 << _TIE_BITS) - 1)
        comp = (
            kpad[rows, blk1].astype(jnp.int64)
            * jnp.int64(1 << _TIE_BITS)
            + (tie_lo - col1.astype(jnp.int64))
        ).reshape(cp, p1 * m1)
        b2 = (p1 * m1) // m2
        p2 = min(kk, b2)
        _, blk2 = lax.top_k(jnp.max(comp.reshape(cp, b2, m2), axis=2), p2)
        g2 = comp.reshape(cp, b2, m2)[rows, blk2].reshape(cp, p2 * m2)
        top = lax.slice_in_dim(
            jnp.sort(g2, axis=1), p2 * m2 - kk, p2 * m2, axis=1
        )
        col = (tie_lo - (top & tie_lo)).astype(jnp.int32)
        cand = jnp.where(top >= 0, col, jnp.int32(sentinel))
        return jnp.sort(cand, axis=1), count

    return jax.jit(run)


def jit_cache_size() -> int:
    """Compiled-variant count across the selection jits — one term of
    the retrace-regression census (kernels.jit_compilation_count)."""
    total = 0
    with _minted_lock:
        minted = bool(_minted_rows or _minted_cols), tuple(_minted_topk)
    has_rowcol, topks = minted
    fns = []
    if has_rowcol:
        fns += [_miss_jit(), _col_patch_jit()]
    fns += [_topk_jit(kk, s) for kk, s in topks]
    for fn in fns:
        try:
            total += fn._cache_size()
        except Exception:  # pragma: no cover - private-API drift
            pass
    return total


# ---------------------------------------------------------------------------
# Engine: resident key matrix + content-addressed row reuse.
# ---------------------------------------------------------------------------


class _DeviceTopKEngine:
    """Device-resident selection state, held on the scheduler cache as
    ``_topk_dev_engine`` (mirrors ``topk._SelectionCache`` exactly in
    its bookkeeping; the rows live on device instead of in a dict)."""

    __slots__ = (
        "sig", "keys", "cp", "row_digests",
        "node_objs", "node_ids", "node_vers",
    )

    def __init__(self):
        self.sig = None
        self.keys = None          # jax i32[Cp, Np] resident key matrix
        self.cp = 0
        self.row_digests: Dict[int, bytes] = {}
        # Node fingerprint pins — same identity-witness rationale as
        # _SelectionCache.node_objs.
        self.node_objs = None
        self.node_ids = None
        self.node_vers = None

    def invalidate(self) -> None:
        self.sig = None
        self.keys = None
        self.row_digests = {}
        self.node_objs = None
        self.node_ids = None
        self.node_vers = None


class SelectionDeviceState:
    """Per-cycle handle the snapshot passes into ``select_candidates``:
    the device-resident node stacks (placed by the early
    ``device_cache.pack_partial``) plus where the engine lives."""

    __slots__ = (
        "holder", "node_f32", "node_i32", "group_feas",
        "n_padded", "layout_token", "_engine",
    )

    def __init__(self, holder, node_f32, node_i32, group_feas,
                 n_padded: int, layout_token: Optional[str]):
        self.holder = holder
        self.node_f32 = node_f32
        self.node_i32 = node_i32
        self.group_feas = group_feas
        self.n_padded = int(n_padded)
        self.layout_token = layout_token
        self._engine = None

    def engine(self) -> _DeviceTopKEngine:
        if self.holder is not None:
            eng = getattr(self.holder, "_topk_dev_engine", None)
            if eng is None:
                eng = _DeviceTopKEngine()
                try:
                    self.holder._topk_dev_engine = eng
                except Exception:
                    self._engine = eng
                    return eng
            return eng
        # Cold standalone mode (bench): engine scoped to this state.
        if self._engine is None:
            self._engine = _DeviceTopKEngine()
        return self._engine


def standalone_state(node_idle: np.ndarray, node_cap: np.ndarray,
                     node_task_count: np.ndarray,
                     node_max_tasks: np.ndarray, node_feas: np.ndarray,
                     group_rows: np.ndarray,
                     n_padded: Optional[int] = None,
                     ) -> "SelectionDeviceState":
    """Build a :class:`SelectionDeviceState` from raw host arrays —
    cold bench/tool mode: uploads the node stacks itself instead of
    reusing device-cache residency."""
    import jax.numpy as jnp

    N = node_idle.shape[0]
    Np = int(n_padded) if n_padded else N

    def padn(a: np.ndarray, fill: int = 0) -> np.ndarray:
        if a.shape[0] == Np:
            return a
        out = np.full((Np,) + a.shape[1:], fill, dtype=a.dtype)
        out[:N] = a
        return out

    node_f32 = jnp.asarray(np.stack([
        padn(np.ascontiguousarray(node_idle, np.float32)),
        np.zeros((Np,) + node_idle.shape[1:], np.float32),
        padn(np.ascontiguousarray(node_cap, np.float32)),
    ]))
    node_i32 = jnp.asarray(np.stack([
        padn(np.asarray(node_task_count, np.int32)),
        padn(np.asarray(node_max_tasks, np.int32)),
        padn(np.asarray(node_feas, bool)).astype(np.int32),
    ]))
    gf = np.zeros((group_rows.shape[0], Np), bool)
    gf[:, :N] = group_rows
    return SelectionDeviceState(
        None, node_f32, node_i32, jnp.asarray(gf), Np, None
    )


def _keys_placement(cp: int):
    """Class-axis sharding for the resident key matrix when the mesh
    divides it (the per-row work — scoring and top_k — is
    embarrassingly parallel along the class axis), else the default
    single-device placement."""
    try:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .sharding import NODE_AXIS, default_mesh

        mesh = default_mesh()
        if mesh is not None and cp % mesh.size == 0:
            # The 1-D device axis is named for its primary (node-column)
            # role; here it carries class rows.
            return NamedSharding(mesh, P(NODE_AXIS, None))
    except Exception:  # pragma: no cover - mesh probe must never kill
        pass
    return None


def select_rows(
    state: SelectionDeviceState,
    mask: "CombinedMask",          # masks.CombinedMask (unpadded)
    rep_idx: np.ndarray,           # i64[C] representative task ids
    rep_req: np.ndarray,           # f32[C, R]
    rep_fit: np.ndarray,           # f32[C, R]
    rep_priv: np.ndarray,          # i64[C] private-row id or -1
    score_rows_map: Dict[int, np.ndarray],
    idle32: np.ndarray,            # f32[N, R] (unpadded, host)
    cap32: np.ndarray,
    eps32: np.ndarray,
    cap_ok0: np.ndarray,           # bool[N]
    lr_weight: float,
    br_weight: float,
    k: int,
    N: int,
    node_fp: Optional[tuple] = None,
    layout_token: Optional[str] = None,
) -> Optional[dict]:
    """Run the device-resident selection for one cycle.

    Returns ``{"cand_idx", "elig_count", "any_feas", "cache_hits",
    "rows_rebuilt", "cols_patched"}`` (cand_idx with the HOST sentinel
    ``N``), or None when the device path cannot run this cycle (caller
    then takes the labeled host fallback)."""
    try:
        import jax
        import jax.numpy as jnp
    except Exception:  # pragma: no cover - jax baked into the image
        return None
    from .topk import _skey_priv_row

    C = len(rep_idx)
    Np = state.n_padded
    eng = state.engine()
    cp = max(_pow2(C), 1)
    placement = _keys_placement(cp)
    if placement is not None:
        cp = max(cp, getattr(placement.mesh, "size", 1))

    sig = (
        N, Np, int(k), rep_req.shape[1], eps32.tobytes(),
        float(lr_weight), float(br_weight),
        state.layout_token or layout_token,
    )
    if (
        eng.sig != sig
        or eng.keys is None
        or eng.cp != cp
        or eng.keys.shape[1] != Np
    ):
        eng.invalidate()
        eng.sig = sig
        eng.cp = cp
        keys0 = np.full((cp, Np), -1, np.int32)
        if placement is not None:
            import jax

            eng.keys = jax.device_put(keys0, placement)
        else:
            eng.keys = jnp.asarray(keys0)

    # Node-churn fingerprint -> changed column set (identical decision
    # procedure to _SelectionCache's warm path).
    changed_cols = None
    if node_fp is not None:
        ids, vers, node_objs = node_fp
        if eng.node_ids is not None and len(eng.node_ids) == N:
            changed_cols = np.nonzero(
                (ids != eng.node_ids) | (vers != eng.node_vers)
            )[0]
        eng.node_objs = node_objs
        eng.node_ids = ids
        eng.node_vers = vers
    else:
        eng.node_objs = None
        eng.node_ids = None
        eng.node_vers = None
    if changed_cols is None:
        eng.row_digests = {}

    # Per-class content digests -> hit/miss (the host cache's keying,
    # with the row slot as the dict key since (ci, digest) pins ci).
    feas_all = mask.rows_for(rep_idx)                    # bool[C, N]
    any_feas = (feas_all & cap_ok0[None, :]).any(axis=1)
    misses = []
    priv_rows = []
    new_digests: Dict[int, bytes] = {}
    hits = 0
    for ci in range(C):
        p = int(rep_priv[ci])
        if p >= 0:
            priv_rows.append((ci, p))
            continue
        digest = hashlib.blake2b(
            feas_all[ci].tobytes()
            + rep_fit[ci].tobytes()
            + rep_req[ci].tobytes(),
            digest_size=16,
        ).digest()
        new_digests[ci] = digest
        if eng.row_digests.get(ci) == digest:
            hits += 1
        else:
            misses.append(ci)
    eng.row_digests = new_digests

    eps_d = jnp.asarray(eps32)
    lw = jnp.float32(lr_weight)
    bw = jnp.float32(br_weight)
    group_ids_full = np.zeros(cp, np.int32)
    group_ids_full[:C] = mask.task_group[rep_idx]
    req_full = np.zeros((cp, rep_req.shape[1]), np.float32)
    req_full[:C] = rep_req
    fit_full = np.zeros((cp, rep_fit.shape[1]), np.float32)
    fit_full[:C] = rep_fit
    class_full = np.arange(cp, dtype=np.int32)

    # 1) churned-column patch across every resident row.
    cols_patched = 0
    if hits and changed_cols is not None and len(changed_cols):
        m = _pow2(len(changed_cols))
        cols_p = np.full(m, Np, np.int32)
        cols_p[:len(changed_cols)] = changed_cols
        with _minted_lock:
            _minted_cols.add(m)
        eng.keys = _col_patch_jit()(
            eng.keys, jnp.asarray(cols_p),
            jnp.asarray(req_full), jnp.asarray(fit_full),
            jnp.asarray(class_full), jnp.asarray(group_ids_full),
            state.node_f32, state.node_i32, state.group_feas,
            eps_d, lw, bw,
        )
        cols_patched = len(changed_cols)

    # 2) full rebuild of missed rows, chunked by the cell cap.
    chunk = max(1, min(cp, _MISS_CHUNK_CELLS // max(Np, 1)))
    for m0 in range(0, len(misses), chunk):
        batch = misses[m0:m0 + chunk]
        b = _pow2(len(batch))
        rows_p = np.full(b, cp, np.int32)
        rows_p[:len(batch)] = batch
        with _minted_lock:
            _minted_rows.add(b)
        eng.keys = _miss_jit()(
            eng.keys, jnp.asarray(rows_p),
            jnp.asarray(req_full[rows_p % cp]),
            jnp.asarray(fit_full[rows_p % cp]),
            jnp.asarray(class_full[rows_p % cp]),
            jnp.asarray(group_ids_full[rows_p % cp]),
            state.node_f32, state.node_i32, state.group_feas,
            eps_d, lw, bw,
        )

    # 3) private rows: host-computed every cycle (their static score
    # addend is never cached — same rule as the host cache) and
    # scattered in through the shared device-cache row patcher.
    if priv_rows:
        from .device_cache import _patch_axes_lock, _patch_axes_used, _patcher

        b = _pow2(len(priv_rows))
        rows_p = np.full(b, cp, np.int32)
        vals_p = np.full((b, Np), -1, np.int32)
        for i, (ci, p) in enumerate(priv_rows):
            srow = np.asarray(score_rows_map.get(p, np.zeros(N)),
                              np.float32)
            row = _skey_priv_row(
                rep_req[ci:ci + 1], rep_fit[ci:ci + 1], ci,
                idle32, cap32, eps32, cap_ok0,
                feas_all[ci:ci + 1], srow,
                lr_weight, br_weight,
            )
            rows_p[i] = ci
            vals_p[i, :N] = row
        with _patch_axes_lock:
            _patch_axes_used.add(0)
        eng.keys = _patcher(0)(
            eng.keys, jnp.asarray(rows_p), jnp.asarray(vals_p)
        )

    # 4) top-K extraction + eligibility gauge, one fused pass.
    kk = min(int(k), Np)
    with _minted_lock:
        _minted_topk.add((kk, N))
    # The composite tie keys inside the extraction are int64; the x64
    # context must cover trace AND lowering (it is part of the jit
    # cache key, so every call goes through it). No 64-bit dtype
    # escapes — both outputs are i32.
    with jax.enable_x64(True):
        cand_dev, count_dev = _topk_jit(kk, N)(eng.keys)
    cand = np.full((C, int(k)), N, np.int32)
    cand[:, :kk] = np.asarray(cand_dev)[:C]
    elig_count = np.asarray(count_dev)[:C]

    return {
        "cand_idx": cand,
        "elig_count": elig_count,
        "any_feas": any_feas,
        "cache_hits": hits,
        "rows_rebuilt": len(misses),
        "cols_patched": cols_patched,
    }
