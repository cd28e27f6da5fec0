"""Device-resident snapshot: persistent solver buffers + delta patches.

PR 1 made the HOST side of the snapshot incremental (fingerprint-patched
columnar arrays in ``_TensorizeCache``); this module extends the same
"keep the problem resident, ship only deltas" move onto the device.
Every ``tensorize(device=True)`` used to rebuild a fresh
:class:`~.kernels.PackedInputs` and re-ship all of it host→device — a
round trip per stacked buffer, ~6 MB at 50k×5k, every cycle, even when
a 1% delta changed a few hundred rows. CvxCluster (PAPERS.md) gets its
100-1000× on granular allocation problems from exactly this shape of
re-solve: the operator stays resident, only the changed entries move.

The cache holds, per PackedInputs field, the device buffer AND the
exact host copy it was built from. Packing a new snapshot then becomes,
per field:

- **reuse** — bit-identical host array → hand back the resident buffer,
  zero bytes shipped (the steady-state no-churn cycle);
- **patch** — same shape/dtype, few dirty rows → ship only those rows
  and scatter them in with ONE jitted ``.at[rows].set`` whose input
  buffer is **donated**, so XLA updates the resident allocation in
  place instead of materializing a second copy;
- **full upload** — cold cache, shape/dtype drift (bucket growth,
  resource-layout change), or bulk dirtiness past the patch break-even
  (same ~25% rule as the host-side ``_refresh_node_arrays``).

Change detection is a host-side diff against the cached host copy —
O(array bytes) of numpy compare, a few ms at 50k×5k and **exact by
construction**: the dirty-name ledger (``ClusterInfo.dirty_jobs/nodes``
→ clone fingerprints) decides which HOST rows get recomputed, and the
diff here is what guarantees the device buffers converge to those rows
bit-for-bit no matter which path produced them. Parity is therefore a
structural property, pinned by tests/solver/test_device_cache.py.

Shapes stay stable across cycles because tensorize buckets every axis
(``_task_bucket``/``_pow2``/128-multiples), so the patch jits compile
once per (buffer shape, row-bucket) pair and the solver jit never
retraces on a steady stream of deltas (tests/solver/test_retrace_guard
pins this).

OWNERSHIP: the returned PackedInputs buffers belong to the cache and
are valid until the next ``pack()`` on the same scheduler cache — a
later patch DONATES the old buffer, which deletes it under any holder.
Consume the inputs within the cycle (the action does); copy to host
(``np.asarray``) anything that must outlive it.
"""

from __future__ import annotations

import functools
import logging
from typing import Dict, Optional

import numpy as np

from ..utils.lockdebug import wrap_lock
from .contracts import contracts_enabled, validate_packed

logger = logging.getLogger(__name__)

# Forensics of the most recent pack() (bench/metrics attribution, read
# by actions.allocate_tpu and bench.py). Single-threaded by
# construction, like snapshot.last_tensorize_stats.
last_pack_stats: dict = {}

# Axis along which cycle-to-cycle deltas are row-shaped, per
# PackedInputs field (stacked buffers carry their stack dim first).
_ROW_AXIS = {
    "task_f32": 1,
    "task_i32": 1,
    "node_f32": 1,
    "node_i32": 1,
    "group_feas": 0,
    "pair_idx": 0,
    "pair_feas": 0,
    "score_idx": 0,
    "score_rows": 0,
    "queue_f32": 1,
    "misc": 0,
    # Candidate slabs (solver/topk.py): class-row deltas, same donated
    # row-scatter machinery as the other factorized rows.
    "cand_idx": 0,
    "cand_static": 0,
    "cand_info": 1,
}

# Past this dirty fraction a full upload beats row patching (mirrors
# the host-side bulk-dirty rule in snapshot._refresh_node_arrays).
_BULK_DIRTY_DEN = 4
# Buffers below this size are cheaper to re-ship whole than to run a
# scatter program over (also keeps tiny fields like ``misc`` from
# minting patch-jit entries).
_MIN_PATCH_BYTES = 4096

# Row-bucket axes that have minted a patch jit (for retrace counting).
_patch_axes_used: set = set()
_patch_axes_lock = wrap_lock("solver.patch_axes")


def _row_bucket(n: int) -> int:
    """Power-of-two bucket for the patched-row axis so a churning dirty
    count does not mint a new jit per cycle."""
    if n <= 0:
        return 1
    return 1 << (n - 1).bit_length()


@functools.lru_cache(maxsize=None)
def _patcher(axis: int):
    """Jitted donated row-scatter along ``axis``. Padded row indices
    point one past the end and are dropped (``mode='drop'``), so the
    row bucket never writes garbage."""
    import jax

    def patch(buf, rows, vals):
        idx = (slice(None),) * axis + (rows,)
        return buf.at[idx].set(vals, mode="drop")

    return jax.jit(patch, donate_argnums=(0,))


def patch_jit_cache_size() -> int:
    """Total compiled variants across every patch jit minted so far —
    one term of the retrace-regression guard."""
    total = 0
    with _patch_axes_lock:
        axes = tuple(_patch_axes_used)
    for axis in axes:
        try:
            total += _patcher(axis)._cache_size()
        except Exception:  # pragma: no cover - private-API drift
            pass
    return total


class DeviceSnapshotCache:
    """Per-scheduler-cache device residency for the solver's inputs.

    Lives on the SchedulerCache object (``_device_snapshot_cache``
    attribute), giving it exactly the lifetime of the mirror it
    shadows — same pattern as ``snapshot._TensorizeCache``."""

    __slots__ = ("host", "dev", "layout_token", "placement", "_deferred")

    def __init__(self):
        # field -> exact host copy of what is resident on device
        self.host: Dict[str, np.ndarray] = {}
        # field -> jax.Array resident buffer
        self.dev: Dict[str, object] = {}
        # Stats of a pack_partial() awaiting merge into the next pack()
        # (the two are one logical pack per cycle).
        self._deferred: Optional[dict] = None
        # Solver device-layout key (the solve plan's layout_token):
        # resident buffers are only reusable under the layout they were
        # placed for — a mesh/mode flip voids them all (labeled
        # ``mesh-change`` full re-upload).
        self.layout_token = None
        # jax.sharding.Sharding applied at upload time (None = default
        # single-device placement).
        self.placement = None

    def drop(self) -> None:
        """Release every resident buffer (shutdown / tests)."""
        self.host.clear()
        self.dev.clear()

    # ------------------------------------------------------------------

    def _diff_rows(self, name: str, arr: np.ndarray, cached: np.ndarray):
        axis = _ROW_AXIS[name]
        neq = arr != cached
        if neq.ndim > 1:
            red = tuple(i for i in range(neq.ndim) if i != axis)
            dirty = neq.any(axis=red)
        else:
            dirty = neq
        return np.nonzero(dirty)[0], arr.shape[axis]

    def _upload(self, name: str, arr: np.ndarray, reason: str, stats):
        import jax.numpy as jnp

        if self.placement is not None:
            import jax

            dev = jax.device_put(arr, self.placement)
        else:
            dev = jnp.asarray(arr)
        self.host[name] = arr
        self.dev[name] = dev
        stats["uploads"] += 1
        stats["bytes_shipped"] += arr.nbytes
        stats["full_reasons"][name] = reason
        stats["field_outcomes"][name] = "upload"
        return dev

    def _patch(self, name: str, arr: np.ndarray, rows: np.ndarray, stats):
        import jax.numpy as jnp

        axis = _ROW_AXIS[name]
        nrows = arr.shape[axis]
        K = _row_bucket(rows.size)
        # Padded indices = nrows (one past the end): dropped by the
        # scatter, so the bucket costs shipping, not correctness.
        rows_p = np.full(K, nrows, dtype=np.int32)
        rows_p[:rows.size] = rows
        vals = np.take(arr, rows, axis=axis)
        vshape = list(vals.shape)
        vshape[axis] = K
        vals_p = np.zeros(tuple(vshape), dtype=arr.dtype)
        sl = [slice(None)] * vals.ndim
        sl[axis] = slice(0, rows.size)
        vals_p[tuple(sl)] = vals
        with _patch_axes_lock:
            _patch_axes_used.add(axis)
        dev = _patcher(axis)(
            self.dev[name], jnp.asarray(rows_p), jnp.asarray(vals_p)
        )
        self.host[name] = arr
        self.dev[name] = dev
        stats["patches"] += 1
        stats["rows_patched"] += int(rows.size)
        stats["bytes_shipped"] += vals_p.nbytes + rows_p.nbytes
        stats["field_outcomes"][name] = "patch"
        return dev

    def _pack_field(self, name: str, arr: np.ndarray,
                    cold_reason: str, stats: dict):
        """Reuse/patch/upload decision for ONE stacked field (shared by
        :meth:`pack` and :meth:`pack_partial`)."""
        cached = self.host.get(name)
        dev = self.dev.get(name)
        if cached is None or dev is None:
            return self._upload(name, arr, cold_reason, stats)
        if cached.shape != arr.shape or cached.dtype != arr.dtype:
            return self._upload(name, arr, "shape-change", stats)
        rows, nrows = self._diff_rows(name, arr, cached)
        if rows.size == 0:
            stats["reuses"] += 1
            stats["field_outcomes"][name] = "reuse"
            return dev
        if arr.nbytes < _MIN_PATCH_BYTES:
            return self._upload(name, arr, "small-buffer", stats)
        if rows.size * _BULK_DIRTY_DEN > nrows:
            return self._upload(name, arr, "bulk-dirty", stats)
        return self._patch(name, arr, rows, stats)

    def _empty_stats(self) -> dict:
        return {
            "reuses": 0,
            "patches": 0,
            "uploads": 0,
            "rows_patched": 0,
            "bytes_shipped": 0,
            "slab_bytes_shipped": 0,
            "bytes_total": 0,
            "full_reasons": {},
            "field_outcomes": {},
        }

    def _enter_layout(self, placement, layout_token, stats) -> str:
        cold_reason = "cold"
        if layout_token != self.layout_token:
            if self.host:
                self.drop()
                cold_reason = "mesh-change"
                stats["layout_change"] = True
            self.layout_token = layout_token
        self.placement = placement
        return cold_reason

    def pack_partial(self, arrays: Dict[str, np.ndarray],
                     placement: Optional[object] = None,
                     layout_token: Optional[str] = None) -> Dict[str, object]:
        """Place a SUBSET of the cycle's stacked fields on device ahead
        of the full :meth:`pack` — the device-resident selection pass
        (solver/select_device.py) needs the node stacks and group rows
        resident BEFORE the candidate slabs it produces can exist. The
        later pack() sees bit-identical host arrays and reuses these
        buffers; the traffic stats here are deferred and merged into
        that pack()'s ledger so per-cycle accounting stays whole."""
        stats = self._empty_stats()
        cold_reason = self._enter_layout(placement, layout_token, stats)
        out = {
            name: self._pack_field(name, arr, cold_reason, stats)
            for name, arr in arrays.items()
        }
        if self._deferred is None:
            self._deferred = stats
        else:  # two partials before a pack: fold counters forward
            for key in ("patches", "uploads", "rows_patched",
                        "bytes_shipped"):
                self._deferred[key] += stats[key]
            self._deferred["full_reasons"].update(stats["full_reasons"])
            self._deferred["field_outcomes"].update(
                stats["field_outcomes"]
            )
        return out

    def pack(self, arrays: Dict[str, np.ndarray],
             placement: Optional[object] = None,
             layout_token: Optional[str] = None):
        """Build a :class:`~.kernels.PackedInputs` from stacked host
        arrays, reusing/patching resident device buffers per field (see
        module docstring for the reuse/patch/upload decision). Records
        per-cycle forensics in :data:`last_pack_stats` and exports the
        aggregate counters through ``metrics``.

        ``placement``/``layout_token`` parameterize residency by the
        solver's device layout (the solve plan's fields): a token
        change drops every resident buffer — a buffer laid out
        for one mesh/mode cannot be patched into another — and the
        whole snapshot re-uploads under the new placement, labeled
        ``mesh-change``."""
        from .kernels import PackedInputs

        if contracts_enabled():
            # Runtime twin of the kbtlint shape-contracts pass: every
            # stacked buffer against the declaration table, symbolic
            # dims bound across fields (KBT_CHECK_CONTRACTS=1).
            validate_packed(arrays, where="device_cache.pack")

        stats = self._empty_stats()
        cold_reason = self._enter_layout(placement, layout_token, stats)
        fields: Dict[str, object] = {}
        for name, arr in arrays.items():
            stats["bytes_total"] += arr.nbytes
            shipped_before = stats["bytes_shipped"]
            fields[name] = self._pack_field(name, arr, cold_reason, stats)
            if name.startswith("cand"):
                stats["slab_bytes_shipped"] += (
                    stats["bytes_shipped"] - shipped_before
                )

        # Fold in a preceding pack_partial (same cycle, same logical
        # pack): its uploads/patches are real traffic; a field it
        # already placed shows as "reuse" above, so surface the partial
        # outcome instead for forensics.
        deferred, self._deferred = self._deferred, None
        if deferred is not None:
            for key in ("patches", "uploads", "rows_patched",
                        "bytes_shipped"):
                stats[key] += deferred[key]
            stats["full_reasons"].update(deferred["full_reasons"])
            for f, outcome in deferred["field_outcomes"].items():
                if outcome != "reuse":
                    if stats["field_outcomes"].get(f) == "reuse":
                        stats["reuses"] -= 1
                    stats["field_outcomes"][f] = outcome
            if deferred.get("layout_change"):
                stats["layout_change"] = True

        last_pack_stats.clear()
        last_pack_stats.update(stats)
        try:
            from .. import metrics

            metrics.update_device_cache(stats)
        except Exception:  # pragma: no cover - metrics must never kill
            logger.exception("device-cache metrics export failed")
        return PackedInputs(**fields)


def device_cache_of(cache) -> Optional[DeviceSnapshotCache]:
    """The scheduler cache's device snapshot cache, created on first
    use; None for slots-only stand-ins (then callers pack uncached)."""
    if cache is None:
        return None
    dc = getattr(cache, "_device_snapshot_cache", None)
    if dc is None:
        dc = DeviceSnapshotCache()
        try:
            cache._device_snapshot_cache = dc
        except Exception:
            return None
    return dc
