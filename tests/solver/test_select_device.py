"""Device-resident selection (solver/select_device.py): bit-equality
against the host topk pass under seeded churn, the labeled host
fallbacks, and layout-token invalidation of the resident key matrix.

The parity loop runs in-process on the conftest 8-device mesh (where
the class-axis sharding of the key matrix engages) and in SUBPROCESSES
on forced 1- and 2-device meshes (the host device count is frozen at
backend init) — the device path must be bit-equal to the host path on
every mesh size, not just the one the suite happens to run on.
"""

import os
import subprocess
import sys

import numpy as np
import pytest


def run_parity_cycles(cycles=5, seed=3, n=700, t=300, groups=8):
    """Seeded churned host-vs-device selection parity loop: every cycle
    asserts the device CandidateSet is bit-equal to the host one (slabs
    AND stats that feed the solver), then churns ~5% of nodes. Also
    asserts the cross-cycle caches on both sides made the SAME reuse
    decisions (the O(churn) warm property survives the port).
    Importable from the small-mesh subprocess scripts; returns the
    total device cache hits so callers can assert warmth engaged."""
    from kube_batch_tpu.solver import select_device
    from kube_batch_tpu.solver.masks import CombinedMask
    from kube_batch_tpu.solver.topk import select_candidates

    rng = np.random.RandomState(seed)
    task_req = np.c_[
        rng.choice([250, 500, 1000, 2000], t),
        rng.choice([256, 1024, 4096], t),
    ].astype(np.float32)
    task_group = (np.arange(t) % groups).astype(np.int32)
    group_rows = rng.rand(groups, n) > 0.1
    pair_idx = np.asarray([5, 17], np.int32)
    pair_rows = rng.rand(2, n) > 0.3
    score_rows_map = {31: (rng.rand(n) * 3.0).astype(np.float32)}
    node_idle = np.c_[
        rng.uniform(4000, 32000, n), rng.uniform(8192, 131072, n)
    ].astype(np.float32)
    node_cap = (node_idle * 1.5).astype(np.float32)
    node_task_count = rng.randint(0, 5, n).astype(np.int32)
    node_max_tasks = np.where(rng.rand(n) < 0.2, 4, 0).astype(np.int32)
    node_ok = rng.rand(n) > 0.05
    eps = np.asarray([10.0, 10.0], np.float32)
    ids = np.arange(n, dtype=np.int64)
    vers = np.zeros(n, np.int64)
    zeros = np.zeros_like(node_idle)
    k = 64

    class _Holder:
        pass

    host_holder = _Holder()
    engine_holder = _Holder()  # device engine rides across cycles
    hits_host = hits_dev = 0
    for _cyc in range(cycles):
        mask = CombinedMask(
            node_ok=node_ok, task_group=task_group,
            group_rows=group_rows & node_ok[None, :],
            pair_idx=pair_idx,
            pair_rows=pair_rows & node_ok[None, :],
        )
        args = (
            mask, score_rows_map, task_req, task_req, node_idle,
            node_cap, zeros, node_task_count, node_max_tasks,
            eps, 1.0, 0.5, k,
        )
        host = select_candidates(
            *args, cache_holder=host_holder,
            node_fp=(ids, vers.copy(), None),
        )
        state = select_device.standalone_state(
            node_idle, node_cap, node_task_count, node_max_tasks,
            node_ok, mask.group_rows,
        )
        state.holder = engine_holder  # production engine residency
        dev = select_candidates(
            *args, cache_holder=_Holder(),
            node_fp=(ids, vers.copy(), None), device_state=state,
        )
        assert host is not None and dev is not None
        assert dev.stats["select_path"] == "device", dev.stats
        assert (dev.cand_idx == host.cand_idx).all()
        assert (dev.cand_static == host.cand_static).all()
        assert (dev.cand_info == host.cand_info).all()
        assert (dev.task_cand == host.task_cand).all()
        assert dev.stats["sel_cache_hits"] == host.stats["sel_cache_hits"]
        hits_host += host.stats["sel_cache_hits"]
        hits_dev += dev.stats["sel_cache_hits"]
        # ~5% node churn (capacity AND task-count moves) before the
        # next cycle; version bumps are how production reports it.
        churn = rng.choice(n, size=max(n // 20, 1), replace=False)
        node_idle[churn] = np.c_[
            rng.uniform(4000, 32000, len(churn)),
            rng.uniform(8192, 131072, len(churn)),
        ].astype(np.float32)
        node_task_count[churn] = rng.randint(0, 5, len(churn))
        vers[churn] += 1
    assert hits_host == hits_dev
    assert hits_dev > 0, "warm O(churn) reuse never engaged on device"
    return hits_dev


_SMALL_MESH_SCRIPT = r"""
import sys
from kube_batch_tpu.utils.backend import force_cpu_devices
assert force_cpu_devices(%(devices)d)
sys.path.insert(0, r"%(testdir)s")
from test_select_device import run_parity_cycles
hits = run_parity_cycles(cycles=4, seed=%(seed)d)
print("SELECT_PARITY_OK", hits)
"""


class TestDeviceSelectionParity:
    def test_parity_churned_cycles_8dev(self):
        # conftest forces 8 CPU devices: cp divides the mesh, so the
        # class-axis NamedSharding of the resident key matrix engages.
        run_parity_cycles(cycles=5, seed=3)

    @pytest.mark.parametrize("devices", [1, 2])
    def test_parity_small_mesh_subprocess(self, devices):
        testdir = os.path.dirname(os.path.abspath(__file__))
        script = _SMALL_MESH_SCRIPT % {
            "devices": devices, "testdir": testdir, "seed": 11 + devices,
        }
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # subprocess owns its device count
        out = subprocess.run(
            [sys.executable, "-c", script], capture_output=True,
            text=True, timeout=600, env=env, cwd=os.path.dirname(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            ),
        )
        assert "SELECT_PARITY_OK" in out.stdout, (
            out.stdout, out.stderr[-2000:],
        )


def _one_shot(device_state, monkey_env=None, releasing=False):
    """Single tiny selection pass, returning the CandidateSet."""
    from kube_batch_tpu.solver.masks import CombinedMask
    from kube_batch_tpu.solver.topk import select_candidates

    n, t = 64, 16
    rng = np.random.RandomState(0)
    task_req = np.c_[
        rng.choice([250, 500], t), rng.choice([256, 1024], t)
    ].astype(np.float32)
    node_idle = np.tile(
        np.asarray([32000.0, 131072.0], np.float32), (n, 1)
    )
    releasing_cols = (
        np.full_like(node_idle, 100.0) if releasing
        else np.zeros_like(node_idle)
    )
    mask = CombinedMask(
        node_ok=np.ones(n, bool),
        task_group=np.zeros(t, np.int32),
        group_rows=np.ones((1, n), bool),
        pair_idx=np.zeros((0,), np.int32),
        pair_rows=np.zeros((0, n), bool),
    )
    return select_candidates(
        mask, {}, task_req, task_req, node_idle, node_idle,
        releasing_cols, np.zeros(n, np.int32), np.zeros(n, np.int32),
        np.asarray([10.0, 10.0], np.float32), 1.0, 1.0, 8,
        device_state=device_state,
    )


def _tiny_state():
    from kube_batch_tpu.solver import select_device

    n = 64
    node_idle = np.tile(
        np.asarray([32000.0, 131072.0], np.float32), (n, 1)
    )
    return select_device.standalone_state(
        node_idle, node_idle, np.zeros(n, np.int32),
        np.zeros(n, np.int32), np.ones(n, bool), np.ones((1, n), bool),
    )


class TestDeviceSelectionRouting:
    def test_env_off_switch_labels_host_fallback(self, monkeypatch):
        monkeypatch.setenv("KBT_SELECT_DEVICE", "0")
        cs = _one_shot(_tiny_state())
        assert cs.stats["select_path"] == "host:env-disabled"

    def test_releasing_labels_host_fallback(self):
        cs = _one_shot(_tiny_state(), releasing=True)
        assert cs.stats["select_path"] == "host:releasing"

    def test_device_path_engages_and_counts(self):
        from kube_batch_tpu import metrics

        before = metrics.solver_selection_device.total()
        cs = _one_shot(_tiny_state())
        assert cs.stats["select_path"] == "device"
        assert metrics.solver_selection_device.total() == before + 1

    def test_no_device_state_stays_host(self):
        cs = _one_shot(None)
        assert cs.stats["select_path"] == "host"


class TestLayoutTokenInvalidation:
    """A rack-map move (same device count, same mode) must void BOTH
    cross-cycle selection caches — the carried key rows were laid out
    for the old node->rack decomposition."""

    def _warm_then_flip(self, monkeypatch, device):
        from kube_batch_tpu.solver import sharding, select_device
        from kube_batch_tpu.solver.masks import CombinedMask
        from kube_batch_tpu.solver.plan import selection_token
        from kube_batch_tpu.solver.topk import select_candidates

        monkeypatch.setitem(sharding._layout_state, "devices", 8)
        monkeypatch.setitem(sharding._layout_state, "rack", None)
        monkeypatch.delenv("KBT_SPARSE_SHARD_MODE", raising=False)

        n, t = 96, 24
        rng = np.random.RandomState(1)
        task_req = np.c_[
            rng.choice([250, 500, 1000], t), rng.choice([256, 1024], t)
        ].astype(np.float32)
        node_idle = np.c_[
            rng.uniform(4000, 32000, n), rng.uniform(8192, 131072, n)
        ].astype(np.float32)
        mask = CombinedMask(
            node_ok=np.ones(n, bool),
            task_group=np.zeros(t, np.int32),
            group_rows=np.ones((1, n), bool),
            pair_idx=np.zeros((0,), np.int32),
            pair_rows=np.zeros((0, n), bool),
        )
        zc = np.zeros(n, np.int32)
        ids = np.arange(n, dtype=np.int64)
        vers = np.zeros(n, np.int64)

        class _Holder:
            pass

        holder = _Holder()

        def run():
            state = None
            if device:
                state = select_device.standalone_state(
                    node_idle, node_idle, zc, zc,
                    np.ones(n, bool), mask.group_rows,
                )
                state.holder = holder
            return select_candidates(
                mask, {}, task_req, task_req, node_idle, node_idle,
                np.zeros_like(node_idle), zc, zc,
                np.asarray([10.0, 10.0], np.float32), 1.0, 1.0, 8,
                cache_holder=holder, node_fp=(ids, vers, None),
                device_state=state,
                layout_token=selection_token(),
            )

        run()
        warm = run()
        assert warm.stats["sel_cache_hits"] > 0
        # The rack map moves under the caches (a sharded dispatch on a
        # re-coordinated mesh would pin a different digest).
        monkeypatch.setitem(sharding._layout_state, "rack", "feedbeef")
        cold = run()
        assert cold.stats["sel_cache_hits"] == 0
        return warm, cold

    def test_host_cache_invalidates_on_rack_change(self, monkeypatch):
        warm, cold = self._warm_then_flip(monkeypatch, device=False)
        assert warm.stats["select_path"] == "host"
        assert cold.stats["select_path"] == "host"

    def test_device_engine_invalidates_on_rack_change(self, monkeypatch):
        warm, cold = self._warm_then_flip(monkeypatch, device=True)
        assert warm.stats["select_path"] == "device"
        assert cold.stats["select_path"] == "device"
        assert cold.stats["sel_rows_rebuilt"] > 0
