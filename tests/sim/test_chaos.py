"""Chaos-path coverage: the deterministic simulator under a
solver-exc + solver-hang + bind fault storm must keep every invariant,
complete every cycle (the ladder absorbs device faults inside the
cycle), re-promote the device path when faults stop, and replay
bit-identically. doc/design/robustness.md."""

import pytest

from kube_batch_tpu.metrics import metrics as m
from kube_batch_tpu.sim.faults import parse_fault_spec
from kube_batch_tpu.sim.harness import ClusterSimulator, SimConfig
from kube_batch_tpu.sim.trace import TraceReader

STORM = "solver-exc:0.05,solver-hang:0.01,bind:0.05"


def _storm_cfg(cycles, tmp_path, seed=11, faults=STORM):
    return SimConfig(
        cycles=cycles, seed=seed, faults=faults, backend="dense",
        trace_path=str(tmp_path / "chaos.jsonl"),
    )


def _run(cfg):
    sim = ClusterSimulator(cfg)
    return sim.run()


class TestChaosStorm:
    def test_storm_completes_clean_and_replays_bit_equal(self, tmp_path):
        # Fault rates scaled up so a CI-sized run still injects a
        # meaningful storm (~15 exc + ~3 hangs over 150 cycles).
        cfg = _storm_cfg(
            150, tmp_path,
            faults="solver-exc:0.1,solver-hang:0.02,bind:0.05",
        )
        fallbacks_before = m.solver_fallback.get(
            ("dense", "native", "exception")
        )
        report = _run(cfg)
        assert report.violations == []
        assert report.cycle_errors == 0  # every fault contained in-cycle
        assert report.fault_counts.get("solver-exc", 0) > 0
        assert report.fault_counts.get("solver-hang", 0) > 0
        assert report.fault_counts.get("bind", 0) > 0
        # The ladder actually ran: device-rung descents were recorded.
        assert m.solver_fallback.get(
            ("dense", "native", "exception")
        ) > fallbacks_before
        # Hangs quarantined the backend at least once, and the breaker
        # re-promoted once the fault windows closed.
        assert report.breaker is not None
        assert report.breaker["trips"] >= 1
        assert report.breaker["reclosures"] >= 1
        assert report.breaker["state"] == "closed"
        assert report.placements > 0

        # Bit-equal replay: same placements every recorded cycle, same
        # invariant cleanliness — breaker state and fault windows are
        # cycle-counted, so record and replay walk the same ladder.
        replay_cfg = SimConfig(
            replay=TraceReader.load(str(tmp_path / "chaos.jsonl")),
            backend="dense",
        )
        replayed = _run(replay_cfg)
        assert replayed.replay_mismatches == []
        assert replayed.violations == []
        assert replayed.cycle_errors == 0

    def test_backend_loss_window_holds_breaker_open(self, tmp_path):
        cfg = _storm_cfg(
            80, tmp_path, seed=5, faults="backend-loss:0.05",
        )
        report = _run(cfg)
        assert report.violations == []
        assert report.cycle_errors == 0
        assert report.fault_counts.get("backend-loss", 0) > 0
        # Lost-backend cycles fail the solve AND the canary, so the
        # breaker opened and had failing probes before re-promoting.
        assert report.breaker["trips"] >= 1
        assert report.breaker["state"] == "closed"

    @pytest.mark.slow
    def test_storm_2k_cycles(self, tmp_path):
        """The acceptance-criteria soak (also run by `make chaos-smoke`
        at a CI-friendly size): 2k cycles under the issue's exact storm
        spec, zero violations, zero wedges, breaker re-promoted,
        bit-equal replay."""
        cfg = _storm_cfg(2000, tmp_path)
        report = _run(cfg)
        assert report.violations == []
        assert report.cycle_errors == 0
        assert report.breaker["state"] == "closed"
        assert report.breaker["trips"] >= 1
        replay_cfg = SimConfig(
            replay=TraceReader.load(str(tmp_path / "chaos.jsonl")),
            backend="dense",
        )
        replayed = _run(replay_cfg)
        assert replayed.replay_mismatches == []
        assert replayed.violations == []


class TestFaultSpec:
    def test_new_kinds_parse(self):
        spec = parse_fault_spec(STORM + ",backend-loss:0.01")
        assert spec["solver-exc"] == 0.05
        assert spec["solver-hang"] == 0.01
        assert spec["backend-loss"] == 0.01

    def test_unknown_kind_still_rejected(self):
        with pytest.raises(ValueError):
            parse_fault_spec("solver-oops:0.1")

    def test_device_kinds_rejected_on_native_backend(self, tmp_path):
        """--backend native never dispatches a device solve, so device
        fault kinds would count injections while exercising nothing —
        a vacuous chaos run must be rejected up front."""
        cfg = SimConfig(
            cycles=10, seed=1, faults="solver-exc:0.1",
            backend="native",
            trace_path=str(tmp_path / "t.jsonl"),
        )
        with pytest.raises(ValueError, match="device backend"):
            ClusterSimulator(cfg)

    def test_tiny_solve_budget_only_with_device_faults(self, tmp_path,
                                                      monkeypatch):
        """The small wall-clock budget exists to cap INJECTED hangs:
        only a cycle with a planned hang runs under it. Construction and
        every other cycle keep the generous production budget, or a
        contended CI box turns a healthy solve's scheduling stall into
        a SolveTimeout cycle error (soak flake) or a breaker trip."""
        from kube_batch_tpu.sim import harness
        from kube_batch_tpu.solver import containment

        cfg = SimConfig(
            cycles=5, seed=1, faults="bind:0.05", backend="dense",
            trace_path=str(tmp_path / "a.jsonl"),
        )
        sim = ClusterSimulator(cfg)
        try:
            assert containment.solve_budget() >= 30.0
        finally:
            sim.close()

        stamped = []
        configure = containment.configure

        def recording(solve_budget=None):
            stamped.append(solve_budget)
            configure(solve_budget)

        monkeypatch.setattr(containment, "configure", recording)
        cfg2 = SimConfig(
            cycles=12, seed=1, faults="solver-hang:0.3",
            backend="dense",
            trace_path=str(tmp_path / "b.jsonl"),
        )
        sim2 = ClusterSimulator(cfg2)
        assert containment.solve_budget() >= 30.0
        report = sim2.run()
        hangs = report.fault_counts.get("solver-hang", 0)
        assert hangs > 0
        assert stamped.count(harness._HANG_BUDGET_S) == hangs
        assert all(
            b is None or b == harness._HANG_BUDGET_S or b >= 30.0
            for b in stamped
        )
