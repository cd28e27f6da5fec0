"""chip_smoke.py's one-chip phase, rehearsed at a small size on the CPU
mesh: the same deployment build, cycle sequence and checks the chip run
makes (backend ``jax-cpu`` here), so a change that would break the chip
smoke fails in the tier-1 suite first."""

import chip_smoke


def test_single_phase_rehearsal(monkeypatch):
    monkeypatch.setenv("KBT_SOLVER", "jax")
    # The size policy keeps small waves dense; the chip size engages the
    # sparse path on its own. Which pow2 patch buckets a wave touches is
    # data-dependent at this size: this seed's waves stay in one bucket,
    # as seed 0's do at the chip size.
    monkeypatch.setenv("KBT_SOLVER_TOPK", "64")
    out = chip_smoke.run_single(2, "cpu", nodes=1100, pods=5000, groups=50)
    assert out == {"cold_rows_differing_from_cpu": 0}


def test_main_refuses_without_tpu(monkeypatch, capsys):
    # No libtpu chip bounds in this worker's environment.
    monkeypatch.setattr(chip_smoke, "pin_first_chip", lambda: None)
    assert chip_smoke.main([]) == 2
    assert capsys.readouterr().out == ""
