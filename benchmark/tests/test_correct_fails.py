"""``correct`` must come out false when the timed path is broken: the
control (the reference placer with the cpu guarantee broken, in the
scheduler's place) and each fault a one-chip cell can have. The harness's
look for a chip is skipped; the rest of a run is driven as it stands."""

import pytest

import control
import small


def _incorrect(run):
    return any(v != 0 for v in run.checks.values())


CELLS = ["k8s5k.burst", "k8s5k.steady"]


def _no_step(kind):
    """A step that returns its state unchanged: nothing is placed in the
    window (the steady set-up's standing population still is, so that the
    run reaches its window)."""
    if kind == "burst":
        return lambda dep: None

    def frozen_once_arrivals_start(dep, stop):
        sched = dep.sched
        real_once = sched.run_once

        def once():
            if not any(name.startswith("a") for name in dep.live):
                real_once()

        sched.run_once = once
        sched.run_micro = lambda: True
        sched.run(stop)

    return frozen_once_arrivals_start


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    kind = workload.split(".")[1]
    run = small.drive(workload, control.CONTROLS[kind])
    assert _incorrect(run)
    assert run.checks["oversubscribed_nodes"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_unchanged_state_is_not_correct(workload):
    kind = workload.split(".")[1]
    run = small.drive(workload, _no_step(kind))
    assert _incorrect(run)
    assert run.checks["pods_left_that_fit"] > 0


def _broken_binds(kind, fault):
    """The scheduler in its place, with its binds broken once the window's
    traffic starts (every bind, for bursts): half of the batch left out
    (every second bind dropped), or an answer altered where it is
    produced (every bind sent to node n0)."""
    calls = []

    def arm(dep):
        real = type(dep.cluster).bind_pod.__get__(dep.cluster)

        def broken(pod, hostname):
            if kind == "steady" and not any(
                    name.startswith("a") for name in dep.live):
                return real(pod, hostname)
            calls.append(pod)
            if fault == "half_dropped":
                return None if len(calls) % 2 == 0 else real(pod, hostname)
            return real(pod, "n0")

        dep.cluster.bind_pod = broken

    if kind == "burst":
        def place(dep):
            arm(dep)
            dep.sched.run_once()
    else:
        def place(dep, stop):
            arm(dep)
            dep.sched.run(stop)
    return place, calls


@pytest.mark.parametrize("fault", ["half_dropped", "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_bind_is_not_correct(workload, fault):
    place, calls = _broken_binds(workload.split(".")[1], fault)
    run = small.drive(workload, place)
    assert calls, "the fault was never reached"
    assert _incorrect(run)
