"""CPU rehearsal of run.py at a small size of each cell: the result line's
shape, the correctness check passing on a sound run, and the refusal to
run, or to report device metrics, off a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import small

CELLS = [c["name"] for c in
         small.harness.load_json(small.harness.ROOT,
                                 "BENCHMARK.json")["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_result_line(workload, trace):
    result, run = small.measure(workload, trace)
    line = json.loads(json.dumps(result))
    assert set(line) == KEYS  # no breakdown: no device trace off a TPU
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(c["limit"] == 0 for c in line["checks"].values())
    assert line["device"]["platform"] == "cpu"
    bench = small.harness.load_json(small.harness.ROOT, "BENCHMARK.json")
    kind = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in bench[kind]}
    assert set(line["metrics"]) <= names
    # Device metrics never come from a CPU run.
    device_metrics = {m["name"] for m in bench[kind]
                      if m["source"] == "device_trace"}
    assert not device_metrics & set(line["metrics"])
    assert "busy_s" not in line["device"]
    if not trace:
        assert "setup_s" in line["metrics"]
        assert all(m["value"] > 0 for m in line["metrics"].values())


def _run_py(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", str(small.SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    proc = _run_py(small.harness.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "TPU" in proc.stderr


def test_refuses_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark."""
    root = small.harness.ROOT
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(root, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout == ""
