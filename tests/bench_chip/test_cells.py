"""Each one-chip cell of BENCHMARK.json, its set-up, window and check end
to end at a tiny size on the CPU: the run's result line is whole, with
the cell's end-to-end metrics, and its check passes. The four-worker
cell runs on four virtual CPU devices in a process of its own."""

import json
import os
import subprocess
import sys

import pytest

from harness import spec
from tiny import ROOT, benchmark_cells, run_tiny


@pytest.mark.parametrize("cell", benchmark_cells(chips=1))
def test_cell_runs_and_checks_out(cell):
    result, checks = run_tiny(cell)
    assert result["correct"], checks
    e2e = spec.metrics_for(spec.benchmark(), cell, False)
    assert set(result["metrics"]) == {m["name"] for m in e2e}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    for c in checks.values():
        assert c["limit"] is None or c["value"] <= c["limit"]


def four_workers(fault=""):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "bench_chip",
                                      "_four_workers.py"), fault],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_workers_run_and_check_out():
    out = four_workers()
    assert out["count"] == 4
    assert out["correct"], out["checks"]
