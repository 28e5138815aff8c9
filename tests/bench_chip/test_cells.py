"""Each cell's set-up, window and check, end to end at a tiny size on the
CPU: the run's result line is whole and its check passes."""

import json
import os
import subprocess
import sys

import pytest

from tiny import ROOT, run_tiny

E2E = {"imnet1m.train": {"pairs_per_s", "setup_s"},
       "imnet63k.train": {"pairs_per_s", "setup_s"},
       "imnet1m.serve": {"qps", "setup_s"}}


@pytest.mark.parametrize("cell", sorted(E2E))
def test_cell_runs_and_checks_out(cell):
    result, checks = run_tiny(cell)
    assert result["correct"], checks
    assert set(result["metrics"]) == E2E[cell]
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
