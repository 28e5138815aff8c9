"""The harness refuses to measure anything but the chip it knows."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from tiny import BENCH, ROOT

ARGS = ["--workload", "imnet1m.train", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_refuses_the_cpu():
    proc = _run_py(ROOT)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(str(tmp_path))
    _no_result(proc)
    assert "src/repro" in proc.stderr


class _Dev:
    def __init__(self, kind):
        self.platform, self.device_kind = "tpu", kind


@pytest.fixture
def run_module():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PEAKS = {"devices": {"TPU v5 lite": {"bf16_flops": 197e12}}}


def test_refuses_a_device_missing_from_the_peaks_table(run_module,
                                                       monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v99")])
    with pytest.raises(SystemExit, match="no peaks"):
        run_module.device_or_exit(1, PEAKS)


def test_refuses_too_few_chips(run_module, monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v5 lite")])
    with pytest.raises(SystemExit, match="needs 4 chips"):
        run_module.device_or_exit(4, PEAKS)
    assert run_module.device_or_exit(1, PEAKS)[0].device_kind == (
        "TPU v5 lite")


def test_the_peaks_table_holds_the_v5e():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["int8_ops"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert "TPU v5e" in peaks["source"]


def test_sweep_refuses_a_kind_that_is_not_open_loop():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/sweep.py", "--workload",
         "imnet1m.train", "--rates", "100"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "not an open-loop serving kind" in proc.stderr
    assert "setup, Replay, window_stats" in proc.stderr
