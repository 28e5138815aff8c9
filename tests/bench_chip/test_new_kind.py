"""A cell of a new traffic kind is added as new files and entries only.

In a copy of the benchmark's paths, a toy kind (``harness/toy.py``) with a
fault of its own, a configuration, a traffic mix, limits, CPU stand-ins
and entries in a copy of BENCHMARK.json make a cell that runs through
``cells.run`` to a whole result line, with its check passing and, under
its own fault, failing; and no file of the copy but the added ones
changes."""

import filecmp
import json
import os
import shutil
import subprocess
import sys

from tiny import ROOT

TOY = '''"""A toy serving kind: the nearest gallery row of each query of a
batch, as fast as the device answers, for the window's length."""

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells


@jax.jit
def nearest(g, q):
    return jnp.argmin(jnp.sum((q[:, None, :] - g[None]) ** 2, -1), axis=1)


def _run(cfg, traffic, seed, seconds, device, t_start):
    rng = np.random.default_rng(seed)
    g = rng.standard_normal((cfg["rows"], cfg["width"])).astype(np.float32)
    q = rng.standard_normal((traffic["batch"], cfg["width"])).astype(
        np.float32)
    gd, qd = jax.device_put(g, device), jax.device_put(q, device)
    np.asarray(nearest(gd, qd))                 # warm-up: set-up
    t0 = time.perf_counter()
    lat = []
    while time.perf_counter() - t0 < seconds:
        t = time.perf_counter()
        got = np.asarray(nearest(gd, qd))
        lat.append(time.perf_counter() - t)
    truth = np.argmin(((q[:, None] - g[None]) ** 2).sum(-1), axis=1)
    n = len(lat) * len(q)
    ctx = {"kind": "serve", "setup_s": t0 - t_start, "window_s": seconds,
           "t0": t0, "latency_s": np.repeat(lat, len(q)),
           "lag_s": np.zeros(n), "completed_in_window": n, "attempted": n,
           "failed": 0, "memory_peak_bytes": 0, "spans": [],
           "batches": (n, len(lat))}
    return ctx, {"answers_wrong": float((got != truth).sum())}


def drive(cfg, traffic, *, seed, seconds, prof, t_start, devices,
          control=False):
    ctx, nums = _run(cfg, traffic, seed, seconds, devices[0], t_start)
    ctx["memory_peak_bytes"] = cells.peak_bytes(devices)
    return ctx, nums, None


@contextlib.contextmanager
def _shifted():
    global nearest
    orig = nearest
    nearest = lambda g, q: (orig(g, q) + 1) % g.shape[0]  # noqa: E731
    try:
        yield
    finally:
        nearest = orig


FAULTS = {"shifted": _shifted}
'''

READER = '''"""toy_batches.serve (batches; a toy per-layer metric)."""


def read(ctx):
    return None if ctx["kind"] != "serve" else ctx["batches"][1]
'''

ADDED = {
    "benchmarks/chip/harness/toy.py": TOY,
    "benchmarks/chip/metrics/toy_batches.serve.py": READER,
    "benchmarks/chip/configs/toy.json": {
        "name": "toy", "reduced": [], "rows": 512, "width": 16},
    "benchmarks/chip/traffic/toyload.json": {"kind": "toy", "batch": 64},
    "benchmarks/chip/limits/toy.toyload.json": {
        "faults": ["shifted"], "numbers": {"answers_wrong": {"limit": 0}}},
    "tests/bench_chip/tiny/configs/toy.json": {"rows": 256},
    "tests/bench_chip/tiny/traffic/toyload.json": {"batch": 32},
}

DRIVE = '''import json, sys
sys.path.insert(0, "tests/bench_chip")
from tiny import run_tiny, tiny_cell
import calibrate
from harness import spec
result, checks = run_tiny("toy.toyload")
mix = tiny_cell("toy.toyload")[2]
with calibrate.fault("shifted", spec.driver(mix["kind"])):
    broken, _ = run_tiny("toy.toyload")
print(json.dumps({"result": result, "broken": broken["correct"],
                  "cells": [w["name"] for w in spec.benchmark()["workloads"]],
                  "traced": [m["name"] for m in spec.metrics_for(
                      spec.benchmark(), "toy.toyload", True)]}))
'''


def _files(top):
    out = set()
    for d, _, names in os.walk(top):
        out |= {os.path.relpath(os.path.join(d, n), top) for n in names}
    return out


def test_a_new_kind_is_new_files_only(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    ignore = shutil.ignore_patterns("__pycache__")
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p, ignore=ignore)
    before = _files(tmp_path)
    for rel, body in ADDED.items():
        assert rel not in before, rel
        text = body if isinstance(body, str) else json.dumps(body)
        (tmp_path / rel).write_text(text)
    bench["configs"].append({"name": "toy", "source": "a test",
                             "file": "benchmarks/chip/configs/toy.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "toy.toyload", "config": "toy",
                               "traffic": "toyload", "chips": 1,
                               "why": "a test"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "qps")["workloads"].append("toy.toyload")
    bench["per_layer"].append({
        "name": "toy_batches.serve", "unit": "batches", "better": "higher",
        "source": "program_counter", "layer": "toy", "moves": "qps",
        "workloads": ["toy.toyload"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", DRIVE], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    result = out["result"]
    assert result["correct"] and result["attempted"] > 0
    assert set(result["metrics"]) == {"qps", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert not out["broken"]
    assert "toy.toyload" in out["cells"]
    assert "toy_batches.serve" in out["traced"]

    after = {f for f in _files(tmp_path) if not f.startswith(".bench_out")}
    assert after == before | set(ADDED) | {"BENCHMARK.json"}
    for rel in before:
        assert filecmp.cmp(tmp_path / rel, os.path.join(ROOT, rel),
                           shallow=False), rel
