"""The benchmark's cells at a tiny size on the CPU.

Widths, rows and pools are cut here only, for tests: the benchmark's own
configurations are never run on the CPU. Each configuration's CPU
stand-in sizes are ``tiny/configs/<config>.json`` and each traffic mix's
``tiny/traffic/<traffic>.json``, beside this file; a cell runs with both
laid over its own. ``run_tiny`` drives a cell past the harness's look for
a chip (``run.py``), through the same set-up, window and check as a run
on the chip, with the Pallas kernels interpreted.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def benchmark_cells(chips=None) -> list:
    """The names of BENCHMARK.json's cells, those on ``chips`` chips only
    where it is given."""
    from harness import spec
    return [w["name"] for w in spec.benchmark()["workloads"]
            if chips is None or w["chips"] == chips]


def stand_in(part: str, name: str, cell: str) -> dict:
    """The CPU stand-in sizes ``tiny/<part>/<name>.json`` of a cell's
    configuration (``part`` "configs") or traffic mix ("traffic")."""
    path = os.path.join(HERE, "tiny", part, name + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"cell {cell}: no CPU stand-in {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def tiny_cell(name: str, **traffic_kw):
    """(workload, tiny cfg, traffic, limits) of a BENCHMARK.json cell."""
    from harness import spec
    work, cfg, traffic, limits = spec.cell(spec.benchmark(), name)
    cfg = dict(cfg, **stand_in("configs", work["config"], name))
    traffic = dict(traffic, **stand_in("traffic", work["traffic"], name))
    traffic.update(traffic_kw)
    return work, cfg, traffic, limits


def run_tiny(name: str, seed: int = 2 ** 31 + 11, seconds: float = 1.0,
             trace: bool = False, **traffic_kw):
    """One run of a cell at the tiny size, past the harness's look for a
    chip; returns (result, checks)."""
    import time

    from harness import cells, spec
    work, cfg, traffic, limits = tiny_cell(name, **traffic_kw)
    bench = spec.benchmark()
    return cells.run(
        work, cfg, traffic, limits, spec.metrics_for(bench, name, trace),
        seed=seed, seconds=seconds, trace=trace, peaks=TINY_PEAKS,
        t_start=time.perf_counter(),
        out_dir=os.path.join(ROOT, ".bench_out", "tests"))
