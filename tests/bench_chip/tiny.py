"""The benchmark's cells at a tiny size on the CPU.

Widths, rows and pools are cut here only, for tests: the benchmark's own
configurations are never run on the CPU. ``run_tiny`` drives a cell past
the harness's look for a chip (``run.py``), through the same set-up,
window and check as a run on the chip, with the Pallas kernels
interpreted.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the CPU stand-in sizes of every configuration: widths, rows and pools
TINY = dict(feat_dim=256, n_samples=2048, n_classes=16, n_similar=4000,
            n_dissimilar=4000, gallery_rows=4096, gallery_chunk=1024,
            check_requests=256)
TINY_PROJ = {"imnet1m": 32, "imnet1m-4w": 32, "imnet63k": 96}
TINY_BATCH = {"imnet1m": 64, "imnet1m-4w": 64, "imnet63k": 16}
TINY_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cell(name: str, **traffic_kw):
    """(workload, tiny cfg, traffic, limits) of a BENCHMARK.json cell."""
    from harness import spec
    work, cfg, traffic, limits = spec.cell(spec.benchmark(), name)
    cfg = dict(cfg, **{k: v for k, v in TINY.items()
                       if k in cfg or k == "n_samples"})
    cfg["proj_dim"] = TINY_PROJ[work["config"]]
    cfg["batch_size"] = TINY_BATCH[work["config"]]
    if traffic["kind"] == "serve":
        traffic = dict(traffic, rate_qps=150, lead_s=0.3, pool=512)
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
