"""Run one cell of the chip benchmark once, from the repository root:

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; its traffic's ``kind`` names the module that drives it,
``harness/<kind>.py`` (``harness/spec.py`` says what such a driver
exposes). The run makes its data on the device from ``--seed``, warms
every shape it will use (set-up), measures for ``--seconds``, checks what
the timed path produced against a plain reference, and prints one JSON
object as its last line of stdout. With ``--trace 1`` the window runs
under the JAX profiler and the line carries the per-layer metrics, the
device's busy time and a breakdown. There is no CPU fallback: without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from harness import cells, spec  # noqa: E402


def device_or_exit(chips: int, peaks: dict):
    """The cell's devices, or exit: no TPU, too few chips, or a device the
    peaks table does not know."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"run.py: no TPU (JAX found {devices[0].platform}); the "
                 f"benchmark does not fall back to the CPU")
    if len(devices) < chips:
        sys.exit(f"run.py: the cell needs {chips} chips, JAX sees "
                 f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks["devices"]:
        sys.exit(f"run.py: no peaks for device kind {kind!r} in peaks.json")
    return devices


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401  the system under test
    except ImportError:
        sys.exit("run.py: the program (src/repro) is not in this checkout")
    bench = spec.benchmark()
    work, cfg, traffic, limits = spec.cell(bench, args.workload)
    peaks = spec.load_json(os.path.join(HERE, "peaks.json"))
    device_or_exit(work["chips"], peaks)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    result, checks = cells.run(
        work, cfg, traffic, limits,
        spec.metrics_for(bench, work["name"], bool(args.trace)),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        peaks=peaks["devices"][jax.devices()[0].device_kind],
        t_start=T_START, out_dir=os.path.join(spec.ROOT, ".bench_out"))
    spec.emit(result, checks)


if __name__ == "__main__":
    main()
