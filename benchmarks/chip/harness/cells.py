"""One run of one cell: set-up, window, check, metrics, result line."""

from __future__ import annotations

import gc
import os
import shutil
import sys

import jax

from harness import spec, trace as tracing


class Profile:
    """The JAX profiler around the measured window, with a host annotation
    ``bench_window`` that marks the window on the trace's own clock."""

    def __init__(self, out_dir: str):
        self.dir = os.path.join(out_dir, "trace")
        shutil.rmtree(self.dir, ignore_errors=True)
        self._ann = None

    def start(self):
        # JAX's own host events and the annotations, without the Python
        # tracer: it records every Python call and slows a host loop
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def mark(self):
        self._ann = jax.profiler.TraceAnnotation(tracing.WINDOW)
        self._ann.__enter__()

    def stop(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self, devices):
        return tracing.reduce(tracing.find_xplane(self.dir),
                              [d.id for d in devices])


def settle_heap() -> None:
    """End of set-up: collect, then move every object that set-up made out
    of the collector's reach, as a long-running server does after its
    warm-up. Otherwise Python's full collections walk JAX's whole heap
    during the window, and each walk stalls every thread for tens of
    milliseconds."""
    gc.collect()
    gc.freeze()


def release_heap() -> None:
    """After the window: hand the set-up heap back to the collector, so
    that the cell's device arrays can be freed before the reference."""
    gc.unfreeze()
    gc.collect()


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run(work, cfg, traffic, limits, metric_entries, *, seed, seconds, trace,
        peaks, t_start, out_dir):
    """Run the cell once; returns (result dict, checks dict)."""
    devices = jax.devices()[:work["chips"]]
    prof = Profile(out_dir) if trace else None
    drive = spec.driver(traffic["kind"]).drive
    ctx, nums, _ = drive(cfg, traffic, seed=seed, seconds=seconds,
                         prof=prof, t_start=t_start, devices=devices)
    ctx.update(cfg=cfg, traffic=traffic, peaks=peaks, chips=len(devices))
    correct, checks = spec.judge(nums, limits)
    if prof is not None:
        ctx["trace"] = prof.reduce(devices)
    d0 = devices[0]
    result = {
        "correct": bool(correct),
        "attempted": int(ctx["attempted"]),
        "failed": int(ctx["failed"]),
        "metrics": spec.read_metrics(metric_entries, ctx),
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": ctx["memory_peak_bytes"]},
    }
    if prof is not None:
        tr = ctx["trace"]
        for i, d in sorted(tr["devices"].items()):
            print(f"device TPU {i}: busy {d['busy_ns'] / 1e9!r} s of the "
                  f"{d['recorded_ns'] / 1e9!r} s its trace recorded of the "
                  f"window's {tr['window_s']!r} s", file=sys.stderr)
        result["device"]["busy_s"] = tr["busy_s"]
        result["device"]["window_s"] = tr["window_s"]
        result["breakdown"] = tr["breakdown"]
    return result, checks
