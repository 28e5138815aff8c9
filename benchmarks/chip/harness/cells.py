"""One run of one cell: set-up, window, check, metrics, result line."""

from __future__ import annotations

import gc
import math
import os
import shutil
import sys
import time

import jax
import numpy as np

from harness import data, serve, spec, train, trace as tracing


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


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run(work, cfg, traffic, limits, metric_entries, *, seed, seconds, trace,
        peaks, t_start, out_dir):
    """Run the cell once; returns (result dict, checks dict)."""
    devices = jax.devices()[:work["chips"]]
    prof = Profile(out_dir) if trace else None
    drive = {"train": _train, "serve": _serve}[traffic["kind"]]
    ctx, nums = drive(cfg, traffic, seed=seed, seconds=seconds, prof=prof,
                      t_start=t_start, devices=devices)
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


def _train(cfg, traffic, *, seed, seconds, prof, t_start, devices):
    start = traffic["window_start"]
    env = train.build(cfg, seed)

    def on_start():
        settle_heap()
        if prof is not None:
            prof.start()
            prof.mark()

    hook = train.Hook(seconds, start, on_start=on_start,
                      on_stop=prof.stop if prof is not None else None)
    history, src = train.drive(env, hook, n_record=start + 1)
    peak = _peak_bytes(devices)
    release_heap()
    steps = hook.step1 - hook.step0
    in_window = [h for h in history if h["step"] > hook.step0]
    ctx = {
        "kind": "train",
        "setup_s": hook.t0 - t_start,
        "window_s": hook.t1 - hook.t0,
        "steps": steps,
        "pairs": steps * cfg["batch_size"] * cfg["n_workers"],
        "attempted": steps,
        "failed": sum(not math.isfinite(h["loss"]) for h in in_window),
        "memory_peak_bytes": peak,
    }
    steps, nums = train.identify(env, src, start + 1)
    if not any(nums.values()):
        seen = train.program_seen(cfg, history, hook, start)
        nums.update(train.check(env, cfg, seen, steps, start)[0])
    return ctx, nums


def _serve(cfg, traffic, *, seed, seconds, prof, t_start, devices):
    key = data.base_key(seed)
    due, qid = data.arrivals(traffic["rate_qps"], seconds,
                             traffic["lead_s"], seed, traffic["pool"])
    L, pool, stack = serve.setup(key, cfg, traffic, traced=prof is not None,
                                 max_traces=len(due) + 1024)
    rp = serve.Replay(stack.scheduler, pool, due, qid, traffic)
    marks = {}

    def on_window():
        if prof is not None:
            prof.mark()
        marks["hist0"] = stack.batch_hist()

    settle_heap()
    if prof is not None:
        prof.start()
    t_first = time.perf_counter() + 0.05
    t0 = t_first + traffic["lead_s"]
    rp.run(t0, on_window=on_window)
    serve.sleep_until(t0 + seconds)
    hist1 = stack.batch_hist()
    if prof is not None:
        prof.stop()
    rp.wait(t0 + seconds + 60.0)
    t_end = time.perf_counter()
    ws = serve.window_stats(rp, t0, seconds, t_end)
    closed = stack.close()
    peak = _peak_bytes(devices)
    spans = stack.tracer.drain()
    rp.scheduler = None
    del stack
    release_heap()
    mono0 = t0 + time.monotonic() - time.perf_counter()   # spans' clock
    ctx = {
        "kind": "serve",
        "setup_s": t0 - t_start,
        "window_s": seconds,
        "t0": t0,
        "latency_s": ws["latency_s"],
        "lag_s": ws["lag_s"],
        "completed_in_window": ws["completed_in_window"],
        "attempted": ws["n_due"],
        "failed": ws["n_failed"] + (0 if closed else 1),
        "memory_peak_bytes": peak,
        "spans": [s for s in spans
                  if mono0 <= s["root"]["t_start"] < mono0 + seconds],
        "batches": (hist1[0] - marks["hist0"][0],
                    hist1[1] - marks["hist0"][1]),
    }
    nums = serve.check(key, L, cfg, pool, rp, ws["in_window"], seed)[0]
    nums["requests_unanswered"] = float(ws["n_unanswered"])
    return ctx, nums
