"""The program's own stages in a traced run's profile.

The program annotates its stages in the JAX profiler's trace
(``repro.obs.annotate``): the trainer's loop (``train.batch``,
``train.step``, ``train.log`` and their children) and the serving stack's
mirrored ``obs`` spans (``batch``, ``engine``, ``device_topk`` ...). They
are events of the host plane, on the clock of the device's events, so the
stage the host was in can be set against the device's idle time. JAX's own
compile events (``backend_compile_and_load``, ``backend_compile``, the
names ``jax/_src/compiler.py`` gives them) are there too.

The trace is the ``.xplane.pb`` that ``cells.Profile`` writes under
``<ROOT>/.bench_out/trace``, read once per process. The window is the
harness's ``bench_window`` annotation. A device is idle where its trace
recorded no op, over the part of the window its trace recorded
(``trace.recorded_ends``), as ``trace.reduce`` measures it. A span the
trace does not hold reads None: a program without the annotation has no
such stage to report.
"""

from __future__ import annotations

import os

from harness import spec, trace

TRACE_DIR = os.path.join(spec.ROOT, ".bench_out", "trace")
COMPILES = ("backend_compile_and_load", "backend_compile")
STAGES = ("train", "train.batch", "train.draw", "train.gather",
          "train.stack", "train.step", "train.log", "engine")

_loaded: dict = {}


class HostSpans:
    """The window, the host events of the program's stages and of JAX's
    compiles (by name), and each device's op intervals and event ends."""

    def __init__(self, window, host: dict, ops: dict, ends: dict):
        self.window = window            # (lo, hi) in ns, or None
        self.host = host                # name -> [(start, end)]
        self.ops = ops                  # device id -> [(start, end)]
        self.ends = ends                # device id -> [end of each event]

    @classmethod
    def from_file(cls, path: str) -> "HostSpans":
        devs, host_plane = trace.planes(path)
        wanted = set(STAGES) | set(COMPILES) | {trace.WINDOW}
        host: dict = {}
        for line in (host_plane.lines if host_plane is not None else ()):
            for s, e, name in trace._events(line):
                if name in wanted:
                    host.setdefault(name, []).append((s, e))
        win = host.pop(trace.WINDOW, None)
        ops, ends = {}, {}
        for i, plane in devs.items():
            lines = [trace._events(ln) if ln else [] for ln in
                     (trace._line(plane, "XLA Ops"),
                      trace._line(plane, "XLA Modules"))]
            ops[i] = [(s, e) for s, e, _ in lines[0]]
            ends[i] = [e for evs in lines for _, e, _ in evs]
        return cls(win[0] if win else None, host, ops, ends)

    def mean_ms(self, name: str):
        """Mean duration in ms of the ``name`` events that start in the
        window; None where there are none."""
        if self.window is None:
            return None
        d = durations_in(self.host.get(name, ()), *self.window)
        return 1e-6 * sum(d) / len(d) if d else None

    def count(self, names) -> int:
        """Events of any of ``names`` that start in the window."""
        return sum(len(durations_in(self.host.get(n, ()), *self.window))
                   for n in names)

    def idle_in(self, name: str, device_ids):
        """Share (%) of the window in which a device is idle while the
        host is inside a ``name`` event, over the part its trace recorded,
        averaged over ``device_ids``; None where no ``name`` event meets
        the window."""
        if self.window is None:
            return None
        lo, hi = self.window
        spans = trace.clip(self.host.get(name, ()), lo, hi)
        if not spans:
            return None
        return idle_share_in({i: self.ops[i] for i in device_ids},
                             {i: self.ends[i] for i in device_ids},
                             spans, lo, hi)


def durations_in(events, lo: float, hi: float) -> list:
    """Durations of the events that start in [lo, hi)."""
    return [e - s for s, e in events if lo <= s < hi]


def idle_inside(busy, spans, lo: float, end: float) -> float:
    """Length of [lo, end) in which the host is inside one of ``spans``
    and no interval of ``busy`` (merged, as ``trace.union`` gives) runs."""
    inside = trace.union(trace.clip(spans, lo, end))
    return sum((e - s) - trace.covered(busy, s, e) for s, e in inside)


def idle_share_in(ops: dict, ends: dict, spans, lo: float, hi: float):
    """``idle_inside`` per device over what its trace recorded of the
    window [lo, hi), as a share (%), averaged over the devices. A device
    whose trace recorded nothing of the window counts 0."""
    stop = trace.recorded_ends(ends, lo, hi)
    shares = []
    for i, dev_ops in ops.items():
        end = stop[i]
        if end <= lo:
            shares.append(0.0)
            continue
        busy = trace.union(trace.clip(dev_ops, lo, end))
        shares.append(idle_inside(busy, spans, lo, end) / (end - lo))
    return 100.0 * sum(shares) / len(shares) if shares else None


def load(ctx):
    """The traced run's ``HostSpans``, read once per process; None in a
    run without a trace."""
    if ctx.get("trace") is None:
        return None
    try:
        path = trace.find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _loaded:
        _loaded.clear()
        _loaded[key] = HostSpans.from_file(path)
    return _loaded[key]


def span_ms(ctx, kind: str, name: str):
    """Mean ms of the ``name`` span in a traced run of ``kind``."""
    hs = load(ctx) if ctx["kind"] == kind else None
    return None if hs is None else hs.mean_ms(name)


def idle_in(ctx, kind: str, name: str):
    """``HostSpans.idle_in`` over the cell's devices."""
    hs = load(ctx) if ctx["kind"] == kind else None
    if hs is None:
        return None
    return hs.idle_in(name, list(ctx["trace"]["devices"]))


def compiles(ctx, kind: str):
    """JAX's compile events in the window: 0 where there are none, None
    where the trace has no window."""
    hs = load(ctx) if ctx["kind"] == kind else None
    if hs is None or hs.window is None:
        return None
    return hs.count(COMPILES)
