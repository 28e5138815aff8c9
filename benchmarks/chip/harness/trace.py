"""Reduction of a JAX profiler trace (``.xplane.pb``) to device figures.

Read with ``jax.profiler.ProfileData.from_file``. A TPU device is a plane
named ``/device:TPU:<id>``; its ``XLA Ops`` line holds one event per
operation run on the chip, and its ``XLA Modules`` line one per program
run. Host threads are the lines of the ``/host:CPU`` plane; their events
are the annotations the host recorded (``TraceAnnotation`` and JAX's own
dispatch spans). All times are nanoseconds on one clock.

The window is the host annotation ``WINDOW`` that the harness opens and
closes around the measured window. Per device, within the window:

* busy: the union of the op intervals; idle share = 1 - busy / window,
  taken over the part of the window that the device's trace recorded
  (see ``recorded_ends``);
* per op name: summed duration; per program name: summed duration and
  count;
* collective-only: the time inside all-reduce ops that no other op of the
  device overlaps;
* idle gaps: the longest stretches with no op (of a microsecond or more),
  each labelled by the innermost host event that covers most of it.

The device's and the host's timestamps agree only to about a millisecond
(in the recorded test trace each program started 1.3 ms before the host
call that launched it): enough for windows of seconds, while a gap's
label can be off by that much.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

WINDOW = "bench_window"
MIN_GAP_NS = 1000      # shorter idle stretches are the seams between ops
TRUNCATED = 0.05       # a device trace ending this share early was cut
_COLLECTIVE = re.compile(r"all-reduce|all_reduce|allreduce|reduce-scatter|"
                         r"all-gather|collective-permute", re.I)


def is_collective(op: str) -> bool:
    """Whether an op event is a collective. A TPU op event is named by its
    HLO text, ``%<name> = <shape> <opcode>(<operands>)``; only the
    instruction's own name counts (XLA names it after its opcode, as in
    ``%all-reduce.3`` or ``%all-reduce-start``), never an operand: an op
    that consumes an all-reduce's result is compute."""
    return bool(_COLLECTIVE.search(op.split(" = ", 1)[0]))


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def union(intervals):
    """Merge (start, end) intervals; returns a sorted (n, 2) array."""
    if not len(intervals):
        return np.zeros((0, 2))
    iv = np.asarray(sorted(intervals), dtype=np.float64)
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def clip(intervals, lo, hi):
    out = []
    for s, e, *rest in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, *rest))
    return out


def covered(merged, lo, hi) -> float:
    """Length of [lo, hi) covered by merged intervals."""
    if not len(merged):
        return 0.0
    s = np.clip(merged[:, 0], lo, hi)
    e = np.clip(merged[:, 1], lo, hi)
    return float(np.sum(e - s))


def _device_id(name: str):
    m = re.fullmatch(r"/device:TPU:(\d+)", name)
    return int(m.group(1)) if m else None


def planes(path: str):
    """(device planes {id: plane}, host plane or None) of a trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devs, host = {}, None
    for p in pd.planes:
        i = _device_id(p.name)
        if i is not None:
            devs[i] = p
        elif p.name == "/host:CPU":
            host = p
    return devs, host


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def recorded_ends(ends: dict, lo: float, hi: float) -> dict:
    """Where each device's trace ends inside the window [lo, hi), given
    the end times of its events: the window's end, unless the device's
    last event ends more than ``TRUNCATED`` of the window before the
    latest device's. The profiler keeps a bounded buffer per device, and
    a device that runs many small programs fills it early: on four chips
    device 0, which gathers and stacks every worker's batch, stopped
    recording 1.4 s into a 10 s window while the other three ran on."""
    last = {i: min(max(e, default=lo), hi) for i, e in ends.items()}
    latest = max(last.values(), default=hi)
    return {i: (t if latest - t > TRUNCATED * (hi - lo) else hi)
            for i, t in last.items()}


def reduce(path: str, device_ids, n_gaps: int = 10, n_ops: int = 10):
    """Figures of the cell's devices inside the window of one trace."""
    devs, host = planes(path)
    host_events = []
    if host is not None:
        for line in host.lines:
            host_events += _events(line)
    win = [(s, e) for s, e, n in host_events if n == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    lo, hi = win[0]
    labels = [(s, e, n) for s, e, n in host_events if n != WINDOW]
    lines = {}
    for i in device_ids:
        plane = devs.get(i)
        if plane is None:
            raise ValueError(f"device {i} is not in the trace")
        lines[i] = [_events(ln) if ln else [] for ln in
                    (_line(plane, "XLA Ops"), _line(plane, "XLA Modules"))]
    ends = recorded_ends({i: [e for evs in ls for _, e, _ in evs]
                          for i, ls in lines.items()}, lo, hi)
    per_dev = {}
    for i, (ops, mods) in lines.items():
        end = ends[i]
        ops, mods = clip(ops, lo, end), clip(mods, lo, end)
        merged = union([(s, e) for s, e, _ in ops])
        busy = covered(merged, lo, end)
        op_time: dict = {}
        op_count: dict = {}
        for s, e, n in ops:
            op_time[n] = op_time.get(n, 0.0) + (e - s)
            op_count[n] = op_count.get(n, 0) + 1
        mod_time: dict = {}
        for s, e, n in mods:
            t, c = mod_time.get(n, (0.0, 0))
            mod_time[n] = (t + (e - s), c + 1)
        coll = [(s, e) for s, e, n in ops if is_collective(n)]
        other = union([(s, e) for s, e, n in ops if not is_collective(n)])
        coll_merged = union(coll)
        coll_only = sum((e - s) - covered(other, s, e)
                        for s, e in coll_merged)
        per_dev[i] = {"busy_ns": busy, "recorded_ns": end - lo,
                      "ops_ns": op_time, "op_counts": op_count,
                      "modules_ns": mod_time, "collective_only_ns": coll_only,
                      "gaps": _gaps(merged, lo, end, labels, n_gaps)}
    window_ns = hi - lo
    # each device's busy share of what its trace recorded, over the window
    busy_s = float(np.mean([d["busy_ns"] / d["recorded_ns"]
                            if d["recorded_ns"] else 0.0
                            for d in per_dev.values()])) * window_ns / 1e9
    ops_all: dict = {}
    for d in per_dev.values():
        for n, t in d["ops_ns"].items():
            ops_all[n] = ops_all.get(n, 0.0) + t / len(per_dev)
    top_ops = sorted(ops_all.items(), key=lambda kv: -kv[1])[:n_ops]
    # on several chips a gap names its device: "TPU 2: <host annotation>"
    gaps = sorted(((f"TPU {i}: {n}" if len(per_dev) > 1 else n, t)
                   for i, d in per_dev.items() for n, t in d["gaps"]),
                  key=lambda g: -g[1])[:n_gaps]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "devices": per_dev,
        "breakdown": {
            "device_ops": [[n, t / 1e9] for n, t in top_ops],
            "idle_gaps": [[n, t / 1e9] for n, t in gaps],
        },
    }


def _gaps(merged, lo, hi, labels, n):
    """The n longest idle stretches of a device, each named by the host
    annotation that covers most of it (the shortest one on ties, which is
    the innermost)."""
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
            if edges[j + 1] - edges[j] >= MIN_GAP_NS]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        best, best_cov, best_len = "no host annotation", 0.0, np.inf
        for hs, he, name in labels:
            cov = min(e, he) - max(s, hs)
            if cov <= 0:
                continue
            if (cov > best_cov * 1.0001 or
                    (cov >= best_cov * 0.9999 and he - hs < best_len)):
                best, best_cov, best_len = name, cov, he - hs
        out.append((best, (e - s)))
    return out
