"""Shared readings for the per-layer metrics: device figures from the
trace reduction (``harness/trace.py``) and the program's ``obs`` spans."""

from __future__ import annotations

import re

STEP_PROGRAM = re.compile(r"step_fn")      # sync.make_train_step's jit


def idle_share(ctx):
    tr = ctx.get("trace")
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def step_ns(dev: dict):
    """Summed device time of the trainer's step program on one device."""
    return sum(t for name, (t, _) in dev["modules_ns"].items()
               if STEP_PROGRAM.search(name))


def step_seconds(ctx):
    """Mean device seconds of one run of the step program, averaged over
    the cell's devices; None where the trace holds no step program."""
    tr = ctx.get("trace")
    if ctx["kind"] != "train" or tr is None:
        return None
    per = []
    for d in tr["devices"].values():
        runs = [(t, c) for name, (t, c) in d["modules_ns"].items()
                if STEP_PROGRAM.search(name)]
        n = sum(c for _, c in runs)
        if not n:
            return None
        per.append(sum(t for t, _ in runs) / n / 1e9)
    return sum(per) / len(per)


def op_seconds_per_call(ctx, pattern: str):
    """Mean device seconds per event of the ops whose name matches, over
    the cell's devices; None where no op matches."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    rx = re.compile(pattern)
    total = n = 0
    for d in tr["devices"].values():
        for name, t in d["ops_ns"].items():
            if rx.search(name):
                total += t
                n += d["op_counts"][name]
    return total / n / 1e9 if n else None


def mean_bucket(ctx) -> float:
    """Mean padded batch (the engine's bucket) of the window's batches,
    from the ``device_topk`` spans' ``batch`` attribute."""
    sizes = [sp["attrs"]["batch"] for sp in _spans(ctx["spans"],
                                                   "device_topk")]
    return sum(sizes) / len(sizes) if sizes else float(
        ctx["cfg"]["max_batch"])


def _spans(traces, name):
    def walk(sp):
        if sp["name"] == name:
            yield sp
        for c in sp.get("children", ()):
            yield from walk(c)

    for tr in traces:
        yield from walk(tr["root"])


def span_durations(traces, name):
    return [sp["t_end"] - sp["t_start"] for sp in _spans(traces, name)
            if sp["t_end"] is not None]


def self_time(traces, name, child):
    """Duration of each ``name`` span minus its ``child`` children."""
    out = []
    for sp in _spans(traces, name):
        if sp["t_end"] is None:
            continue
        kids = sum(c["t_end"] - c["t_start"] for c in sp["children"]
                   if c["name"] == child and c["t_end"] is not None)
        out.append(sp["t_end"] - sp["t_start"] - kids)
    return out
