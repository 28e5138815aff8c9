"""Finding a cell's pieces by name, and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
configuration's entry names its file; the mix is
``traffic/<traffic>.json``, and its ``kind`` names the module
``harness/<kind>.py`` that drives it (``driver``); the limits of the
cell's correctness check are ``limits/<cell>.json``; each metric is read
by ``metrics/<metric>.py``. Adding a cell, a mix, a kind or a metric is
adding files and entries.

A driver module exposes

* ``drive(cfg, traffic, *, seed, seconds, prof, t_start, devices,
  control=False) -> (ctx, numbers, control numbers or None)``: one run of
  a cell: set-up, the measured window (under ``prof``, a
  ``cells.Profile``, when the run is traced), and the correctness check;
  ``numbers`` are the compared numbers by name, and with ``control`` the
  control's too. ``cells.run`` and ``calibrate.py`` both call it, so the
  limits are read on the timed path;
* optionally ``FAULTS``: ``{name: () -> context manager}``, faults of its
  own that ``calibrate.fault`` can plant besides the shared ones.

``ctx["kind"]`` is the class of result, which says which metric readers
apply; it is not the traffic kind. Every class gives ``kind``, ``setup_s``
(process start to the window's start), ``window_s``, ``attempted``,
``failed`` and ``memory_peak_bytes``; ``cells.run`` adds ``cfg``,
``traffic``, ``peaks``, ``chips`` and, in a traced run, ``trace``.

* ``"train"``: ``steps`` and ``pairs`` trained in the window. Read by
  ``pairs_per_s`` and the ``.train`` readers; the host-loop readers take
  the trainer's ``train.*`` stages from the trace.
* ``"serve"``: ``t0`` (the window's start, host clock),
  ``completed_in_window`` (requests due in the window and completed in
  it; a closed loop counts every request completed in it),
  ``latency_s`` and ``lag_s`` (per request of the window: due time to
  result, and how late its submit ran; a closed loop has no due times,
  so its latency runs from the submit and ``lag_s`` is empty),
  ``spans`` (the program's ``obs`` traces whose root starts in the
  window) and ``batches``
  ((requests, batches) the scheduler dispatched in the window). Read by
  ``qps`` and the ``.serve`` readers; ``mfu.serve`` and
  ``metric_topk_roofline`` also read ``cfg``'s ``feat_dim``,
  ``proj_dim``, ``gallery_rows`` and ``max_batch``. A new serving driver
  that gives these keys gets those metrics unchanged.
"""

from __future__ import annotations

import glob
import importlib
import importlib.util
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(bench: dict, name: str):
    """(workload entry, configuration dict, traffic dict, limits dict)."""
    work = next((w for w in bench["workloads"] if w["name"] == name), None)
    if work is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == work["config"])
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     work["traffic"] + ".json"))
    lim_path = os.path.join(HERE, "limits", name + ".json")
    limits = load_json(lim_path) if os.path.exists(lim_path) else {}
    return work, cfg, traffic, limits


def driver(kind: str):
    """The module ``harness/<kind>.py`` that drives cells of a traffic
    kind. An unknown kind, or a module with no ``drive``, fails with the
    kinds found."""
    path = os.path.join(HERE, "harness", kind + ".py")
    if kind.isidentifier() and os.path.exists(path):
        mod = importlib.import_module("harness." + kind)
        if hasattr(mod, "drive"):
            return mod
    raise KeyError(f"no driver for traffic kind {kind!r} "
                   f"(harness/{kind}.py with a drive function); the kinds "
                   f"found are {kinds()}")


def kinds() -> list:
    """The traffic kinds of this checkout: the modules of ``harness/``
    that have a ``drive`` function."""
    names = sorted(os.path.basename(p)[:-3] for p in
                   glob.glob(os.path.join(HERE, "harness", "*.py")))
    return [n for n in names if not n.startswith("_")
            and hasattr(importlib.import_module("harness." + n), "drive")]


def metrics_for(bench: dict, name: str, trace: bool):
    """The metric entries this cell reports: its end-to-end metrics in a
    plain run, its per-layer metrics in a traced one."""
    def listed(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if listed(m)]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(metric_name: str):
    """The ``read(ctx)`` function of ``metrics/<metric_name>.py``."""
    path = os.path.join(HERE, "metrics", metric_name + ".py")
    mod_name = "bench_metric_" + "".join(
        c if c.isalnum() else "_" for c in metric_name)
    mod = sys.modules.get(mod_name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[mod_name] = mod
    return mod.read


def read_metrics(entries, ctx) -> dict:
    """Each metric's reader over the run's context; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        v = reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def judge(nums: dict, limits: dict):
    """(correct, checks): every compared number against its limit. A
    number the limits file does not name is reported and not judged; a
    limits file that names a number the run did not produce fails."""
    checks, ok = {}, True
    for name, lim in limits.get("numbers", {}).items():
        v = nums.get(name)
        limit = lim["limit"]
        good = v is not None and math.isfinite(v) and v <= limit
        ok &= good
        checks[name] = {"value": v, "limit": limit}
    for name, v in nums.items():
        if name not in checks:
            checks[name] = {"value": v, "limit": None}
    if not limits.get("numbers"):
        ok = False                  # a cell with no limits cannot pass
    return ok, checks


def emit(result: dict, checks: dict) -> None:
    """Print the compared numbers as the last lines of stderr, then the
    result as the last line of stdout, ``checks`` its last key."""
    for name, c in checks.items():
        value = None if c["value"] is None else float(c["value"])
        print(f"check {name} {value!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
