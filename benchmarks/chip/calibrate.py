"""Readings that a cell's correctness limits are set from, in one process:

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 101-112 [--control-seeds 101-103] \
        [--faults half,noexchange,altered --fault-seeds 301-303] \
        [--seconds 3] [--out <file.json>]

For each seed it runs the cell's driver as a run does, untraced, with a
window of ``--seconds``, and prints the compared numbers of the program
(``program``) with the window's failed requests or steps; for the seeds
of ``--control-seeds`` also those of the control (``control``: the
reference computed in bfloat16, put in the program's place, on the same
batches or requests); and for each fault and
fault seed those of the program with the fault planted (``fault:<name>``):

* ``unchanged``: the train step returns its state unchanged;
* ``half``: the loss sees the first half of each batch only (training), or
  the second half of each served batch gets the first half's answers;
* ``noexchange``: the workers' ``pmean`` is left out (several workers);
* ``altered``: one served id of each batch is changed where it is made;

and any fault of the cell's driver's own ``FAULTS``.

The lower reading of a number is the largest the program gives over the
seeds; its upper reading the smallest the control or a fault gives.
Requires the chip, like ``run.py``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from harness import spec  # noqa: E402


def seeds(text: str):
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


@contextlib.contextmanager
def fault(name: str, driver=None):
    """Plant one fault in the program for the duration of the block: one
    of the shared faults above, or one of ``driver``'s ``FAULTS``."""
    own = getattr(driver, "FAULTS", {})
    if name in own:
        with own[name]():
            yield
        return
    import jax
    import numpy as np
    from repro.core import losses
    from repro.core.ps import sync
    from repro.serve import engine as engine_mod
    saved = []

    def patch(obj, attr, new):
        saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    if name == "unchanged":
        orig = sync.make_train_step

        def make(*a, **kw):
            step = orig(*a, **kw)
            return lambda state, batch: (state, step(state, batch)[1])
        patch(sync, "make_train_step", make)
    elif name == "half":
        orig_loss = losses.dml_pair_loss

        def half_loss(L, batch, **kw):
            n = batch["sim"].shape[0] // 2
            return orig_loss(L, {k: v[:n] for k, v in batch.items()}, **kw)
        patch(losses, "dml_pair_loss", half_loss)
        orig_search = engine_mod.RetrievalEngine.search

        def half_search(self, queries, *a, **kw):
            d, i = orig_search(self, queries, *a, **kw)
            if d.ndim == 2 and d.shape[0] > 1:
                h = d.shape[0] // 2
                d, i = d.copy(), i.copy()
                d[h:2 * h], i[h:2 * h] = d[:h], i[:h]
            return d, i
        patch(engine_mod.RetrievalEngine, "search", half_search)
    elif name == "noexchange":
        patch(jax.lax, "pmean", lambda x, axis_name, **kw: x)
    elif name == "altered":
        orig_search = engine_mod.RetrievalEngine.search

        def altered(self, queries, *a, **kw):
            d, i = orig_search(self, queries, *a, **kw)
            # index the copy itself: the chip hands back column-major
            # arrays, whose reshape(-1) is a copy, not a view
            i = np.array(i, copy=True)
            i[0, 0] = (i[0, 0] + 1) % self.index.size
            return d, i
        patch(engine_mod.RetrievalEngine, "search", altered)
    elif name:
        raise ValueError(f"unknown fault {name!r}; the driver's own are "
                         f"{sorted(own)}")
    try:
        yield
    finally:
        for obj, attr, old in reversed(saved):
            setattr(obj, attr, old)


def readings(work, cfg, traffic, seed, seconds, *, control=False):
    """{"program": numbers[, "control": numbers]} of one untraced run of a
    cell's driver, the program's with the window's ``failed``."""
    import jax
    ctx, nums, low = spec.driver(traffic["kind"]).drive(
        cfg, traffic, seed=seed, seconds=seconds, prof=None,
        t_start=time.perf_counter(), devices=jax.devices()[:work["chips"]],
        control=control)
    out = {"program": dict(nums, failed=float(ctx["failed"]))}
    if low is not None:
        out["control"] = low
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("calibrate.py: no TPU")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    work, cfg, traffic, _ = spec.cell(spec.benchmark(), args.workload)
    driver = spec.driver(traffic["kind"])

    rows = []
    ctl = set(seeds(args.control_seeds))
    plan = ([("", s) for s in seeds(args.seeds)]
            + [(f, s) for f in filter(None, args.faults.split(","))
               for s in seeds(args.fault_seeds)])
    for name, seed in plan:
        t = time.perf_counter()
        with fault(name, driver):
            out = readings(work, cfg, traffic, seed, args.seconds,
                           control=not name and seed in ctl)
        for what, nums in out.items():
            if name:
                what = f"fault:{name}"
            row = {"what": what, "seed": seed,
                   "s": time.perf_counter() - t, **nums}
            rows.append(row)
            print(json.dumps(row), flush=True)
    summary = {}
    for key in sorted({k for r in rows for k in r} - {"what", "seed", "s"}):
        for what in sorted({r["what"] for r in rows}):
            vals = [r[key] for r in rows if r["what"] == what and key in r]
            if vals:
                summary.setdefault(key, {})[what] = [min(vals), max(vals)]
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows,
                       "summary": summary}, f, indent=1)


if __name__ == "__main__":
    main()
