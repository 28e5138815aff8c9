"""Find a serving cell's knee: one set-up, then one open-loop window per
offered rate, in one process on the chip. The cell's traffic kind must be
an open-loop serving kind: its driver (``harness/<kind>.py``) exposes
``setup``, ``Replay`` and ``window_stats`` as ``harness/serve.py`` does.

    python3 benchmarks/chip/sweep.py --workload <serving cell> \
        --rates 1000,2000,3000 [--seconds 5] [--seed 7]

Prints, per rate, the p50 and p99 latency from due time to result, the
requests that failed or expired, the completed rate, the mean batch and
the requests due in the window and not finished at its close
(``backlog``), and how long after the close the last of them finished
(``backlog_s``): a growing backlog shows there. The knee is the highest
rate whose p99 stays within the interactive deadline with nothing expired
and no backlog; the cell's traffic file then offers a fixed share of it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from harness import spec  # noqa: E402

OPEN_LOOP = ("setup", "Replay", "window_stats")


def open_loop_driver(traffic: dict):
    """The cell's driver, or exit naming what an open-loop serving kind
    lacks."""
    kind = traffic["kind"]
    mod = spec.driver(kind)
    missing = [a for a in OPEN_LOOP if not hasattr(mod, a)]
    if missing:
        sys.exit(f"sweep.py: traffic kind {kind!r} is not an open-loop "
                 f"serving kind: harness/{kind}.py has no "
                 f"{', '.join(missing)}")
    return mod


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    _, cfg, traffic, _ = spec.cell(spec.benchmark(), args.workload)
    serve = open_loop_driver(traffic)
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep.py: no TPU")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from harness import cells, data
    _, pool, stack = serve.setup(data.base_key(args.seed), cfg, traffic)
    cells.settle_heap()
    for rate in [float(r) for r in args.rates.split(",")]:
        due, qid = data.arrivals(rate, args.seconds, traffic["lead_s"],
                                 args.seed, traffic["pool"])
        rp = serve.Replay(stack.scheduler, pool, due, qid, traffic)
        h0 = stack.batch_hist()
        t0 = time.perf_counter() + 0.05 + traffic["lead_s"]
        rp.run(t0)
        rp.wait(t0 + args.seconds + 30)
        end = time.perf_counter()
        ws = serve.window_stats(rp, t0, args.seconds, end)
        h1 = stack.batch_hist()
        inw = ws["in_window"]
        last = np.nanmax(rp.t_done[inw]) - (t0 + args.seconds)
        # due in the window and not finished when it closed
        backlog = int(np.sum(~(rp.t_done[inw] <= t0 + args.seconds)))
        lat = ws["latency_s"] * 1e3
        print(json.dumps({
            "rate": rate, "n": ws["n_due"], "failed": ws["n_failed"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "completed_per_s": ws["completed_in_window"] / args.seconds,
            "mean_batch": (h1[0] - h0[0]) / max(h1[1] - h0[1], 1),
            "backlog": backlog,
            "backlog_s": float(last),
            "gen_lag_p99_ms": float(np.percentile(ws["lag_s"], 99)) * 1e3,
        }), flush=True)
    stack.close()


if __name__ == "__main__":
    main()
