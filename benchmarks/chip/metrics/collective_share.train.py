"""collective_share.train (%; layer: worker exchange, the ``pmean`` of
``core/ps/sync.py``; moves pairs_per_s). Per device, the time inside
all-reduce ops during which no other op of that device runs, over the
device time of the step programs; averaged over the cell's devices. Only
a cell with more than one worker has an exchange to read."""

from harness import trace_metrics


def read(ctx):
    if ctx["kind"] != "train" or ctx["cfg"]["n_workers"] < 2:
        return None
    tr = ctx.get("trace")
    if tr is None:
        return None
    shares = []
    for d in tr["devices"].values():
        step = trace_metrics.step_ns(d)
        if not step:
            return None
        shares.append(d["collective_only_ns"] / step)
    return 100.0 * sum(shares) / len(shares)
