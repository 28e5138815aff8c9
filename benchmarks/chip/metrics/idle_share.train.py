"""idle_share.train (%; layer: device; moves pairs_per_s). 1 - busy /
window from the trace, busy being the union of the intervals in which an
op ran, averaged over the cell's devices (each device's own is in the
breakdown's idle gaps)."""

from harness import trace_metrics


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return trace_metrics.idle_share(ctx)
