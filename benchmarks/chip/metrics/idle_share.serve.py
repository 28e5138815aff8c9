"""idle_share.serve (%; layer: device; moves qps). As idle_share.train,
over the serving window."""

from harness import trace_metrics


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return trace_metrics.idle_share(ctx)
