"""mfu.ivf (%; layer: batch step, projection, coarse probe and segment
scan; moves qps). The FLOPs that the window's completed queries require
(each query's projection through L, its distances to the C centroids and
its probed rows' cross terms, with the rows probed per query from the
engine's IVF counters), per second of the window, over the bf16 peak."""

from harness import ivf_counts


def read(ctx):
    work = ctx.get("ivf")
    if work is None or not work["probes"]:
        return None
    cfg = ctx["cfg"]
    rows = work["rows_probed"] / (work["probes"] / cfg["nprobe"])
    flops = ivf_counts.query_flops(cfg["feat_dim"], cfg["proj_dim"],
                                   cfg["n_clusters"], rows)
    rate = ctx["completed_in_window"] / ctx["window_s"]
    return 100.0 * rate * flops / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
