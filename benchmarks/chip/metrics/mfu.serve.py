"""mfu.serve (%; layer: whole batch step, projection and scan; moves
qps). The FLOPs that the window's completed queries require (each
query's projection through L and its exact scan of the gallery), per
second of the window, over the bf16 peak."""

from harness import counts


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    cfg = ctx["cfg"]
    flops = counts.query_flops(cfg["feat_dim"], cfg["proj_dim"],
                               cfg["gallery_rows"])
    rate = ctx["completed_in_window"] / ctx["window_s"]
    return 100.0 * rate * flops / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
