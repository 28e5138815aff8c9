"""mfu.train (%; layer: train step; moves pairs_per_s). The whole step's
share of the chips' bf16 peak: pairs/s of the traced window times the
FLOPs a pair requires (forward z L^T and the weight gradient, 4 d_in d_out;
no gradient w.r.t. the data), over chips x peak. The trainer's f32 dots
run at XLA's default precision, one bf16 pass, so bf16 is the peak."""

from harness import counts


def read(ctx):
    if ctx["kind"] != "train":
        return None
    cfg = ctx["cfg"]
    flops = counts.train_step_flops(1, cfg["feat_dim"], cfg["proj_dim"])
    rate = ctx["pairs"] / ctx["window_s"]
    return 100.0 * rate * flops / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
