"""train_step_roofline (%; layer: train step; moves pairs_per_s). The
least time of one worker's step (the larger of its FLOPs over the bf16
peak and its required bytes over HBM bandwidth: read both sides of the
batch, read L, write L), over the device time per step of the trainer's
jitted step program (``XLA Modules`` events named after ``step_fn``),
averaged over the cell's devices."""

from harness import counts, trace_metrics


def read(ctx):
    per_step = trace_metrics.step_seconds(ctx)
    if per_step is None:
        return None
    cfg, pk = ctx["cfg"], ctx["peaks"]
    b, d_in, d_out = cfg["batch_size"], cfg["feat_dim"], cfg["proj_dim"]
    least, _ = counts.least_seconds(
        counts.train_step_flops(b, d_in, d_out),
        counts.train_step_bytes(b, d_in, d_out),
        pk["bf16_flops"], pk["hbm_bytes_per_s"])
    return 100.0 * least / per_step
