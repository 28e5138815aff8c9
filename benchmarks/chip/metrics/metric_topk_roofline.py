"""metric_topk_roofline (%; layer: kernels, ``kernels/metric_topk``
``_metric_topk_kernel``; moves qps). The least time of the scan's
required work per call (the larger of 2 Nq M d_out FLOPs over the bf16
peak and the gallery, its norms and the queries over HBM bandwidth), over
the kernel's mean device time per call in the trace. Nq is the padded
bucket the scheduler's batches fill on average, M the gallery's rows."""

from harness import counts, trace_metrics


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    per_call = trace_metrics.op_seconds_per_call(ctx, r"metric_topk")
    if per_call is None:
        return None
    cfg, pk = ctx["cfg"], ctx["peaks"]
    nq = trace_metrics.mean_bucket(ctx)
    m, d = cfg["gallery_rows"], cfg["proj_dim"]
    least, _ = counts.least_seconds(counts.topk_scan_flops(nq, m, d),
                                    counts.topk_scan_bytes(nq, m, d),
                                    pk["bf16_flops"], pk["hbm_bytes_per_s"])
    return 100.0 * least / per_call
