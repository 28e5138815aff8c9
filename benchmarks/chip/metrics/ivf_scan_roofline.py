"""ivf_scan_roofline (%; layer: kernels, ``kernels/ivf_scan``
``_ivf_scan_kernel``; moves qps). The least time of one call's required
work (the larger of 2 x rows probed x d_out FLOPs over the bf16 peak and
the real rows of the distinct probed segments, with their norms and ids,
plus the queries, over HBM bandwidth; ``harness/ivf_counts.py``), over
the kernel's mean device time per call in the trace. Rows per call come
from the engine's IVF counters over the window's calls."""

from harness import counts, ivf_counts, trace_metrics


def read(ctx):
    work = ctx.get("ivf")
    if work is None or ctx.get("trace") is None:
        return None
    per_call = trace_metrics.op_seconds_per_call(ctx, r"ivf_scan")
    call = ivf_counts.per_call(work, ctx["cfg"]["nprobe"])
    if per_call is None or call is None:
        return None
    d, pk = ctx["cfg"]["proj_dim"], ctx["peaks"]
    least, _ = counts.least_seconds(
        ivf_counts.scan_flops(call["rows_probed"], d),
        ivf_counts.scan_bytes(call["rows_read"], call["queries"], d),
        pk["bf16_flops"], pk["hbm_bytes_per_s"])
    return 100.0 * least / per_call
