"""gen_lag_ms.serve (ms; layer: load generator, the benchmark's own;
moves qps). The 99th percentile of how late the client thread submitted
the window's requests behind their due times. A starved generator shows
here, not as a fast server."""

import numpy as np


def read(ctx):
    if ctx["kind"] != "serve" or not len(ctx["lag_s"]):
        return None
    return float(np.percentile(ctx["lag_s"], 99)) * 1e3
