"""latency_p99_ms.serve (ms; layer: scheduler, the front door,
``serve/scheduler.py``; moves qps). The 99th percentile of the latency of
every request due in the window, from its due time to its result, with a
request that fails, expires or is rejected counted with the whole time the
run waited for it. The tail is a per-layer reading here, with no bound:
at 10 s a stall of the whole process of about 110 ms, in about half of
the runs on the v5e host, moves it between about 83 and 165 ms."""

import numpy as np


def read(ctx):
    if ctx["kind"] != "serve" or not len(ctx["latency_s"]):
        return None
    return float(np.percentile(ctx["latency_s"], 99)) * 1e3
