"""engine_host_ms.serve (ms; layer: engine, ``serve/engine.py``; moves
qps). Mean per batch of the program's ``engine`` span minus its
``device_topk`` child: the hot-query LRU hashing, the padding and the
slicing that the engine does on the host around the device call."""

from harness import trace_metrics


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    d = trace_metrics.self_time(ctx["spans"], "engine", "device_topk")
    return 1e3 * sum(d) / len(d) if d else None
