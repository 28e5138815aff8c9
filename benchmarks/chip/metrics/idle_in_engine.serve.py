"""idle_in_engine.serve (%; layer: engine, ``serve/engine.py``; moves
qps). The share of the window in which the device runs no op while the
scheduler's worker is inside the program's ``engine`` span (the profiler
mirror of the ``obs`` span: stacking the batch, the LRU, the pad, the
scan's dispatch and read-back), as against batch formation and the
scheduler's waits; as ``idle_share.serve`` is averaged."""

from harness import host_spans


def read(ctx):
    return host_spans.idle_in(ctx, "serve", "engine")
