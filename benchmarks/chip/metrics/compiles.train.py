"""compiles.train (compiles; layer: trainer host loop; moves pairs_per_s).
JAX's compile events (``backend_compile_and_load``, ``backend_compile``)
that start in the training window of the profiler's trace; each nests
inside the program span that paid for it. 0 where there are none."""

from harness import host_spans


def read(ctx):
    return host_spans.compiles(ctx, "train")
