"""compiles.serve (compiles; layer: engine; moves qps). JAX's compile
events (``backend_compile_and_load``, ``backend_compile``) that start in
the serving window of the profiler's trace; each nests inside the
program span that paid for it. 0 where there are none."""

from harness import host_spans


def read(ctx):
    return host_spans.compiles(ctx, "serve")
