"""dispatch_ms.train (ms; layer: trainer host loop, ``core/ps/trainer.py``;
moves pairs_per_s). Mean duration of the program's ``train.step`` span:
the host's call of the jitted step until it returns, that is its dispatch
and, on four chips, the resharding of the stacked batch out of device 0;
over the steps that start in the window, from the profiler's trace."""

from harness import host_spans


def read(ctx):
    return host_spans.span_ms(ctx, "train", "train.step")
