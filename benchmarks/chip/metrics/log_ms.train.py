"""log_ms.train (ms; layer: trainer host loop, ``core/ps/trainer.py``;
moves pairs_per_s). Mean duration of the program's ``train.log`` span per
logged step (every ``log_every``-th): the read-back of the step's metrics,
which waits for the device, the merge of the workers' factors and the
``step_hook``; over the logged steps that start in the window."""

from harness import host_spans


def read(ctx):
    return host_spans.span_ms(ctx, "train", "train.log")
