"""batch_ms.train (ms; layer: trainer host loop, ``core/ps/trainer.py``
``train_dml_distributed``; moves pairs_per_s). Mean duration of the
program's ``train.batch`` span, the trainer's ``next(batches)``: every
worker's pair draw (``train.draw``) and gathers (``train.gather``) and
the stacking of their batches (``train.stack``), over the steps that
start in the window, from the profiler's trace."""

from harness import host_spans


def read(ctx):
    return host_spans.span_ms(ctx, "train", "train.batch")
