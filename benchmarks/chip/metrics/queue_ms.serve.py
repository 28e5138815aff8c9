"""queue_ms.serve (ms; layer: scheduler, ``serve/scheduler.py``; moves
qps). Mean duration of the program's ``queue`` span (admission to
dequeue) over the requests admitted in the window, from the ``obs``
tracer at sample rate 1."""

from harness import trace_metrics


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    d = trace_metrics.span_durations(ctx["spans"], "queue")
    return 1e3 * sum(d) / len(d) if d else None
