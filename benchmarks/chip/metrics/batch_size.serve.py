"""batch_size.serve (requests; layer: scheduler; moves qps). Mean live
requests per batch the scheduler dispatched in the window, from the
program's ``frontend_batch_size`` histogram (sum over count)."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    total, n = ctx["batches"]
    return total / n if n else None
