"""qps (queries/s, higher is better; host clock): requests completed
inside the window, over the window."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return ctx["completed_in_window"] / ctx["window_s"]
