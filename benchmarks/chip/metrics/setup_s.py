"""setup_s (s, lower is better; host clock): process start to the first
timed step or request. Loading, making the data and weights, compiling or
loading every program from the cache, and warming up all count."""


def read(ctx):
    return ctx["setup_s"]
