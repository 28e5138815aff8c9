"""pairs_per_s (pairs/s, higher is better; host clock): every pair
constraint trained in the window, summed over workers, over the whole
window. The window runs from one logged step of the trainer to another,
both points where the trainer has read the step's loss back, so the
device is drained at either end."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return ctx["pairs"] / ctx["window_s"]
