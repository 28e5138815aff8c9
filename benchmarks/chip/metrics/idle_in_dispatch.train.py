"""idle_in_dispatch.train (%; layer: trainer host loop; moves
pairs_per_s). The share of the window in which the device runs no op
while the host is inside the program's ``train.step`` span (the call of
the jitted step, and on four chips the resharding of the batch), per
device over the part of the window its trace recorded, averaged over the
cell's devices as ``idle_share.train`` is."""

from harness import host_spans


def read(ctx):
    return host_spans.idle_in(ctx, "train", "train.step")
