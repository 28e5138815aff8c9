"""ivf_pad_share.serve (%; layer: IVF probe and segment scan,
``serve/ivf.py``; moves qps). Pad slots over all slots that the window's
IVF batches scanned (real rows of every probe plus pad slots), from the
engine's ``ivf_pad_slots_total`` and ``ivf_rows_probed_total``."""


def read(ctx):
    work = ctx.get("ivf")
    if work is None:
        return None
    slots = work["pad_slots"] + work["rows_probed"]
    return 100.0 * work["pad_slots"] / slots if slots else None
