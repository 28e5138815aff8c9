"""Operations and bytes that the measured work requires, from its shapes.

These are the numerators of every utilisation and roofline share. They
count what the algorithm needs, not what an implementation happens to do:
a copy, a pad or a recomputation adds time but no count, so it shows as a
lower share. All counts are per call, in FLOPs (a multiply-add is 2) and
bytes of HBM traffic at 4 bytes per f32.
"""

from __future__ import annotations

F32 = 4


def train_step_flops(pairs: int, d_in: int, d_out: int) -> int:
    """One Eq. 4 SGD step over ``pairs`` pairs: the forward ``z L^T``
    (2 d_in d_out per pair) and the weight gradient ``(w * Lz)^T z``
    (2 d_in d_out per pair). No gradient is taken w.r.t. the data, and the
    elementwise hinge and update are O(d_out) per pair, left out."""
    return 4 * pairs * d_in * d_out


def train_step_bytes(pairs: int, d_in: int, d_out: int) -> int:
    """The least HBM traffic of one step: read both sides of every pair,
    read L and write the updated L."""
    return F32 * (2 * pairs * d_in + 2 * d_out * d_in)


def topk_scan_flops(n_queries: int, gallery_rows: int, d_out: int) -> int:
    """The exact scan's cross term ``qp . gp`` for every (query, row)."""
    return 2 * n_queries * gallery_rows * d_out


def topk_scan_bytes(n_queries: int, gallery_rows: int, d_out: int) -> int:
    """The exact scan reads the projected gallery and its norms once and
    the projected queries once."""
    return F32 * (gallery_rows * d_out + gallery_rows + n_queries * d_out)


def query_flops(d_in: int, d_out: int, gallery_rows: int) -> int:
    """One served query: its projection through L and its exact scan."""
    return 2 * d_in * d_out + topk_scan_flops(1, gallery_rows, d_out)


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  peak_bytes: float):
    """(the least time on the chip, the bound that sets it)."""
    t_flops, t_bytes = flops / peak_flops, nbytes / peak_bytes
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
