"""Operations and bytes that an IVF search requires, from the scan's own
counts (``RetrievalEngine``'s ``ivf_*_total`` counters) and its shapes.

As ``counts.py``: what the algorithm needs, not what an implementation
does. The segment scan reads each real row of a probed segment, with its
norm and id, once per batch however many queries probe it (``rows
read``), and scores it once per probing query (``rows probed``). So a
kernel that shares a segment's read among the queries that probe it
cannot read above 100% of this roofline; one that reads it per query, or
scans pad slots, shows lower.
"""

from __future__ import annotations

from harness.counts import F32


def scan_flops(rows_probed: float, d_out: int) -> float:
    """The cross term ``qp . gp`` of every probed row, per query."""
    return 2 * rows_probed * d_out


def scan_bytes(rows_read: float, n_queries: float, d_out: int) -> float:
    """The real rows of the distinct probed segments with their norms and
    ids (f32, int32), and the projected queries."""
    return F32 * (rows_read * (d_out + 2) + n_queries * d_out)


def query_flops(d_in: int, d_out: int, n_clusters: int,
                rows_probed: float) -> float:
    """One served query: its projection through L, its distances to the
    C centroids (the coarse probe) and its probed rows' cross terms."""
    return 2 * d_in * d_out + 2 * n_clusters * d_out + scan_flops(
        rows_probed, d_out)


def per_call(work: dict, nprobe: int) -> dict:
    """The window's counters per device call: rows probed, rows read and
    live queries; None where there was no call."""
    calls = work["calls"]
    if not calls:
        return None
    return {"rows_probed": work["rows_probed"] / calls,
            "rows_read": work["rows_read"] / calls,
            "queries": work["probes"] / nprobe / calls}
