"""Fused IVF segment scan: probed gather + factored distance + top-k.

Kernel/ops/ref contract (docs/kernels.md): ``ops.ivf_scan_topk`` is the
public dispatcher; ``kernel.ivf_scan_topk_fused`` the raw Pallas call;
``ref.ivf_scan_topk_ref`` the XLA oracle serve/ivf.py scans with;
``ref.ivf_search_ref`` the plain oracle of the whole search (projection,
probe and exact top-k over the probed lists) from raw rows.
"""

from repro.kernels.ivf_scan.kernel import ivf_scan_topk_fused
from repro.kernels.ivf_scan.ops import ivf_scan_topk
from repro.kernels.ivf_scan.ref import ivf_scan_topk_ref, ivf_search_ref

__all__ = ["ivf_scan_topk", "ivf_scan_topk_fused", "ivf_scan_topk_ref",
           "ivf_search_ref"]
