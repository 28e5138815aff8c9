"""Pure-XLA oracle for the fused IVF full-precision segment scan.

The semantics serve/ivf.py's probed scan and the Pallas kernel
(kernel.py) both implement: gather each query's probed full-precision
segments, score them with the factored squared distance

    d = max(||qp||² + gn - 2 <qp, gp_row>, 0)

and keep the kk best (distance, id) candidates. Candidates flatten
probe-major / slot-minor — the order the kernel streams tiles in — so
position-order tie-breaks agree. Unlike pq_adc, the contraction over k
is a real reduction (XLA einsum vs MXU dot tree orders can differ), so
the kernel contract here is indices-equal / distances-allclose, not
bitwise (tests/test_scan_kernels.py pins exactly that).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels._dispatch import HIGHEST, topk_by_distance


def ivf_scan_topk_ref(qp, probes, g, gn, ids, kk: int):
    """Score the probed segments of each query and keep the top kk.

    Args:
      qp: (Nq, k) projected queries.
      probes: (Nq, nprobe) int32 probed cluster ids (``mode="clip"`` on
        the gather, so an out-of-range sentinel cluster — the sharded
        path's all-pad slot C_loc — reads the last real segment safely
        only when callers append one; in-range ids are unaffected).
      g: (C, cap, k) segment rows (0 on pad slots).
      gn: (C, cap) row norms (+BIG on pad slots).
      ids: (C, cap) int32 global row ids (-1 on pad slots).
      kk: candidates kept per query (<= nprobe * cap).

    Returns (dists (Nq, kk) f32 ascending, ids (Nq, kk) int32), sorted
    lexicographically by (distance, id); -1 ids mark under-filled
    probes.
    """
    gg = jnp.take(g, probes, axis=0, mode="clip")    # (Nq, np, cap, k)
    gng = jnp.take(gn, probes, axis=0, mode="clip")  # (Nq, np, cap)
    idg = jnp.take(ids, probes, axis=0, mode="clip")
    qn = jnp.sum(jnp.square(qp), axis=1)
    cross = jnp.einsum("qpck,qk->qpc", gg, qp, precision=HIGHEST)
    d = jnp.maximum(qn[:, None, None] + gng - 2.0 * cross, 0.0)
    Nq = qp.shape[0]
    return topk_by_distance(d.reshape(Nq, -1), idg.reshape(Nq, -1), kk)
