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

import jax
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


def ivf_search_ref(L, queries, gallery, centroids, assign, nprobe: int,
                   k_top: int):
    """The whole IVF search, written plainly: the oracle for
    ``IVFIndex.topk`` from raw rows.

    In f32 at ``highest`` matmul precision, with no kernel, layout, cache
    or batching: project the queries and the gallery through ``L``, take
    each query's ``nprobe`` nearest centroids (ties to the smaller list
    id), and return the exact top ``k_top`` over the members of those
    lists, with distances from the rows projected here (summed from the
    difference), not from an index's stored rows.

    Args:
      L: (d_out, d_in) metric factor.
      queries: (Nq, d_in) raw queries; gallery: (M, d_in) raw rows.
      centroids: (C, d_out) the index's list centroids.
      assign: (M,) int32 list of each gallery row (an index's learned
        membership, given like weights).

    Returns (dists (Nq, k_top) ascending, ids (Nq, k_top)); ties go to the
    smaller id; -1 ids (and +inf distances) where the probed lists hold
    fewer than ``k_top`` rows.
    """
    with jax.default_matmul_precision("highest"):
        qp = jnp.asarray(queries, jnp.float32) @ jnp.asarray(L, jnp.float32).T
        gp = jnp.asarray(gallery, jnp.float32) @ jnp.asarray(L, jnp.float32).T
        cd = jnp.sum(jnp.square(qp[:, None, :] - centroids[None]), axis=-1)
        _, probes = jax.lax.top_k(-cd, nprobe)                 # (Nq, np)
        member = jnp.any(jnp.asarray(assign)[None, :, None]
                         == probes[:, None, :], axis=-1)       # (Nq, M)
        d = jnp.sum(jnp.square(qp[:, None, :] - gp[None]), axis=-1)
        d = jnp.where(member, d, jnp.inf)
        ids = jnp.broadcast_to(jnp.arange(gp.shape[0], dtype=jnp.int32),
                               d.shape)
        neg, pos = jax.lax.top_k(-d, k_top)      # ties: the earlier row
        dists = -neg
        return dists, jnp.where(jnp.isinf(dists), -1,
                                jnp.take_along_axis(ids, pos, axis=1))
