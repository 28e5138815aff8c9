"""Public wrappers for the fused metric top-k kernel: layout + fallback.

The serving contract (serve/index.py builds on this):

  * ``project_gallery``  — the once-per-index amortization: gp = G @ L^T and
    its row norms. Everything at query time is O(k)-dimensional.
  * ``tile_gallery``     — the once-per-index layout: the projected rows as
    the scan reads them, padded to ``(slots, kl)`` — ``slots`` a whole
    number of gallery tiles, ``kl`` on a TPU a whole number of lanes —
    and their norms as a ``(1, slots)`` row holding ``+BIG`` in the pad
    slots, so pad slots never enter the top-k.
  * ``metric_topk``      — projects the queries (one XLA matmul), pads them
    to the query tile and runs the Pallas scan (kernel.py) over a tiled
    gallery as it stands: no call copies anything gallery-sized.
  * ``metric_topk_xla``  — the factored pure-XLA path over the same tiled
    gallery.

Both paths share the query projection (default precision) and score
at full f32 precision (docs/kernels.md "Precision on the TPU").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels._dispatch import (HIGHEST, LANE, SUBLANE,
                                     check_metric_factor, matmul_t,
                                     pad_axis, pick_block, round_up)
from repro.kernels.metric_topk.kernel import BIG, metric_topk_fused

GALLERY_TILE = 1024     # gallery slots per kernel step
_TILE_ROWS = 16 * GALLERY_TILE    # rows per block of tile_gallery's copy


def project_gallery(L, gallery):
    """Pre-project the gallery once: returns (gp (M,k) f32, gn (M,) f32).

    This is the index-build step that amortizes the learned metric — after
    it, no query ever touches the d-dimensional space again. ``L`` is
    (d_out, d_in) — square or rectangular — and gp is sized d_out.
    """
    check_metric_factor(L, jnp.shape(gallery)[-1])
    gp = matmul_t(gallery, L)
    gn = jnp.sum(jnp.square(gp), axis=1)
    return gp, gn


def tile_gallery(gp, gn):
    """Lay projected rows out once as the scan reads them.

    Returns (gp (slots, kl) f32, gn (1, slots) f32): ``slots`` rounds M
    up to the gallery tile (to the lane width below one tile); the new
    rows hold zeros and norm ``+BIG``. On a TPU ``kl`` rounds k up to the
    lane width, with zeros in the new columns: the TPU lays an f32
    (M, k) array with k off the lane width out column-major, the kernel
    reads row-major, and (slots, kl) is laid out row-major, so no call
    re-lays it. Elsewhere ``kl`` is k. Rows stay rows: XLA's CPU dot
    sums a row's distance in the same order whatever the row count
    (``pairwise_sqdist_ref``), which a transposed gallery would not.
    """
    k = gp.shape[1]
    lanes = round_up(k, LANE) if jax.default_backend() == "tpu" else k
    return _tile(gp, gn, lanes)


@functools.partial(jax.jit, static_argnames=("lanes",))
def _tile(gp, gn, lanes: int):
    # the rows move over in blocks, each written in place into the
    # result: the build holds the input, the result and one block, not a
    # whole re-laid copy besides
    M = gp.shape[0]
    slots = round_up(M, pick_block(M, GALLERY_TILE, LANE))
    rows = min(M, _TILE_ROWS)

    def put(i, out):
        start = jnp.minimum(i * rows, M - rows)   # the last block overlaps
        block = jax.lax.dynamic_slice_in_dim(gp, start, rows, 0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, pad_axis(block.astype(jnp.float32), lanes, 1), start, 0)

    out = jnp.zeros((slots, lanes), jnp.float32)
    out = jax.lax.fori_loop(0, -(-M // rows), put, out)
    gn = pad_axis(gn.astype(jnp.float32), slots, 0, value=BIG)
    return out, gn[None, :]


@functools.partial(jax.jit, static_argnames=("k_top",))
def metric_topk_xla(L, queries, gp, gn, k_top: int):
    """Factored XLA path over a tiled gallery (``tile_gallery``): project
    queries, reuse the stored norms, lax.top_k. Production path on hosts
    without a Pallas backend. ``k_top`` must not exceed the real rows.

    The cross term is ``pairwise_sqdist_ref``'s over rows padded to a
    whole number of lanes, so a row scores the same bits here as in
    MutableIndex's delta scan and the sharded scan. The queries take the
    gallery's zero columns (on a TPU) and their norms are summed without
    them.
    """
    qp = matmul_t(queries, L)
    qn = jnp.sum(jnp.square(qp), axis=1)
    cross = matmul_t(pad_axis(qp, gp.shape[1], 1), gp, HIGHEST)
    d = jnp.maximum(qn[:, None] + gn - 2.0 * cross, 0.0)
    neg, idx = jax.lax.top_k(-d, k_top)
    return -neg, idx.astype(jnp.int32)


def metric_topk(L, queries, gp, gn, *, k_top: int = 10,
                block_q: int = 128, interpret=None):
    """Top-k gallery neighbors of raw queries under the metric L^T L.

    Args:
      L: (d_out, d_in) metric factor — square or rectangular (low rank).
      queries: (Nq, d_in) raw queries.
      gp, gn: the tiled gallery (``tile_gallery``): (slots, kl) and
        (1, slots). ``k_top`` must not exceed its real rows, or pad
        slots are returned (``ExactIndex.topk`` checks).
      block_q: kernel query tile.
      interpret: None (default) compiles the kernel on TPU and interprets
        elsewhere; pass a bool to force.

    Returns (dists (Nq, k_top) f32 ascending, indices (Nq, k_top) int32).
    """
    Nq, d = queries.shape
    check_metric_factor(L, d)
    slots = gp.shape[0]
    if k_top > slots:
        raise ValueError(f"k_top={k_top} > gallery slots {slots}")
    # query rows pad to the query tile and are sliced back after
    bQ = pick_block(Nq, block_q, SUBLANE)
    qpad = pad_axis(matmul_t(queries, L), round_up(Nq, bQ), 0)
    dists, idxs = metric_topk_fused(qpad, gp, gn, k_top=k_top, block_q=bQ,
                                    block_m=min(GALLERY_TILE, slots),
                                    interpret=interpret)
    return dists[:Nq], idxs[:Nq]
