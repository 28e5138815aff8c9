"""Public wrappers for the fused metric top-k kernel: padding + fallback.

The serving contract (serve/index.py builds on this):

  * ``project_gallery``  — the once-per-index amortization: gp = G @ L^T and
    its row norms. Everything at query time is O(k)-dimensional.
  * ``metric_topk``      — projects the queries (one XLA matmul), then
    padded dispatch into the Pallas scan kernel
    (kernel.py); ``use_kernel=False`` routes to the factored XLA path
    instead (there is no automatic shape-based fallback — padding makes
    every shape kernel-tileable).
  * ``metric_topk_xla``  — the factored pure-XLA fast path (also the
    per-shard body inside serve/index.py's shard_map).

Both paths share the query projection (default precision) and score
at full f32 precision (docs/kernels.md "Precision on the TPU").

Padding rules: query rows pad to the query tile (outputs sliced back);
gallery rows pad to the gallery tile with ``gn = +BIG`` sentinels so
they can never enter the top-k. The projection dim k is never padded:
the kernel's blocks span it whole.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels._dispatch import (LANE, SUBLANE, check_metric_factor,
                                     matmul_t, pad_axis, pick_block,
                                     round_up)
from repro.kernels.metric_topk.kernel import BIG, metric_topk_fused
from repro.kernels.metric_topk.ref import metric_topk_ref


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


@functools.partial(jax.jit, static_argnames=("k_top",))
def metric_topk_xla(L, queries, gp, gn, k_top: int):
    """Factored XLA path: project queries, reuse precomputed gallery norms,
    lax.top_k. Production path on hosts without a Pallas backend."""
    return metric_topk_ref(matmul_t(queries, L), gp, k_top, gn)


def metric_topk(L, queries, gp, gn=None, *, k_top: int = 10,
                block_q: int = 128, block_m: int = 1024,
                use_kernel: bool = True, interpret=None):
    """Top-k gallery neighbors of raw queries under the metric L^T L.

    Args:
      L: (d_out, d_in) metric factor — square or rectangular (low rank).
      queries: (Nq, d_in) raw queries.
      gp: (M, d_out) pre-projected gallery (see project_gallery).
      gn: optional (M,) precomputed gp row norms.
      block_q / block_m: kernel query / gallery row tiles.
      interpret: None (default) compiles the kernel on TPU and interprets
        elsewhere; pass a bool to force.

    Returns (dists (Nq, k_top) f32 ascending, indices (Nq, k_top) int32).
    """
    Nq, d = queries.shape
    check_metric_factor(L, d)
    M, k = gp.shape
    if k_top > M:
        raise ValueError(f"k_top={k_top} > gallery size M={M}")
    if gn is None:
        gn = jnp.sum(jnp.square(gp.astype(jnp.float32)), axis=1)
    if not use_kernel:
        return metric_topk_xla(L, queries, gp, gn, k_top)

    # row tiles: queries sliced back after, gallery padded with BIG norms
    # (a copy of gp per call unless M is a multiple of the tile). The
    # projected dim stays whole: a block spanning a full dim is legal at
    # any width, so gp is never copied to lane-pad it.
    bQ = pick_block(Nq, block_q, SUBLANE)
    bM = pick_block(M, block_m, LANE)
    qpad = pad_axis(matmul_t(queries, L), round_up(Nq, bQ), 0)
    gpad = pad_axis(gp.astype(jnp.float32), round_up(M, bM), 0)
    gnpad = pad_axis(gn.astype(jnp.float32), round_up(M, bM), 0, value=BIG)

    dists, idxs = metric_topk_fused(qpad, gpad, gnpad[None, :],
                                    k_top=k_top, block_q=bQ, block_m=bM,
                                    interpret=interpret)
    return dists[:Nq], idxs[:Nq]
