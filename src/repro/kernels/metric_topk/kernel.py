"""Pallas TPU kernel: fused metric-space distance + streaming top-k.

The serving hot path: given queries already projected into the metric
space (``qp = q @ L^T`` (Nq, k), one XLA matmul in ops.py) and a gallery
that was pre-projected and laid out **once** at index build time
(``gp = G @ L^T`` (M, kl), ``gn = ||gp||^2`` (1, M); ops.py
``tile_gallery``), compute per query the k_top nearest gallery rows under
the Mahalanobis metric ``M = L^T L`` — in one pass, without ever
materializing the (Nq, M) distance matrix in HBM:

    D[:, j]  = ||qp||^2 + gn_j - 2 qp . gp_j (per (bQ, bM) gallery tile)
    best     = stream-merge(best, D tile)    (running top-k in VMEM)

The query projection stays outside the kernel: at the paper's widths L
is (1000, 21,504) f32, 86 MB, far more than VMEM holds, and sharing the
XLA projection with the reference path keeps the two paths' qp
identical.

Grid: (Nq/bQ, M/bM) — gallery innermost, so the query tile and the
running (bQ, k_top) best-distance/best-index buffers live in VMEM across
the whole gallery sweep; outputs are written on the last gallery step.
The merge is k_top rounds of (min, argmin, one-hot mask) over the
(bQ, k_top + bM) candidate row — pure VPU ops, no sort network — which
is cheap because k_top << bM. Row norms ride in as a (1, M) lane-major
row so each (1, bM) tile broadcasts straight against the (bQ, bM)
cross term.

Tie-breaking matches ``jax.lax.top_k``: equal distances resolve to the
smaller gallery index (earlier tiles sit first in the candidate row;
within a tile the index iota ascends; argmin takes the first minimum).

The gallery arrives tiled (ops.py ``tile_gallery``): rows padded to the
tile with ``gn = +BIG`` sentinels, so pad rows can never enter the top-k,
and on a TPU columns padded with zeros to ``kl``, a whole number of
lanes. The TPU lays an f32 (M, k) array with k off the lane width out
column-major, and a kernel operand in that layout is copied whole to
row-major on every call; (M, kl) arrives row-major. Blocks span kl
whole; the queries' columns are padded to kl here, inside the call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._dispatch import HIGHEST, default_interpret, pad_axis

# Sentinel "infinite" distance for padded gallery rows / best-buffer init.
# Large enough to lose to any real squared distance, small enough that
# qn + BIG stays finite in float32.
BIG = 1e30


def _merge_topk(bd, bi, d, gidx, k_top: int):
    """Stream-merge a distance tile into the running top-k.

    bd (bQ, k_top) f32 ascending, bi (bQ, k_top) i32, d (bQ, bM) f32,
    gidx (bQ, bM) i32 global gallery indices. Returns new (bd, bi).
    """
    cd = jnp.concatenate([bd, d], axis=1)               # (bQ, k_top + bM)
    ci = jnp.concatenate([bi, gidx], axis=1)
    pos_iota = jax.lax.broadcasted_iota(jnp.int32, cd.shape, 1)
    new_d, new_i = [], []
    for _ in range(k_top):
        m = jnp.min(cd, axis=1)                         # (bQ,)
        pos = jnp.argmin(cd, axis=1).astype(jnp.int32)  # first min = low idx
        hit = pos_iota == pos[:, None]                  # (bQ, k_top + bM)
        new_d.append(m)
        new_i.append(jnp.sum(jnp.where(hit, ci, 0), axis=1))
        cd = jnp.where(hit, BIG, cd)                    # knock out the winner
    return jnp.stack(new_d, axis=1), jnp.stack(new_i, axis=1)


def _metric_topk_kernel(qp_ref, gp_ref, gn_ref,
                        od_ref, oi_ref,
                        bd_ref, bi_ref,
                        *, k_top: int, nm: int, block_m: int):
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _reset():
        bd_ref[...] = jnp.full(bd_ref.shape, BIG, jnp.float32)
        bi_ref[...] = jnp.zeros(bi_ref.shape, jnp.int32)

    qp = qp_ref[...]                                     # (bQ, k)
    qn = jnp.sum(jnp.square(qp), axis=1, keepdims=True)  # (bQ, 1)
    cross = jax.lax.dot_general(
        qp, gp_ref[...], (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    d = jnp.maximum(qn + gn_ref[...] - 2.0 * cross, 0.0)  # (bQ, bM)
    gidx = (mi * block_m
            + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1))

    bd, bi = _merge_topk(bd_ref[...], bi_ref[...], d, gidx, k_top)
    bd_ref[...] = bd
    bi_ref[...] = bi

    @pl.when(mi == nm - 1)
    def _epilogue():
        od_ref[...] = bd_ref[...]
        oi_ref[...] = bi_ref[...]


@functools.partial(jax.jit, static_argnames=("k_top", "block_q", "block_m",
                                             "interpret"))
def metric_topk_fused(qp, gp, gn, *, k_top: int = 10,
                      block_q: int = 128, block_m: int = 1024,
                      interpret=None):
    """Fused distance + streaming top-k over a pre-projected gallery.

    Args:
      qp: (Nq, k) f32 projected queries.
      gp: (M, kl) f32 pre-projected gallery rows, kl >= k (zero columns
        past k).
      gn: (1, M) f32 squared norms of gp rows (+BIG for padded rows).
      interpret: None compiles on TPU and interprets elsewhere.

    Shapes must tile evenly (ops.py pads queries, ``tile_gallery`` the
    gallery): Nq % block_q == 0 and M % block_m == 0. Returns
    (dists (Nq, k_top) f32 ascending, indices (Nq, k_top) int32).
    """
    M, k = gp.shape
    qp = pad_axis(qp, k, 1)
    Nq = qp.shape[0]
    bQ, bM = min(block_q, Nq), min(block_m, M)
    assert Nq % bQ == 0 and M % bM == 0, (Nq, M, bQ, bM)
    assert gn.shape == (1, M), (gn.shape, M)
    assert k_top <= M, (k_top, M)
    nm = M // bM

    kernel = functools.partial(_metric_topk_kernel, k_top=k_top, nm=nm,
                               block_m=bM)
    return pl.pallas_call(
        kernel,
        grid=(Nq // bQ, nm),
        in_specs=[
            pl.BlockSpec((bQ, k), lambda i, j: (i, 0)),     # qp
            pl.BlockSpec((bM, k), lambda i, j: (j, 0)),     # gp
            pl.BlockSpec((1, bM), lambda i, j: (0, j)),     # gn
        ],
        out_specs=[
            pl.BlockSpec((bQ, k_top), lambda i, j: (i, 0)),
            pl.BlockSpec((bQ, k_top), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Nq, k_top), jnp.float32),
            jax.ShapeDtypeStruct((Nq, k_top), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bQ, k_top), jnp.float32),   # running best distances
            pltpu.VMEM((bQ, k_top), jnp.int32),     # running best indices
        ],
        interpret=default_interpret(interpret),
        name="metric_topk",
    )(qp, gp, gn)
