"""Pallas TPU kernel: fused IVF segment gather + factored distance + top-k.

The IVF serving hot loop (serve/ivf.py): per query, gather the
full-precision rows of its ``nprobe`` probed segments, score them with
the factored squared distance, and stream-merge a running top-kk —
without materializing the (block_q, nprobe, cap, k) segment gather the
XLA path pays for in HBM.

Same skeleton as kernels/pq_adc: grid (Nq, nprobe * nsteps), one query
per program row, probe/tile stream innermost, probe list as a
scalar-prefetch operand so the gp/gn/id block index maps DMA the right
(bM, k) segment tile per step, running (1, kk) best buffers in VMEM
scratch, best-index init -1 (BIG-sentinel survivors must look like real
pad candidates; ops.py masks and re-sorts). Per-query rows, and each
tile's row norms and ids, travel as (n, 1, ·) arrays whose (None, 1, ·)
blocks equal their last two dims — a layout the TPU lowering accepts at
any tile width. The only body difference is
the score: an MXU dot of the (1, k) query row against the (bM, k) tile
replaces the one-hot LUT accumulate — which also means the contraction
over k is a genuine reduction, so distances match the XLA reference to
rounding, not bitwise (pq_adc's per-term-exact trick has no analogue
here; metric_topk has the same property).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._dispatch import HIGHEST, default_interpret
from repro.kernels.metric_topk.kernel import BIG, _merge_topk


def _ivf_scan_kernel(probes_ref, qp_ref, g_ref, gn_ref, ids_ref,
                     od_ref, oi_ref, bd_ref, bi_ref, *, kk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _reset():
        bd_ref[...] = jnp.full(bd_ref.shape, BIG, jnp.float32)
        bi_ref[...] = jnp.full(bi_ref.shape, -1, jnp.int32)

    qp = qp_ref[...]                                     # (1, k)
    qn = jnp.sum(jnp.square(qp), axis=1, keepdims=True)  # (1, 1)
    cross = jax.lax.dot_general(                         # (1, bM)
        qp, g_ref[...], (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    d = jnp.maximum(qn + gn_ref[...] - 2.0 * cross, 0.0)

    bd, bi = _merge_topk(bd_ref[...], bi_ref[...], d, ids_ref[...], kk)
    bd_ref[...] = bd
    bi_ref[...] = bi

    @pl.when(j == pl.num_programs(1) - 1)
    def _epilogue():
        od_ref[...] = bd_ref[...]
        oi_ref[...] = bi_ref[...]


@functools.partial(jax.jit, static_argnames=("cap", "kk", "block_m",
                                             "interpret"))
def ivf_scan_topk_fused(probes, qp, g, gn, ids, *, cap: int, kk: int,
                        block_m: int, interpret=None):
    """Fused probed-segment scan + streaming top-k.

    Args:
      probes: (Nq, nprobe) int32 probed cluster ids (scalar-prefetch).
      qp: (Nq, 1, k) projected queries.
      g: (C*cap, k) segment rows;
        gn: (C*cap/block_m, 1, block_m) row norms (+BIG pads) and ids
        the same shape in int32 (-1 pads), one (1, block_m) row per tile.
      cap: rows per segment; block_m: rows per tile, must divide cap.
      interpret: None compiles on TPU and interprets elsewhere.

    Returns (dists (Nq, 1, kk) f32, ids (Nq, 1, kk) int32) in
    streaming-merge order; ids at the BIG sentinel may repeat a
    knocked-out winner — ops.py masks them to -1 before the final sort.
    """
    Nq, nprobe = probes.shape
    rows, k = g.shape
    assert qp.shape == (Nq, 1, k), (qp.shape, Nq, k)
    bM = block_m
    assert cap % bM == 0 and rows % cap == 0, (rows, cap, bM)
    assert gn.shape == ids.shape == (rows // bM, 1, bM), (gn.shape, bM)
    assert kk <= nprobe * cap, (kk, nprobe, cap)
    nsteps = cap // bM          # tiles per probed segment

    def seg_row(q, j, pr):      # flat tile index of stream step j
        return pr[q, j // nsteps] * nsteps + j % nsteps

    kernel = functools.partial(_ivf_scan_kernel, kk=kk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Nq, nprobe * nsteps),
        in_specs=[
            pl.BlockSpec((None, 1, k),
                         lambda q, j, pr: (q, 0, 0)),         # qp row
            pl.BlockSpec((bM, k),
                         lambda q, j, pr: (seg_row(q, j, pr), 0)),
            pl.BlockSpec((None, 1, bM),
                         lambda q, j, pr: (seg_row(q, j, pr), 0, 0)),
            pl.BlockSpec((None, 1, bM),
                         lambda q, j, pr: (seg_row(q, j, pr), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, kk), lambda q, j, pr: (q, 0, 0)),
            pl.BlockSpec((None, 1, kk), lambda q, j, pr: (q, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, kk), jnp.float32),   # running best distances
            pltpu.VMEM((1, kk), jnp.int32),     # running best ids
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Nq, 1, kk), jnp.float32),
            jax.ShapeDtypeStruct((Nq, 1, kk), jnp.int32),
        ],
        interpret=default_interpret(interpret),
        name="ivf_scan",
    )(probes, qp, g, gn, ids)
