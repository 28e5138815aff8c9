"""Pallas TPU kernel: fused PQ ADC segment scan + streaming top-k.

The IVFPQ serving hot loop (serve/pq.py): per query, gather the uint8
code blocks of its ``nprobe`` probed segments, accumulate the
per-subspace LUT inner products, apply the ADC identity

    d = max(d_cent + t - 2 * sum_s LUT[s, code_s], 0)

and stream-merge a running top-kk — without ever materializing the
(block_q, nprobe, cap, S) code gather in HBM that the XLA path pays.

Grid: (Nq, nprobe * nsteps) with one query per program row and the
probe/tile stream innermost, so the running (1, kk) best buffers live
in VMEM scratch across a query's whole stream. The probed-segment
gather is the part XLA cannot fuse: the probe list rides in as a
**scalar-prefetch** operand (pltpu.PrefetchScalarGridSpec), so the
code/t/id block index maps read ``probes[q, p]`` before the body runs
and the right (bM, S) code tile is DMA'd per step — codes stream
through VMEM exactly once. The probes' centroid distances ride in SMEM
the same way, one scalar per (query, probe). Per-query LUT rows, and
each tile's t and id rows, travel as (n, 1, ·) arrays whose (None, 1, ·)
blocks equal their last two dims — a layout the TPU lowering accepts at
any width.

The LUT accumulate is S one-hot matmuls: for subspace s, onehot(codes
column s) is (bM, K) and ``LUT_s @ onehot^T`` picks tab[s*K + code] per
row on the MXU. Each term is **exact** in f32 (one 1.0 * entry product,
all other lanes contribute exact zeros regardless of the reduction
tree), and terms accumulate sequentially in subspace order — the two
properties that make the kernel bit-identical to ref.py, which fixes
the same summation order (ops.py asserts nothing weaker).

Tile order matches the reference's probe-major / slot-minor candidate
flattening, so position-order tie-breaks agree with lax.top_k. The
best-index scratch initializes to -1 (not 0): entries still at the BIG
sentinel when the stream ends must be indistinguishable from real
(BIG, -1) pad-slot candidates — ops.py masks ids at BIG to -1 for the
same reason (the merge can re-surface a knocked-out winner's position
once only BIG candidates remain).

TPU tuning caveat: the (bM, S) uint8 code tile has S lanes (typically
8-16), far below the (32, 128) minimum uint8 tile — compiled-mode
layouts will pad lanes internally. Interpret mode (the CPU test path)
is exact regardless; lane-efficient code packing is tuning work
(ROADMAP S6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._dispatch import default_interpret
from repro.kernels.metric_topk.kernel import BIG, _merge_topk


def _pq_adc_kernel(probes_ref, dc_ref, tab_ref, codes_ref, t_ref, ids_ref,
                   od_ref, oi_ref, bd_ref, bi_ref,
                   *, n_codes: int, kk: int, nsteps: int):
    q = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _reset():
        bd_ref[...] = jnp.full(bd_ref.shape, BIG, jnp.float32)
        bi_ref[...] = jnp.full(bi_ref.shape, -1, jnp.int32)

    codes = codes_ref[...].astype(jnp.int32)             # (bM, S)
    tab = tab_ref[...]                                   # (1, SKpad)
    bM, S = codes.shape
    K = n_codes
    code_iota = jax.lax.broadcasted_iota(jnp.int32, (bM, K), 1)
    ip = None
    for s in range(S):          # sequential accumulate: ref.py order
        onehot = (code_iota == codes[:, s][:, None]).astype(jnp.float32)
        term = jax.lax.dot_general(                      # (1, bM)
            tab[:, s * K:(s + 1) * K], onehot,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ip = term if ip is None else ip + term
    dc = dc_ref[q, j // nsteps]                          # SMEM scalar
    d = jnp.maximum(dc + t_ref[...] - 2.0 * ip, 0.0)     # (1, bM)

    bd, bi = _merge_topk(bd_ref[...], bi_ref[...], d, ids_ref[...], kk)
    bd_ref[...] = bd
    bi_ref[...] = bi

    @pl.when(j == pl.num_programs(1) - 1)
    def _epilogue():
        od_ref[...] = bd_ref[...]
        oi_ref[...] = bi_ref[...]


@functools.partial(jax.jit, static_argnames=("n_codes", "cap", "kk",
                                             "block_m", "interpret"))
def pq_adc_topk_fused(probes, tables, dc, codes, t, ids, *, n_codes: int,
                      cap: int, kk: int, block_m: int,
                      interpret=None):
    """Fused ADC scan + streaming top-k over probed code segments.

    Args:
      probes: (Nq, nprobe) int32 probed cluster ids (scalar-prefetch).
      tables: (Nq, 1, SKpad) flattened LUTs, lane-padded with zeros past
        S * n_codes (the per-subspace slices never read the pad).
      dc: (Nq, nprobe) f32 squared centroid distances of the probes
        (scalar-prefetch).
      codes: (C*cap, S) uint8 segment codes; t: (C*cap/block_m, 1,
        block_m) f32 row terms (+BIG on pads); ids: the same shape in
        int32 (-1 on pads), one (1, block_m) row per tile.
      n_codes: codewords per subspace (K = 2**bits).
      cap: rows per segment; block_m: rows per code tile, must divide
        cap evenly (ops.py picks it).
      interpret: None compiles on TPU and interprets elsewhere.

    Returns (dists (Nq, 1, kk) f32, ids (Nq, 1, kk) int32) in
    streaming-merge order (ascending distance); ids at the BIG sentinel
    may repeat a knocked-out winner — ops.py masks them to -1 before the
    final sort.
    """
    Nq, nprobe = probes.shape
    rows, S = codes.shape
    bM = block_m
    assert cap % bM == 0 and rows % cap == 0, (rows, cap, bM)
    assert t.shape == ids.shape == (rows // bM, 1, bM), (t.shape, bM)
    assert kk <= nprobe * cap, (kk, nprobe, cap)
    nsteps = cap // bM          # tiles per probed segment

    def seg_row(q, j, pr):      # flat tile index of stream step j
        return pr[q, j // nsteps] * nsteps + j % nsteps

    kernel = functools.partial(_pq_adc_kernel, n_codes=n_codes, kk=kk,
                               nsteps=nsteps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Nq, nprobe * nsteps),
        in_specs=[
            pl.BlockSpec((None, 1, tables.shape[2]),
                         lambda q, j, pr, dc: (q, 0, 0)),     # LUTs
            pl.BlockSpec((bM, S),
                         lambda q, j, pr, dc: (seg_row(q, j, pr), 0)),
            pl.BlockSpec((None, 1, bM),
                         lambda q, j, pr, dc: (seg_row(q, j, pr), 0, 0)),
            pl.BlockSpec((None, 1, bM),
                         lambda q, j, pr, dc: (seg_row(q, j, pr), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, kk), lambda q, j, pr, dc: (q, 0, 0)),
            pl.BlockSpec((None, 1, kk), lambda q, j, pr, dc: (q, 0, 0)),
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
        name="pq_adc",
    )(probes, dc, tables, codes, t, ids)
