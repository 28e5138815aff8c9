"""Public wrapper for the fused PQ ADC scan: validation, tiling, dispatch.

``pq_adc_topk`` is the one entry point serve/pq.py calls. It owns the
chores the kernel contract forbids inside kernel.py:

  * **validation** — kk must be >= 1 and fit the probed candidate pool
    (the falsy-default bug class: an explicit 0 raises, never silently
    remaps);
  * **XLA fallback** (``use_kernel=False``) — the ref oracle, chunked
    over ``block_q`` query rows with lax.map so the gathered
    (block_q, nprobe, cap, S) intermediate stays cache-sized (the same
    chunking serve/pq.py always used);
  * **kernel dispatch** — flatten segments, lane-pad the LUTs, pick a
    code tile that divides cap, lay the arrays out as the kernel takes
    them (per-query (Nq, 1, ·) LUT rows, one (1, bM) row of t and ids
    per tile), run the fused kernel, then mask
    BIG-sentinel survivors to id -1 and apply the final (distance, id)
    sort so both paths return byte-identical arrays.

Both paths return bit-identical results — tests/test_scan_kernels.py
pins array equality, not allclose.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels._dispatch import (LANE, map_query_chunks, pad_axis,
                                     round_up, segment_block)
from repro.kernels.metric_topk.kernel import BIG
from repro.kernels.pq_adc.kernel import pq_adc_topk_fused
from repro.kernels.pq_adc.ref import pq_adc_topk_ref


def pq_adc_topk(tables, dc, probes, codes, t, ids, *, kk: int,
                block_q: int = 64, block_m: int = 512,
                use_kernel: bool = True, interpret=None):
    """Top-kk ADC candidates per query from its probed code segments.

    Args:
      tables: (Nq, S*K) flattened per-query LUTs (ProductQuantizer
        ``ip_tables`` reshaped).
      dc: (Nq, nprobe) squared centroid distances of the probed clusters.
      probes: (Nq, nprobe) int32 probed cluster ids.
      codes: (C, cap, S) uint8; t: (C, cap) f32 (+BIG pads);
        ids: (C, cap) int32 (-1 pads) — the IVFPQ segment layout.
      kk: candidates kept per query (1 <= kk <= nprobe * cap).
      block_q: XLA-path query chunk (lax.map granularity).
      block_m: kernel-path code-tile rows (rounded to a divisor of cap).
      use_kernel: False routes to the chunked XLA reference.
      interpret: None compiles on TPU / interprets elsewhere; bool forces.

    Returns (dists (Nq, kk) f32 ascending, ids (Nq, kk) int32), sorted
    lexicographically by (distance, id); -1 ids mark under-filled probes.
    """
    C, cap, S = codes.shape
    nprobe = probes.shape[1]
    if kk < 1:
        raise ValueError(f"kk must be >= 1, got {kk}")
    if kk > nprobe * cap:
        raise ValueError(f"kk={kk} > nprobe*cap={nprobe * cap} scanned "
                         f"rows per query")
    if not use_kernel:
        return map_query_chunks(
            lambda tab, pr, d: pq_adc_topk_ref(tab, d, pr, codes, t, ids,
                                               kk),
            (tables, probes, dc), block_q)

    K = tables.shape[1] // S
    bM = segment_block(cap, block_m)
    tab_pad = pad_axis(tables, round_up(tables.shape[1], LANE), 1)
    d, i = pq_adc_topk_fused(
        probes.astype(jnp.int32), tab_pad[:, None, :],
        dc.astype(jnp.float32), codes.reshape(C * cap, S),
        t.reshape(-1, 1, bM), ids.reshape(-1, 1, bM), n_codes=K, cap=cap,
        kk=kk, block_m=bM, interpret=interpret)
    d, i = d[:, 0, :], i[:, 0, :]
    # entries still at the BIG sentinel are pad slots (real rows cannot
    # reach 1e30) — but the streaming merge may have parked a
    # knocked-out winner's id there; the reference always reports -1
    i = jnp.where(d >= BIG, -1, i)
    return jax.lax.sort((d, i), dimension=-1, num_keys=2)
