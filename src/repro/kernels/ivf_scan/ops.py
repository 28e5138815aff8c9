"""Public wrapper for the fused IVF segment scan: validation + dispatch.

``ivf_scan_topk`` is the one entry point serve/ivf.py calls — both for
the single-device query path and (with ``use_kernel=False``) as the
per-shard body inside the sharded shard_map, which is why the XLA
fallback must stay a pure jnp function of its inputs. Chores owned
here, mirroring kernels/pq_adc/ops.py:

  * validation (kk >= 1 and within the probed candidate pool);
  * XLA fallback: the ref oracle chunked over ``block_q`` query rows
    (lax.map keeps the gathered (block_q, nprobe, cap, k) intermediate
    cache-sized — the chunking serve/ivf.py always used);
  * kernel dispatch: pick a tile dividing cap, lay the segments out as
    the kernel takes them (per-query (Nq, 1, k) rows, one (1, bM) row of
    norms and ids per tile), run the fused kernel, mask BIG-sentinel
    survivors to id -1, and apply the final (distance, id) sort.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels._dispatch import map_query_chunks, segment_block
from repro.kernels.metric_topk.kernel import BIG
from repro.kernels.ivf_scan.kernel import ivf_scan_topk_fused
from repro.kernels.ivf_scan.ref import ivf_scan_topk_ref


def ivf_scan_topk(qp, probes, g, gn, ids, *, kk: int, block_q: int = 16,
                  block_m: int = 512, use_kernel: bool = True,
                  interpret=None):
    """Top-kk candidates per query from its probed segments.

    Args:
      qp: (Nq, k) projected queries.
      probes: (Nq, nprobe) int32 probed cluster ids.
      g: (C, cap, k) segment rows; gn: (C, cap) norms (+BIG pads);
        ids: (C, cap) int32 row ids (-1 pads) — the IVF segment layout.
      kk: candidates kept per query (1 <= kk <= nprobe * cap).
      block_q: XLA-path query chunk (lax.map granularity).
      block_m: kernel-path tile rows (rounded to a divisor of cap).
      use_kernel: False routes to the chunked XLA reference (also the
        per-shard body of the sharded path).
      interpret: None compiles on TPU / interprets elsewhere; bool
        forces.

    Returns (dists (Nq, kk) f32 ascending, ids (Nq, kk) int32), sorted
    lexicographically by (distance, id); -1 ids mark under-filled
    probes. Kernel and XLA paths agree on ids exactly and on distances
    to f32 rounding (the k-contraction tree differs — see kernel.py).
    """
    C, cap, k = g.shape
    nprobe = probes.shape[1]
    if kk < 1:
        raise ValueError(f"kk must be >= 1, got {kk}")
    if kk > nprobe * cap:
        raise ValueError(f"kk={kk} > nprobe*cap={nprobe * cap} scanned "
                         f"rows per query")
    if not use_kernel:
        return map_query_chunks(
            lambda q, pr: ivf_scan_topk_ref(q, pr, g, gn, ids, kk),
            (qp, probes), block_q)

    # blocks span k whole (legal at any width), so the segments are
    # never copied to lane-pad it
    bM = segment_block(cap, block_m)
    d, i = ivf_scan_topk_fused(
        probes.astype(jnp.int32), qp.astype(jnp.float32)[:, None, :],
        g.reshape(C * cap, k).astype(jnp.float32),
        gn.reshape(-1, 1, bM), ids.reshape(-1, 1, bM), cap=cap, kk=kk,
        block_m=bM, interpret=interpret)
    d, i = d[:, 0, :], i[:, 0, :]
    # BIG-sentinel survivors are pad slots; the streaming merge may have
    # parked a knocked-out winner's id there — the reference reports -1
    i = jnp.where(d >= BIG, -1, i)
    return jax.lax.sort((d, i), dimension=-1, num_keys=2)
