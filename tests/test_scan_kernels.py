"""Parity tests for the fused segment-scan kernels (pq_adc, ivf_scan).

Contract (docs/kernels.md): ``pq_adc_topk`` returns **bit-identical**
arrays on its kernel and XLA paths (the sequential-subspace-sum
reference fixes the rounding order, so array_equal on distances is the
assertion, not allclose); ``ivf_scan_topk`` matches on indices exactly
and on distances to f32 rounding (its k-contraction tree differs
between paths). Ragged shapes are the point: segment fill below
capacity, capacity not a multiple of the tile, kk larger than any
single segment's real rows, and the full 1..8-bit code range.

Kernels run in interpret mode here (CPU CI) — the same kernel logic the
TPU path compiles, minus the mosaic lowering.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels._dispatch import topk_by_distance
from repro.kernels.ivf_scan import ivf_scan_topk
from repro.kernels.metric_topk.kernel import BIG
from repro.kernels.pq_adc import pq_adc_topk


def _segments(rng, C, cap, fill_lo, fill_hi):
    """Random per-cluster fills (possibly empty segments) + global ids."""
    fills = rng.randint(fill_lo, fill_hi + 1, size=C)
    ids = np.full((C, cap), -1, np.int32)
    nid = 0
    for c in range(C):
        ids[c, :fills[c]] = np.arange(nid, nid + fills[c])
        nid += fills[c]
    return fills, ids


def _pq_case(seed, Nq, C, cap, S, bits, nprobe, fill_lo, fill_hi):
    rng = np.random.RandomState(seed)
    K = 1 << bits
    fills, ids = _segments(rng, C, cap, fill_lo, fill_hi)
    codes = np.zeros((C, cap, S), np.uint8)
    t = np.full((C, cap), BIG, np.float32)
    for c in range(C):
        n = fills[c]
        codes[c, :n] = rng.randint(0, K, (n, S))
        t[c, :n] = rng.randn(n).astype(np.float32)
    tables = rng.randn(Nq, S * K).astype(np.float32)
    dc = np.abs(rng.randn(Nq, nprobe)).astype(np.float32)
    probes = np.stack([rng.choice(C, nprobe, replace=False)
                       for _ in range(Nq)]).astype(np.int32)
    return (jnp.asarray(tables), jnp.asarray(dc), jnp.asarray(probes),
            jnp.asarray(codes), jnp.asarray(t), jnp.asarray(ids))


def _ivf_case(seed, Nq, C, cap, k, nprobe, fill_lo, fill_hi):
    rng = np.random.RandomState(seed)
    fills, ids = _segments(rng, C, cap, fill_lo, fill_hi)
    g = np.zeros((C, cap, k), np.float32)
    gn = np.full((C, cap), BIG, np.float32)
    for c in range(C):
        n = fills[c]
        g[c, :n] = rng.randn(n, k).astype(np.float32)
        gn[c, :n] = np.sum(g[c, :n] ** 2, axis=1)
    qp = rng.randn(Nq, k).astype(np.float32)
    probes = np.stack([rng.choice(C, nprobe, replace=False)
                       for _ in range(Nq)]).astype(np.int32)
    return (jnp.asarray(qp), jnp.asarray(probes), jnp.asarray(g),
            jnp.asarray(gn), jnp.asarray(ids))


# (Nq, C, cap, S, bits, nprobe, kk, block_m, fill_lo, fill_hi)
PQ_CASES = [
    # multi-tile segments, full fill
    (5, 6, 32, 4, 8, 3, 7, 16, 32, 32),
    # cap not a multiple of the tile -> whole-segment tile fallback
    (3, 5, 24, 3, 8, 2, 5, 16, 10, 24),
    # kk exceeds any single segment's real rows (sentinels surface)
    (4, 7, 16, 2, 8, 2, 32, 8, 0, 5),
    # 1-bit and 2-bit codes (K = 2, 4)
    (3, 4, 16, 5, 1, 2, 6, 8, 8, 16),
    (3, 4, 16, 5, 2, 2, 6, 8, 8, 16),
    # kk == the whole candidate pool, odd subspace count
    (2, 4, 8, 3, 4, 3, 24, 8, 2, 8),
]


@pytest.mark.parametrize(
    "Nq,C,cap,S,bits,nprobe,kk,block_m,fill_lo,fill_hi", PQ_CASES)
def test_pq_adc_kernel_bit_identical(Nq, C, cap, S, bits, nprobe, kk,
                                     block_m, fill_lo, fill_hi):
    args = _pq_case(0, Nq, C, cap, S, bits, nprobe, fill_lo, fill_hi)
    d_x, i_x = pq_adc_topk(*args, kk=kk, block_q=2, block_m=block_m,
                           use_kernel=False)
    d_k, i_k = pq_adc_topk(*args, kk=kk, block_q=2, block_m=block_m,
                           use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_k))
    np.testing.assert_array_equal(np.asarray(d_x), np.asarray(d_k))


# (Nq, C, cap, k, nprobe, kk, block_m, fill_lo, fill_hi)
IVF_CASES = [
    (5, 6, 32, 12, 3, 7, 16, 32, 32),          # multi-tile, full fill
    (3, 5, 24, 8, 2, 5, 16, 10, 24),           # cap % tile != 0
    (4, 7, 16, 5, 2, 32, 8, 0, 5),             # kk > real segment rows
    (2, 4, 8, 130, 3, 24, 8, 2, 8),            # k > one lane, full pool
]


@pytest.mark.parametrize("Nq,C,cap,k,nprobe,kk,block_m,fill_lo,fill_hi",
                         IVF_CASES)
def test_ivf_scan_kernel_parity(Nq, C, cap, k, nprobe, kk, block_m,
                                fill_lo, fill_hi):
    args = _ivf_case(0, Nq, C, cap, k, nprobe, fill_lo, fill_hi)
    d_x, i_x = ivf_scan_topk(*args, kk=kk, block_q=2, block_m=block_m,
                             use_kernel=False)
    d_k, i_k = ivf_scan_topk(*args, kk=kk, block_q=2, block_m=block_m,
                             use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_k))
    np.testing.assert_allclose(np.asarray(d_x), np.asarray(d_k),
                               rtol=1e-4, atol=1e-4)


def test_pq_adc_rejects_bad_kk():
    args = _pq_case(1, 2, 4, 8, 2, 4, 2, 8, 8)
    for kk in (0, -3):
        with pytest.raises(ValueError, match="kk"):
            pq_adc_topk(*args, kk=kk)
    with pytest.raises(ValueError, match="kk"):
        pq_adc_topk(*args, kk=2 * 8 + 1)       # > nprobe * cap


def test_ivf_scan_rejects_bad_kk():
    args = _ivf_case(1, 2, 4, 8, 6, 2, 0, 8)
    for kk in (0, -3):
        with pytest.raises(ValueError, match="kk"):
            ivf_scan_topk(*args, kk=kk)
    with pytest.raises(ValueError, match="kk"):
        ivf_scan_topk(*args, kk=2 * 8 + 1)


def test_pq_adc_sentinels_masked_to_minus_one():
    # a nearly-empty gallery: most returned slots must be (BIG-ish, -1),
    # never a duplicated real id (the streaming-merge knockout hazard)
    args = _pq_case(2, 3, 4, 8, 3, 4, 2, 0, 1)
    d_k, i_k = pq_adc_topk(*args, kk=12, use_kernel=True, interpret=True)
    i_k = np.asarray(i_k)
    d_k = np.asarray(d_k)
    for q in range(i_k.shape[0]):
        real = i_k[q][i_k[q] >= 0]
        assert len(real) == len(set(real.tolist())), \
            f"duplicate real ids in query {q}: {i_k[q]}"
    assert (i_k[d_k >= BIG] == -1).all()


class TestTopkContractProperty:
    """Hypothesis: the kernel's output equals the one tie-break contract
    (scan.topk_by_distance over the brute-force candidate matrix)."""

    @pytest.fixture(autouse=True)
    def _hyp(self):
        pytest.importorskip("hypothesis", reason="hypothesis not "
                            "installed (pip install -r "
                            "requirements-dev.txt)")

    def test_pq_adc_matches_topk_by_distance(self):
        from hypothesis import given, settings, strategies as st

        @given(st.integers(0, 10**6), st.integers(1, 4),
               st.integers(1, 3), st.integers(1, 8))
        @settings(max_examples=15, deadline=None)
        def prop(seed, Nq, nprobe, bits):
            C, cap, S = max(nprobe, 3), 8, 3
            tables, dc, probes, codes, t, ids = _pq_case(
                seed, Nq, C, cap, S, bits, nprobe, 0, cap)
            kk = min(5, nprobe * cap)
            d_k, i_k = pq_adc_topk(tables, dc, probes, codes, t, ids,
                                   kk=kk, use_kernel=True, interpret=True)
            # brute-force candidates in the same probe-major order, with
            # the same sequential subspace sum
            tb, dcn = np.asarray(tables), np.asarray(dc)
            pr, cd = np.asarray(probes), np.asarray(codes)
            tn, idn = np.asarray(t), np.asarray(ids)
            K = 1 << bits
            cand_d = np.empty((Nq, nprobe * cap), np.float32)
            cand_i = np.empty((Nq, nprobe * cap), np.int32)
            for q in range(Nq):
                col = 0
                for j in range(nprobe):
                    c = pr[q, j]
                    for r in range(cap):
                        ip = np.float32(0.0)
                        for s in range(S):
                            ip = np.float32(
                                ip + tb[q, s * K + int(cd[c, r, s])])
                        d = np.float32(
                            np.float32(dcn[q, j] + tn[c, r])
                            - np.float32(2.0) * ip)
                        cand_d[q, col] = max(d, np.float32(0.0))
                        cand_i[q, col] = idn[c, r]
                        col += 1
            d_o, i_o = topk_by_distance(jnp.asarray(cand_d),
                                        jnp.asarray(cand_i), kk)
            i_o = np.where(np.asarray(d_o) >= BIG, -1, np.asarray(i_o))
            np.testing.assert_array_equal(np.asarray(i_k), i_o)
            np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_o),
                                       rtol=1e-5, atol=1e-5)

        prop()
