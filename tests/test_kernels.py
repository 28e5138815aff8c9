"""Pallas kernel tests: shape/dtype sweeps + allclose against ref.py oracles
(kernels execute in interpret mode on CPU; TPU is the lowering target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dml_pair import (dml_pair_fused, dml_pair_loss_fused,
                                    dml_pair_loss_reference, dml_pair_ref)
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.pairwise_dist import (metric_sqdist_matrix,
                                         pairwise_sqdist, pairwise_sqdist_ref)


class TestDMLPairKernel:
    @pytest.mark.parametrize("B,k,d", [
        (8, 8, 8), (64, 32, 48), (256, 128, 512), (100, 60, 780),
        (512, 600, 780), (32, 100, 224),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_loss_matches_oracle(self, B, k, d, dtype):
        rng = np.random.RandomState(B + k + d)
        L = jnp.asarray(0.2 * rng.randn(k, d), dtype)
        xs = jnp.asarray(rng.randn(B, d), dtype)
        ys = jnp.asarray(rng.randn(B, d), dtype)
        sim = jnp.asarray((rng.rand(B) < 0.5).astype(np.int32))
        ref = dml_pair_loss_reference(L.astype(jnp.float32),
                                      xs.astype(jnp.float32),
                                      ys.astype(jnp.float32), sim, 1.3, 1.0)
        out = dml_pair_loss_fused(L.astype(jnp.float32),
                                  xs.astype(jnp.float32),
                                  ys.astype(jnp.float32), sim, 1.3, 1.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=1e-5)

    @pytest.mark.parametrize("B,k,d", [(64, 32, 48), (256, 128, 512),
                                       (100, 60, 780)])
    def test_gradients_match_oracle(self, B, k, d):
        rng = np.random.RandomState(7)
        L = jnp.asarray(0.2 * rng.randn(k, d), jnp.float32)
        xs = jnp.asarray(rng.randn(B, d), jnp.float32)
        ys = jnp.asarray(rng.randn(B, d), jnp.float32)
        sim = jnp.asarray((rng.rand(B) < 0.5).astype(np.int32))
        g_ref = jax.grad(dml_pair_loss_reference, argnums=(0, 1, 2))(
            L, xs, ys, sim, 1.3, 1.0)
        g_out = jax.grad(dml_pair_loss_fused, argnums=(0, 1, 2))(
            L, xs, ys, sim, 1.3, 1.0)
        for a, b in zip(g_ref, g_out):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-3, atol=1e-5)

    def test_pad_path_zero_contribution(self):
        # B not divisible by the tile: padding must not change the mean
        rng = np.random.RandomState(0)
        B, k, d = 37, 16, 24
        L = jnp.asarray(0.3 * rng.randn(k, d), jnp.float32)
        xs = jnp.asarray(rng.randn(B, d), jnp.float32)
        ys = jnp.asarray(rng.randn(B, d), jnp.float32)
        sim = jnp.asarray(np.ones(B, np.int32))
        ref = dml_pair_loss_reference(L, xs, ys, sim)
        out = dml_pair_loss_fused(L, xs, ys, sim)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_raw_kernel_outputs(self):
        rng = np.random.RandomState(1)
        B, k, d = 256, 128, 512
        L = jnp.asarray(0.2 * rng.randn(k, d), jnp.float32)
        xs = jnp.asarray(rng.randn(B, d), jnp.float32)
        ys = jnp.asarray(rng.randn(B, d), jnp.float32)
        sim = jnp.asarray((rng.rand(B) < 0.5).astype(np.int32))
        losses, d2, proj = dml_pair_fused(L, xs, ys, sim[:, None], lam=1.0,
                                          margin=1.0, block_b=64,
                                          block_k=64, block_d=128)
        l_ref, d2_ref, p_ref = dml_pair_ref(L, xs, ys, sim)
        np.testing.assert_allclose(losses[:, 0], l_ref, rtol=2e-5, atol=1e-5)
        np.testing.assert_allclose(d2[:, 0], d2_ref, rtol=2e-5, atol=1e-5)
        np.testing.assert_allclose(proj, p_ref, rtol=2e-5, atol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("B,T,H,K,dh", [
        (2, 128, 4, 4, 64),      # MHA
        (2, 128, 8, 2, 64),      # GQA 4:1
        (1, 256, 4, 1, 32),      # MQA
        (2, 64, 4, 4, 128),
        (1, 512, 16, 4, 64),
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_oracle(self, B, T, H, K, dh, causal):
        rng = np.random.RandomState(T + H)
        q = jnp.asarray(rng.randn(B, T, H, dh), jnp.float32)
        k = jnp.asarray(rng.randn(B, T, K, dh), jnp.float32)
        v = jnp.asarray(rng.randn(B, T, K, dh), jnp.float32)
        ref = attention_ref(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=2e-5)

    @pytest.mark.parametrize("window", [32, 64, 128])
    def test_sliding_window(self, window):
        rng = np.random.RandomState(window)
        q = jnp.asarray(rng.randn(1, 256, 4, 32), jnp.float32)
        k = jnp.asarray(rng.randn(1, 256, 4, 32), jnp.float32)
        v = jnp.asarray(rng.randn(1, 256, 4, 32), jnp.float32)
        ref = attention_ref(q, k, v, causal=True, window=window)
        out = flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=2e-5)

    def test_bf16_inputs(self):
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(2, 128, 4, 64), jnp.bfloat16)
        k = jnp.asarray(rng.randn(2, 128, 4, 64), jnp.bfloat16)
        v = jnp.asarray(rng.randn(2, 128, 4, 64), jnp.bfloat16)
        ref = attention_ref(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=5e-2, atol=5e-2)


def _largest_tile(n, cap=512):
    """Largest power-of-two tile (<= cap) dividing n: a kernel-level
    tiling sweep for interpret mode (ops.py pads to TPU-legal tiles)."""
    return next(t for t in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                if t <= cap and n % t == 0)


class TestPairwiseDist:
    @pytest.mark.parametrize("N,M,k", [
        (64, 64, 32), (256, 128, 512), (128, 256, 64), (512, 512, 600),
    ])
    def test_matches_oracle(self, N, M, k):
        rng = np.random.RandomState(N + M)
        xp = jnp.asarray(rng.randn(N, k), jnp.float32)
        yp = jnp.asarray(rng.randn(M, k), jnp.float32)
        xn = jnp.sum(jnp.square(xp), axis=1)[:, None]
        yn = jnp.sum(jnp.square(yp), axis=1)[None, :]
        out = pairwise_sqdist(xp, yp, xn, yn, block_n=_largest_tile(N),
                              block_m=_largest_tile(M),
                              block_c=_largest_tile(k))
        ref = pairwise_sqdist_ref(xp, yp)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("N,M,k", [(37, 53, 20), (8, 300, 130)])
    def test_metric_matrix_pads_any_shape(self, N, M, k):
        # rows off the tiles and d_out off the lane width run the kernel
        # on padded inputs (no silent fallback to the reference)
        rng = np.random.RandomState(N + M)
        L = jnp.asarray(0.3 * rng.randn(k, 24), jnp.float32)
        x = jnp.asarray(rng.randn(N, 24), jnp.float32)
        y = jnp.asarray(rng.randn(M, 24), jnp.float32)
        D = metric_sqdist_matrix(L, x, y)
        ref = metric_sqdist_matrix(L, x, y, use_kernel=False)
        assert D.shape == (N, M)
        np.testing.assert_allclose(np.asarray(D), np.asarray(ref),
                                   rtol=1e-4, atol=1e-3)

    def test_metric_matrix_consistent_with_dml(self):
        from repro.core import dml
        rng = np.random.RandomState(0)
        L = jnp.asarray(0.3 * rng.randn(16, 24), jnp.float32)
        x = jnp.asarray(rng.randn(40, 24), jnp.float32)
        D = metric_sqdist_matrix(L, x, x)
        # diagonal = self-distance = 0, and matches dml.mahalanobis_sqdist
        np.testing.assert_allclose(np.asarray(jnp.diagonal(D)), 0.0,
                                   atol=1e-3)
        d2 = dml.mahalanobis_sqdist(L, x[:1].repeat(40, 0), x)
        np.testing.assert_allclose(np.asarray(D[0]), np.asarray(d2),
                                   rtol=1e-4, atol=1e-3)
