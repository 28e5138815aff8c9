"""Model-internal oracles: chunked forms vs exact sequential recurrences,
attention paths, MoE dispatch vs dense reference, sharding rules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import attention, mamba2, moe, rwkv6
from repro.sharding.partition import logical_to_physical


def _cfg(name, **kw):
    return reduced(get_config(name)).replace(dtype="float32", **kw)


class TestAttentionPaths:
    @pytest.mark.parametrize("causal,kind,window", [
        (True, "full", 0), (True, "sliding", 24), (False, "full", 0)])
    def test_chunked_equals_naive(self, causal, kind, window):
        cfg = _cfg("yi-6b", causal=causal, attention=kind,
                   window=window or 4096)
        rng = np.random.RandomState(0)
        B, T, H, K, dh = 2, 128, cfg.n_heads, cfg.kv_heads, cfg.dim_per_head
        q = jnp.asarray(rng.randn(B, T, H, dh), jnp.float32)
        k = jnp.asarray(rng.randn(B, T, K, dh), jnp.float32)
        v = jnp.asarray(rng.randn(B, T, K, dh), jnp.float32)
        ref = attention.attend_naive(q, k, v, cfg)
        out = attention.attend_chunked(q, k, v, cfg, q_chunk=32, kv_chunk=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_chunked_is_differentiable(self):
        cfg = _cfg("yi-6b")
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(1, 64, 4, 32), jnp.float32)
        k = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.float32)
        v = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.float32)

        def f(q, k, v):
            return jnp.sum(attention.attend_chunked(q, k, v, cfg,
                                                    q_chunk=16, kv_chunk=16))

        def f_ref(q, k, v):
            return jnp.sum(attention.attend_naive(q, k, v, cfg))

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    def test_ring_buffer_cache_sliding(self):
        cfg = _cfg("yi-6b", attention="sliding", window=8)
        c = attention.init_cache(cfg, batch=2, max_seq=100, dtype=jnp.float32)
        assert c.k.shape[1] == 8  # ring buffer, not max_seq


class TestMamba2:
    def test_chunked_equals_sequential(self):
        cfg = _cfg("zamba2-2.7b", ssm_tile_dtype="float32")
        m = mamba2.init_mamba2(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 64, cfg.d_model), jnp.float32)
        out_c = mamba2.apply_mamba2(m, x, cfg, chunk=16)
        out_r = mamba2.apply_mamba2_ref(m, x, cfg)
        np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_r),
                                   rtol=1e-3, atol=1e-4)

    def test_bf16_tiles_close_to_ref(self):
        cfg = _cfg("zamba2-2.7b", ssm_tile_dtype="bfloat16")
        m = mamba2.init_mamba2(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 64, cfg.d_model), jnp.float32)
        out_c = mamba2.apply_mamba2(m, x, cfg, chunk=16)
        out_r = mamba2.apply_mamba2_ref(m, x, cfg)
        rel = float(jnp.max(jnp.abs(out_c - out_r))) / float(
            jnp.max(jnp.abs(out_r)))
        assert rel < 0.03, rel

    def test_decode_matches_prefill(self):
        cfg = _cfg("zamba2-2.7b", ssm_tile_dtype="float32")
        m = mamba2.init_mamba2(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(1)
        B, T = 2, 12
        x = jnp.asarray(rng.randn(B, T, cfg.d_model), jnp.float32)
        full = mamba2.apply_mamba2(m, x, cfg, chunk=4)
        cache = mamba2.init_cache(cfg, B, dtype=jnp.float32)
        outs = []
        for t in range(T):
            y, cache = mamba2.decode_step(m, x[:, t:t + 1], cache, cfg)
            outs.append(y)
        dec = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                                   rtol=1e-3, atol=1e-4)


class TestRWKV6:
    def test_chunked_equals_sequential(self):
        cfg = _cfg("rwkv6-1.6b")
        p = rwkv6.init_rwkv6(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        x = jnp.asarray(0.5 * rng.randn(2, 64, cfg.d_model), jnp.float32)
        out_c = rwkv6.apply_rwkv6(p, x, cfg, chunk=16)
        out_r = rwkv6.apply_rwkv6_ref(p, x, cfg)
        np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_r),
                                   rtol=1e-3, atol=1e-4)

    def test_state_carries_context(self):
        # decoding with the state must differ from decoding from scratch —
        # i.e. the wkv state actually carries history
        cfg = _cfg("rwkv6-1.6b")
        p = rwkv6.init_rwkv6(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(2)
        x = jnp.asarray(0.5 * rng.randn(1, 1, cfg.d_model), jnp.float32)
        fresh = rwkv6.init_cache(cfg, 1, dtype=jnp.float32)
        # random (not constant) bump: the per-head group norm nearly cancels
        # uniform shifts of S, which would make this test vacuous
        bump = jax.random.normal(jax.random.PRNGKey(5), fresh.S.shape)
        warm = fresh._replace(S=fresh.S + bump)
        y1, _ = rwkv6.decode_step(p, x, fresh, cfg)
        y2, _ = rwkv6.decode_step(p, x, warm, cfg)
        assert float(jnp.max(jnp.abs(y1 - y2))) > 1e-6


class TestMoE:
    def test_grouped_equals_dense_when_capacity_ample(self):
        cfg = _cfg("granite-moe-1b-a400m")
        p = moe.init_moe(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 16, cfg.d_model), jnp.float32)
        y_g, aux_g = moe.apply_moe(p, x, cfg, mesh=None)
        y_d, aux_d = moe.apply_moe_dense(p, x, cfg)
        np.testing.assert_allclose(np.asarray(y_g), np.asarray(y_d),
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(float(aux_g), float(aux_d), rtol=1e-4)

    def test_aux_loss_uniform_router_is_one(self):
        # perfectly uniform routing gives aux ~ E * E*(1/E)*(1/E)*k/k = 1
        cfg = _cfg("granite-moe-1b-a400m")
        p = moe.init_moe(cfg, jax.random.PRNGKey(0))
        p = dict(p, router=jnp.zeros_like(p["router"]))
        x = jnp.asarray(np.random.RandomState(0).randn(2, 64, cfg.d_model),
                        jnp.float32)
        _, aux = moe.apply_moe(p, x, cfg)
        assert 0.9 < float(aux) < 1.3


class TestShardingRules:
    def test_divisibility_fallback(self):
        import jax as _jax
        mesh = _jax.make_mesh((1, 1), ("data", "model"))
        # shape divides: sharded; doesn't: replicated
        spec = logical_to_physical(("heads", None), mesh, shape=(9, 4))
        assert spec == jax.sharding.PartitionSpec("model", None) or \
            spec == jax.sharding.PartitionSpec(None, None)

    def test_nondividing_heads_replicate(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        # 9 heads on a 16-wide axis can't shard -> None (simulated with
        # explicit size check against a fake shape)
        from repro.sharding import partition
        spec = partition.logical_to_physical(("heads",), mesh, shape=(9,))
        # model axis size 1 divides anything; use a synthetic rule check:
        spec16 = partition.logical_to_physical(
            ("heads",), jax.make_mesh((1,), ("model",)), shape=(9,))
        assert spec16 is not None  # smoke: callable under any mesh


class TestLaunchHelpers:
    def test_local_mesh_axes_are_auto(self):
        from jax.sharding import AxisType
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh()
        assert tuple(mesh.axis_names) == ("data", "model")
        assert all(t == AxisType.Auto for t in mesh.axis_types)

    def test_compile_cache_dir(self, monkeypatch):
        import os
        from repro.launch import compile_cache
        set_dirs = []
        monkeypatch.setattr(compile_cache.jax.config, "update",
                            lambda k, v: set_dirs.append((k, v)))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert set_dirs == []                 # honoured, nothing else set
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert set_dirs == [("jax_compilation_cache_dir", path)]
