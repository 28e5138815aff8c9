"""Index-hierarchy tests: k-means build, IVF pruned retrieval, engine cache.

The IVF contract under test: at ``nprobe == n_clusters`` the pruned path
is an exact scan (indices identical to ExactIndex), and at modest nprobe
on clustered data it keeps recall high while visiting a fraction of the
gallery. The sharded variants run in the slow subprocess check
(tests/_serve_subprocess_check.py, asserted from test_metric_topk.py).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.metric_topk import project_gallery
from repro.obs import index_memory
from repro.serve import (ExactIndex, GalleryIndex, IVFIndex, MetricIndex,
                         MutableIndex, RetrievalEngine, kmeans_projected,
                         recall_at_k)
from repro.serve.ivf import _balance_assign
from repro.serve.snapshot import load_index, save_index


def _clustered(M, d, n_blobs, noise=0.3, seed=0):
    rng = np.random.RandomState(seed)
    centers = 3.0 * rng.randn(n_blobs, d).astype(np.float32)
    blob = rng.randint(0, n_blobs, M)
    pts = centers[blob] + noise * rng.randn(M, d).astype(np.float32)
    return jnp.asarray(pts, jnp.float32), centers, rng


class TestKMeans:
    def test_objective_decreases_and_shapes(self):
        gp, _, _ = _clustered(1200, 16, 12)
        cent, assign, obj = kmeans_projected(gp, 8, iters=8, seed=1)
        assert cent.shape == (8, 16)
        assert assign.shape == (1200,)
        assert int(assign.min()) >= 0 and int(assign.max()) < 8
        obj = np.asarray(obj)
        assert obj[-1] < obj[0]
        assert (np.diff(obj) <= 1e-5).all(), "Lloyd objective increased"

    def test_blocked_assignment_matches_unblocked(self):
        gp, _, _ = _clustered(700, 8, 6, seed=3)
        c1, a1, _ = kmeans_projected(gp, 4, iters=5, seed=0, block_rows=128)
        c2, a2, _ = kmeans_projected(gp, 4, iters=5, seed=0,
                                     block_rows=4096)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))

    def test_empty_cluster_reseed(self):
        # 6 distinct points tiled: at most 6 occupiable centroids for 8
        # clusters -> the reseed path must keep every centroid finite and
        # every assignment in range instead of dividing by zero
        base = np.asarray(np.random.RandomState(0).randn(6, 4), np.float32)
        gp = jnp.asarray(np.tile(base, (40, 1)))
        cent, assign, obj = kmeans_projected(gp, 8, iters=6, seed=2,
                                             init="random")
        assert np.isfinite(np.asarray(cent)).all()
        assert np.isfinite(np.asarray(obj)).all()
        a = np.asarray(assign)
        assert a.min() >= 0 and a.max() < 8

    def test_random_init_supported(self):
        gp, _, _ = _clustered(300, 8, 4)
        cent, _, _ = kmeans_projected(gp, 4, iters=4, init="random")
        assert cent.shape == (4, 8)
        with pytest.raises(ValueError):
            kmeans_projected(gp, 4, init="mystery")

    def test_more_clusters_than_rows_raises(self):
        gp, _, _ = _clustered(10, 4, 2)
        with pytest.raises(ValueError):
            kmeans_projected(gp, 11)


class TestExactIndex:
    """The single-device ExactIndex holds its gallery once, laid out for
    the scan at build (kernels/metric_topk.tile_gallery). 1,300 rows fill
    two tiles of 1,024 slots and leave 748 pad slots, which no reader may
    see."""

    M, D, K, SLOTS = 1300, 24, 16, 2048

    def _build(self):
        rng = np.random.RandomState(0)
        L = jnp.asarray(0.3 * rng.randn(self.K, self.D), jnp.float32)
        G = jnp.asarray(rng.randn(self.M, self.D), jnp.float32)
        q = jnp.asarray(rng.randn(12, self.D), jnp.float32)
        return L, G, q, ExactIndex.build(L, G)

    def test_size_is_the_real_row_count(self):
        L, G, _, idx = self._build()
        assert idx.size == self.M
        assert idx.gp.shape == (self.SLOTS, self.K)
        assert idx.gn.shape == (1, self.SLOTS)
        for a, b in zip(idx.rows(), project_gallery(L, G)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("k_top", [10, 21])
    def test_pallas_matches_xla(self, k_top):
        _, _, q, idx = self._build()
        d_x, i_x = idx.topk(q, k_top, backend="xla")
        d_p, i_p = idx.topk(q, k_top, backend="pallas")
        np.testing.assert_array_equal(np.asarray(i_p), np.asarray(i_x))
        np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_x),
                                   rtol=1e-4, atol=1e-4)
        assert np.asarray(i_x).max() < self.M

    def test_snapshot_round_trip_returns_rows_bit_for_bit(self, tmp_path):
        L, G, q, idx = self._build()
        save_index(idx, str(tmp_path))
        back = load_index(str(tmp_path))
        assert back.size == self.M
        for a, b in zip(back.rows(), project_gallery(L, G)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(back.topk(q, 10), idx.topk(q, 10)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_mutable_compaction_keeps_answers(self):
        L, G, q, idx = self._build()
        mut = MutableIndex(idx, L, auto_compact_delta=0, auto_compact_dead=0)
        rng = np.random.RandomState(1)
        mut.upsert(rng.randn(40, self.D).astype(np.float32))
        dead = np.arange(0, 300, 7)
        mut.delete(dead)
        d_pre, i_pre = mut.topk(q, 21)
        assert mut.compact()
        assert mut.base.size == self.M + 40 - len(dead)
        d_post, i_post = mut.topk(q, 21)
        np.testing.assert_array_equal(i_post, i_pre)
        np.testing.assert_array_equal(d_post, d_pre)
        assert not np.isin(i_post, dead).any()

    def test_index_memory_counts_one_tiled_copy(self):
        _, _, _, idx = self._build()
        mem = index_memory(idx)
        assert mem["gallery"] == (self.K + 1) * self.SLOTS * 4
        assert mem["gallery"] == idx.gp.nbytes + idx.gn.nbytes


class TestIVFIndex:
    def _build(self, M=600, d=32, k=16, n_clusters=8, seed=0, **kw):
        G, _, rng = _clustered(M, d, 24, seed=seed)
        L = jnp.asarray(0.3 * rng.randn(k, d), jnp.float32)
        q = jnp.asarray(np.asarray(G)[rng.randint(0, M, 20)]
                        + 0.1 * rng.randn(20, d).astype(np.float32))
        return (L, G, q, ExactIndex.build(L, G),
                IVFIndex.build(L, G, n_clusters=n_clusters, seed=0, **kw))

    def test_full_probe_matches_exact(self):
        _, _, q, exact, ivf = self._build()
        d_e, i_e = exact.topk(q, 10)
        d_f, i_f = ivf.topk(q, 10, nprobe=ivf.n_clusters)
        np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_e))
        np.testing.assert_allclose(np.asarray(d_f), np.asarray(d_e),
                                   rtol=1e-4, atol=1e-3)
        d = np.asarray(d_f)
        assert (np.diff(d, axis=1) >= -1e-5).all(), "not ascending"

    def test_recall_at_modest_nprobe(self):
        _, _, q, exact, ivf = self._build(M=4000, n_clusters=16)
        _, i_e = exact.topk(q, 10)
        _, i_a = ivf.topk(q, 10, nprobe=4)
        assert recall_at_k(i_a, i_e) >= 0.9

    def test_protocol_and_alias(self):
        _, _, _, exact, ivf = self._build()
        assert isinstance(exact, MetricIndex)
        assert isinstance(ivf, MetricIndex)
        assert GalleryIndex is ExactIndex
        assert ivf.size == exact.size == 600
        assert ivf.n_shards == 1

    def test_balanced_capacity_bounds_segments(self):
        # one dominant blob would blow up an uncapped segment; the build
        # must spill it and keep cap near cap_factor * M/C
        rng = np.random.RandomState(7)
        hot = 0.2 * rng.randn(900, 16).astype(np.float32)
        cold = 6.0 + 0.2 * rng.randn(100, 16).astype(np.float32)
        G = jnp.asarray(np.concatenate([hot, cold]))
        L = jnp.asarray(np.eye(16, dtype=np.float32))
        ivf = IVFIndex.build(L, G, n_clusters=8, cap_factor=1.25)
        assert ivf.cap <= 168     # ceil(1.25 * 1000/8) rounded to 8
        ids = np.asarray(ivf.ids_pad)
        real = ids[ids >= 0]
        assert len(real) == 1000 == len(np.unique(real)), \
            "every gallery row must live in exactly one segment slot"
        # the spill's own invariants, on the k-means assignment the build
        # balanced: the hot blob's clusters overflow and spill
        cent, before, _ = kmeans_projected(G, 8, iters=10, seed=0)
        before = np.asarray(before)
        counts = np.bincount(before, minlength=8)
        assert counts.max() > ivf.cap
        after = _balance_assign(G, cent, before, ivf.cap)
        slots = np.flatnonzero(ids >= 0)
        np.testing.assert_array_equal(after[ids[slots]], slots // ivf.cap)
        final = np.bincount(after, minlength=8)
        assert final.max() <= ivf.cap and final.sum() == 1000
        under = counts[before] <= ivf.cap
        np.testing.assert_array_equal(after[under], before[under])
        g, c = np.asarray(G), np.asarray(cent)
        d = ((g[:, None, :] - c[None]) ** 2).sum(-1)          # (M, C)
        for row in np.flatnonzero(after != before):
            nearer = d[row] < d[row, after[row]]
            assert (final[nearer] == ivf.cap).all(), row
        # an overflowing cluster keeps its cap nearest rows
        for cl in np.flatnonzero(counts > ivf.cap):
            mine = np.flatnonzero(before == cl)
            kept = mine[after[mine] == cl]
            assert len(kept) == ivf.cap
            assert d[kept, cl].max() <= np.delete(
                d[mine, cl], np.searchsorted(mine, kept)).min()

    def test_pallas_backend_rejected(self):
        _, _, q, _, ivf = self._build()
        with pytest.raises(NotImplementedError):
            ivf.topk(q, 5, backend="pallas")

    def test_oversized_k_top_raises(self):
        _, _, q, _, ivf = self._build()
        with pytest.raises(ValueError):
            ivf.topk(q, 601)
        with pytest.raises(ValueError):
            ivf.topk(q, ivf.cap * 1 + 1, nprobe=1)   # > nprobe*cap pool

    def test_block_q_chunking_invariant(self):
        # query chunk size is a perf knob; results must not depend on it
        L, G, q, _, ivf = self._build()
        d1, i1 = ivf.topk(q, 7, nprobe=3)
        ivf2 = IVFIndex.build(L, G, n_clusters=8, seed=0)
        ivf2.block_q = 4
        d2, i2 = ivf2.topk(q, 7, nprobe=3)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-5, atol=1e-5)


class TestEngineCache:
    def _engine(self, **kw):
        rng = np.random.RandomState(0)
        L = jnp.asarray(0.3 * rng.randn(8, 16), jnp.float32)
        G = jnp.asarray(rng.randn(200, 16), jnp.float32)
        q = rng.randn(6, 16).astype(np.float32)
        return RetrievalEngine(ExactIndex.build(L, G), k_top=5, **kw), q

    def test_repeat_batch_hits_without_device_work(self):
        eng, q = self._engine(cache_size=64)
        d1, i1 = eng.search(q)
        busy = eng.busy_s
        d2, i2 = eng.search(q)          # all rows cached
        assert eng.busy_s == busy       # no device call
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_array_equal(d1, d2)
        st = eng.stats()
        assert st["cache_hits"] == 6 and st["cache_misses"] == 6
        assert st["cache_entries"] == 6

    def test_distinct_k_is_a_distinct_key(self):
        eng, q = self._engine(cache_size=64)
        eng.search(q[0])
        eng.search(q[0], k_top=3)
        assert eng.stats()["cache_misses"] == 2
        eng.search(q[0], k_top=3)
        assert eng.stats()["cache_hits"] == 1

    def test_lru_eviction_bounded(self):
        eng, _ = self._engine(cache_size=4)
        rng = np.random.RandomState(1)
        for _ in range(10):
            eng.search(rng.randn(16).astype(np.float32))
        assert eng.stats()["cache_entries"] == 4

    def test_version_bump_invalidates(self):
        eng, q = self._engine(cache_size=64)
        eng.search(q)
        eng.search(q)
        assert eng.stats()["cache_hits"] == 6
        eng.index.version += 1          # e.g. gallery mutated / swapped
        eng.search(q)                   # must recompute, not serve stale
        st = eng.stats()
        assert st["cache_hits"] == 6 and st["cache_misses"] == 12

    def test_caller_mutation_does_not_poison_cache(self):
        eng, q = self._engine(cache_size=64)
        ref_d, ref_i = map(np.copy, eng.search(q))
        d2, i2 = eng.search(q)          # served from cache (writable)
        d2[:] = 0.0
        i2[:] = -7          # caller scribbles on its results
        d3, i3 = eng.search(q)          # must still be pristine
        assert eng.stats()["cache_hits"] == 12
        np.testing.assert_array_equal(i3, ref_i)
        np.testing.assert_array_equal(d3, ref_d)

    def test_device_qps_excludes_cache_hits(self):
        eng, q = self._engine(cache_size=64)
        eng.search(q)
        eng.search(q)
        st = eng.stats()
        assert st["n_queries"] == 12
        assert st["n_device_queries"] == 6
        assert st["qps"] == pytest.approx(6 / st["busy_s"])

    def test_cache_disabled(self):
        eng, q = self._engine(cache_size=0)
        eng.search(q)
        eng.search(q)
        st = eng.stats()
        assert st["cache_hits"] == 0 and st["cache_entries"] == 0

    def test_empty_batch(self):
        for cache_size in (64, 0):
            eng, _ = self._engine(cache_size=cache_size)
            d, i = eng.search(np.zeros((0, 16), np.float32))
            assert d.shape == (0, 5) and i.shape == (0, 5)

    def test_index_swap_invalidates(self):
        # a freshly built replacement index also has version == 0; the
        # cache must key on index identity, not version alone
        eng, q = self._engine(cache_size=64)
        rng = np.random.RandomState(9)
        other = ExactIndex.build(eng.index.L,
                                 jnp.asarray(rng.randn(200, 16), jnp.float32))
        eng.search(q)
        eng.index = other
        d, i = eng.search(q)            # must requery, not serve gallery A
        st = eng.stats()
        assert st["cache_hits"] == 0 and st["cache_misses"] == 12
        d_ref, i_ref = other.topk(jnp.asarray(q), 5)
        np.testing.assert_array_equal(i, np.asarray(i_ref))

    def test_engine_over_ivf_index(self):
        rng = np.random.RandomState(0)
        L = jnp.asarray(0.3 * rng.randn(8, 16), jnp.float32)
        G, _, _ = _clustered(800, 16, 10)
        exact = RetrievalEngine(ExactIndex.build(L, G), k_top=5)
        ivf = RetrievalEngine(
            IVFIndex.build(L, G, n_clusters=4, nprobe=4), k_top=5)
        q = rng.randn(9, 16).astype(np.float32)
        _, i_e = exact.search(q)
        _, i_a = ivf.search(q)          # nprobe == n_clusters -> exact
        np.testing.assert_array_equal(i_a, i_e)
        assert ivf.stats()["index"] == "IVFIndex"


class TestEngineValidation:
    def _engine(self, **kw):
        rng = np.random.RandomState(0)
        L = jnp.asarray(0.3 * rng.randn(8, 16), jnp.float32)
        G = jnp.asarray(rng.randn(200, 16), jnp.float32)
        q = rng.randn(6, 16).astype(np.float32)
        return RetrievalEngine(ExactIndex.build(L, G), k_top=5, **kw), q

    def test_k_top_zero_rejected(self):
        # regression: `k_top or self.k_top` silently mapped an explicit
        # k_top=0 to the engine default instead of rejecting it
        eng, q = self._engine()
        with pytest.raises(ValueError, match="k_top"):
            eng.search(q, k_top=0)
        with pytest.raises(ValueError, match="k_top"):
            eng.search(q, k_top=-3)
        d, i = eng.search(q)                    # default path unharmed
        assert i.shape == (6, 5)

    def test_batcher_k_top_zero_rejected(self):
        from repro.serve import MicroBatcher
        eng, q = self._engine()
        batcher = MicroBatcher(eng)
        try:
            with pytest.raises(ValueError, match="k_top"):
                batcher.submit(q[0], k_top=0)
            assert batcher.submit(q[0]).result(timeout=30)[1].shape == (5,)
        finally:
            batcher.close()

    def test_engine_ctor_k_top_validated(self):
        rng = np.random.RandomState(0)
        idx = ExactIndex.build(
            jnp.asarray(0.3 * rng.randn(8, 16), jnp.float32),
            jnp.asarray(rng.randn(50, 16), jnp.float32))
        with pytest.raises(ValueError, match="k_top"):
            RetrievalEngine(idx, k_top=0)

    def test_warmup_accepts_k_list(self):
        eng, q = self._engine()
        eng.warmup(ks=[2, 5])                   # pre-compile non-default k
        d, i = eng.search(q, k_top=2)
        assert i.shape == (6, 2)
        with pytest.raises(ValueError, match="k_top"):
            eng.warmup(ks=[0])


@pytest.mark.slow
class TestIVFRecallSweep:
    def test_recall_monotone_in_nprobe(self):
        G, _, rng = _clustered(30_000, 48, 128, seed=5)
        L = jnp.asarray(0.2 * rng.randn(24, 48), jnp.float32)
        q = jnp.asarray(np.asarray(G)[rng.randint(0, 30_000, 64)]
                        + 0.1 * rng.randn(64, 48).astype(np.float32))
        exact = ExactIndex.build(L, G)
        ivf = IVFIndex.build(L, G, n_clusters=32, seed=0)
        _, i_e = exact.topk(q, 10)
        recalls = [recall_at_k(ivf.topk(q, 10, nprobe=p)[1], i_e)
                   for p in (1, 2, 4, 8, 16, 32)]
        assert recalls[-1] == 1.0       # full probe == exact
        assert recalls[0] >= 0.5
        assert all(b >= a - 0.02 for a, b in zip(recalls, recalls[1:])), \
            f"recall not (weakly) monotone in nprobe: {recalls}"
        assert max(recalls[:3]) >= 0.9  # modest nprobe already >= 0.9
