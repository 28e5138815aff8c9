"""Metric top-k retrieval tests: fused kernel vs oracle, serving stack.

Kernel checks run in interpret mode on CPU (TPU is the lowering target);
the sharded engine agreement check runs in a subprocess with 8 forced host
devices (dry-run rule: never force device count in the main process).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.metric_topk import (metric_sqdist_factored, metric_topk,
                                       metric_topk_naive, metric_topk_ref,
                                       metric_topk_xla, project_gallery,
                                       tile_gallery)
from repro.serve import (FakeClock, GalleryIndex, MicroBatcher,
                         RetrievalEngine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _data(Nq, M, d, k, seed=0):
    rng = np.random.RandomState(seed)
    L = jnp.asarray(0.3 * rng.randn(k, d), jnp.float32)
    q = jnp.asarray(rng.randn(Nq, d), jnp.float32)
    G = jnp.asarray(rng.randn(M, d), jnp.float32)
    return L, q, G


class TestMetricTopkKernel:
    @pytest.mark.parametrize("Nq,M,d,k,K", [
        (64, 1024, 128, 64, 10),     # even tiles
        (16, 300, 40, 12, 5),        # nothing divides the tile sizes
        (7, 129, 33, 9, 3),          # tiny + odd everything
        (200, 2048, 96, 48, 20),     # queries over several tiles
        (128, 512, 128, 128, 1),     # k_top = 1
        (8, 96, 24, 8, 96),          # k_top = M (full sort)
        (24, 2500, 40, 24, 10),      # ragged: 572 pad slots past 2 tiles
        (24, 2500, 40, 24, 21),      # the same at the miner's k
        (9, 1030, 16, 8, 21),        # 6 rows into a second tile
    ])
    def test_matches_ref(self, Nq, M, d, k, K):
        L, q, G = _data(Nq, M, d, k, seed=Nq + M)
        gp, gn = project_gallery(L, G)
        d_ref, i_ref = metric_topk_ref(q @ L.T, gp, K, gn)
        d_ker, i_ker = metric_topk(L, q, *tile_gallery(gp, gn), k_top=K)
        np.testing.assert_array_equal(np.asarray(i_ker), np.asarray(i_ref))
        np.testing.assert_allclose(np.asarray(d_ker), np.asarray(d_ref),
                                   rtol=1e-4, atol=1e-4)
        # distances come back ascending
        dk = np.asarray(d_ker)
        assert (np.diff(dk, axis=1) >= -1e-6).all()

    def test_matches_naive_per_pair_baseline(self):
        # the textbook per-pair metric application agrees with the
        # factored/pre-projected path the index serves
        L, q, G = _data(12, 200, 32, 16)
        gp, gn = project_gallery(L, G)
        _, i_ker = metric_topk(L, q, *tile_gallery(gp, gn), k_top=8)
        d_nv, i_nv = metric_topk_naive(L, q, G, 8, chunk=5)
        np.testing.assert_array_equal(np.asarray(i_ker), np.asarray(i_nv))

    def test_bf16_inputs(self):
        L, q, G = _data(16, 256, 64, 32)
        gp, gn = project_gallery(L, G)
        d_ref, i_ref = metric_topk_ref(q @ L.T, gp, 5, gn)
        d_ker, i_ker = metric_topk(L.astype(jnp.bfloat16),
                                   q.astype(jnp.bfloat16),
                                   *tile_gallery(gp, gn), k_top=5)
        # bf16 projection perturbs distances; neighbor sets stay mostly put
        overlap = np.mean([
            len(set(np.asarray(i_ker)[i]) & set(np.asarray(i_ref)[i])) / 5
            for i in range(16)])
        assert overlap > 0.8

    def test_k_top_larger_than_gallery_raises(self):
        # the index knows the real rows; the ops layer only its slots
        L, q, G = _data(4, 16, 8, 4)
        gp, gn = project_gallery(L, G)
        gpt, gnt = tile_gallery(gp, gn)
        with pytest.raises(ValueError):
            GalleryIndex.build(L, G).topk(q, 17, backend="pallas")
        with pytest.raises(ValueError):
            metric_topk(L, q, gpt, gnt, k_top=gpt.shape[0] + 1)

    @pytest.mark.parametrize("M,K", [
        (130, 130),                  # 126 pad slots, k_top = M
        (2500, 10),                  # ragged past two tiles
        (2500, 21),
    ])
    def test_padded_gallery_rows_never_returned(self, M, K):
        # the tiled gallery has pad slots past M; all returned ids real,
        # on the kernel and the XLA path alike
        L, q, G = _data(9, M, 16, 8)
        gp, gn = project_gallery(L, G)
        gpt, gnt = tile_gallery(gp, gn)
        assert gpt.shape[0] > M
        for _, idx in (metric_topk(L, q, gpt, gnt, k_top=K),
                       metric_topk_xla(L, q, gpt, gnt, K)):
            assert np.asarray(idx).max() < M
            assert np.asarray(idx).min() >= 0
            assert (np.sort(np.asarray(idx), axis=1)[:, 1:]
                    != np.sort(np.asarray(idx), axis=1)[:, :-1]).all()

    @pytest.mark.parametrize("platform,lanes", [("cpu", 8), ("tpu", 128)])
    @pytest.mark.parametrize("M", [130, 1000, 1024, 2500])
    def test_tile_gallery_layout(self, M, platform, lanes, monkeypatch):
        # rows stay rows; pad rows hold zeros and BIG norms and fill whole
        # tiles; on a TPU zero columns pad k to the lane width
        L, _, G = _data(1, M, 16, 8)
        gp, gn = project_gallery(L, G)
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        gpt, gnt = (np.asarray(a) for a in tile_gallery(gp, gn))
        slots, tile = gpt.shape[0], 1024 if M > 1024 else 128
        assert slots % tile == 0 and slots - M < tile
        assert gpt.shape[1] == lanes and gnt.shape == (1, slots)
        np.testing.assert_array_equal(gpt[:M, :8], np.asarray(gp))
        np.testing.assert_array_equal(gnt[0, :M], np.asarray(gn))
        assert (gpt[M:] == 0).all() and (gpt[:, 8:] == 0).all()
        assert (gnt[0, M:] == 1e30).all()

    @pytest.mark.parametrize("K", [10, 21])
    def test_lane_padded_gallery_matches_ref(self, K, monkeypatch):
        # the gallery as a TPU holds it (zero columns up to the lanes):
        # kernel and XLA path answer as the oracle on the plain rows
        L, q, G = _data(24, 1300, 40, 24, seed=5)
        gp, gn = project_gallery(L, G)
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            gpt, gnt = tile_gallery(gp, gn)
        assert gpt.shape == (2048, 128)
        d_ref, i_ref = metric_topk_ref(q @ L.T, gp, K, gn)
        for d, i in (metric_topk(L, q, gpt, gnt, k_top=K, interpret=True),
                     metric_topk_xla(L, q, gpt, gnt, K)):
            np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
            np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("M,start", [(400, 0), (410, 0), (390, 20),
                                         (130, 300)])
    def test_xla_distances_independent_of_gallery_size(self, M, start):
        # a row scores bit-identically whatever rows share the scan with
        # it (what lets MutableIndex compaction keep its answers exact)
        L, q, G = _data(9, 520, 24, 12)
        gp, gn = project_gallery(L, G)
        qp = q @ L.T
        full = np.asarray(metric_sqdist_factored(qp, gp, gn))
        part = np.asarray(metric_sqdist_factored(
            qp, gp[start:start + M], gn[start:start + M]))
        np.testing.assert_array_equal(part, full[:, start:start + M])
        # and so does the XLA scan over a tiled gallery
        def scan_all(rows):
            d, i = metric_topk_xla(L, q, *tile_gallery(gp[rows], gn[rows]),
                                   len(range(520)[rows]))
            out = np.empty(d.shape, np.float32)
            np.put_along_axis(out, np.asarray(i), np.asarray(d), 1)
            return out
        np.testing.assert_array_equal(scan_all(slice(start, start + M)),
                                      scan_all(slice(None))[:, start:start + M])


class TestServingStack:
    def test_engine_matches_xla_path_and_buckets(self):
        L, q, G = _data(20, 500, 48, 16)
        index = GalleryIndex.build(L, G)
        d_ref, i_ref = metric_topk_xla(L, q, index.gp, index.gn, 7)
        i_rows = metric_topk_ref(q @ L.T, *index.rows()[:1], 7,
                                 index.rows()[1])[1]
        np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i_rows))
        eng = RetrievalEngine(index, k_top=7, buckets=(8, 32))
        dists, idxs = eng.search(q)          # 20 pads to bucket 32
        np.testing.assert_array_equal(idxs, np.asarray(i_ref))
        np.testing.assert_allclose(dists, np.asarray(d_ref),
                                   rtol=1e-5, atol=1e-5)
        d1, i1 = eng.search(np.asarray(q[3]))   # single-vector request
        np.testing.assert_array_equal(i1, np.asarray(i_ref)[3])
        assert eng.stats()["n_queries"] == 21

    def test_engine_pallas_backend_agrees(self):
        L, q, G = _data(16, 400, 40, 24)
        index = GalleryIndex.build(L, G)
        xla = RetrievalEngine(index, k_top=6, backend="xla").search(q)
        pal = RetrievalEngine(index, k_top=6, backend="pallas").search(q)
        np.testing.assert_array_equal(pal[1], xla[1])
        np.testing.assert_allclose(pal[0], xla[0], rtol=1e-4, atol=1e-4)

    @staticmethod
    def _drain(clock, futs, max_wait_s, guard_s=60.0):
        """Advance the fake clock until every future resolves: wait for
        the worker to park on its coalescing timeout, then push time past
        it. Condition-driven (wait_for_waiters), never sleep-driven."""
        import time as _time
        guard = _time.monotonic() + guard_s
        while not all(f.done() for f in futs):
            assert _time.monotonic() < guard, "futures never resolved"
            try:
                # short rendezvous: the worker may resolve everything and
                # park untimed between our doneness check and this wait
                clock.wait_for_waiters(1, timeout=0.2)
            except TimeoutError:
                continue
            clock.advance(max_wait_s * 2)

    def test_microbatcher_coalesces_and_preserves_results(self):
        L, q, G = _data(30, 300, 32, 16)
        index = GalleryIndex.build(L, G)
        eng = RetrievalEngine(index, k_top=5)
        ref_d, ref_i = eng.search(q)
        clock = FakeClock()
        mb = MicroBatcher(eng, max_batch=16, max_wait_ms=20.0, clock=clock)
        futs = [mb.submit(np.asarray(q[i]), k_top=3) for i in range(30)]
        # virtual time is frozen, so the worker can only dispatch a batch
        # once it is *full* — coalescing is now exact, not probabilistic:
        # 30 submits at max_batch=16 form precisely [16, 14]
        self._drain(clock, futs, mb.max_wait_s)
        for i, f in enumerate(futs):
            d, idx = f.result(timeout=60)
            assert idx.shape == (3,)
            np.testing.assert_array_equal(idx, ref_i[i, :3])
        assert mb.close()
        assert mb.n_batches == 2, "fake-clock coalescing must be exact"
        assert list(mb.batch_sizes) == [16, 14]
        with pytest.raises(RuntimeError):
            mb.submit(np.asarray(q[0]))

    def test_batcher_survives_cancelled_future(self):
        # a rider cancelled while pending must not kill the worker thread
        L, q, G = _data(8, 100, 16, 8)
        eng = RetrievalEngine(GalleryIndex.build(L, G), k_top=3)
        eng.warmup()
        clock = FakeClock()
        mb = MicroBatcher(eng, max_batch=4, max_wait_ms=200.0, clock=clock)
        try:
            doomed = mb.submit(np.asarray(q[0]))
            assert doomed.cancel()
            alive = [mb.submit(np.asarray(q[i])) for i in range(1, 8)]
            self._drain(clock, alive, mb.max_wait_s)
            for f in alive:
                d, idx = f.result(timeout=30)   # resolved if worker lives
                assert idx.shape == (3,)
            assert doomed.cancelled()
        finally:
            assert mb.close()

    def test_batcher_rejects_oversized_k(self):
        L, q, G = _data(4, 64, 16, 8)
        eng = RetrievalEngine(GalleryIndex.build(L, G), k_top=5)
        mb = MicroBatcher(eng)
        try:
            with pytest.raises(ValueError):
                mb.submit(np.asarray(q[0]), k_top=9)
        finally:
            assert mb.close()


@pytest.mark.slow
class TestShardedEngine:
    @pytest.fixture(scope="class")
    def subprocess_result(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tests", "_serve_subprocess_check.py")],
            capture_output=True, text=True, env=env, timeout=600)
        assert proc.returncode == 0, \
            f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("SERVE_CHECK_OK")][0]
        return json.loads(line[len("SERVE_CHECK_OK "):])

    def test_sharded_matches_single_device(self, subprocess_result):
        assert subprocess_result["sharded_matches_single"]
        assert subprocess_result["n_shards"] == 8

    def test_engine_runs_on_sharded_index(self, subprocess_result):
        assert subprocess_result["engine_on_sharded_index"]

    def test_sharded_ivf_matches_single_device(self, subprocess_result):
        assert subprocess_result["ivf_sharded_matches_single"]
