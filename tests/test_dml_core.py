"""Unit tests for the core DML objectives (paper Eq. 1-4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dml
from repro.data import pairs as pairdata
from repro.data.loader import partition_pairs
from repro.optim import sgd

jax.config.update("jax_enable_x64", False)


def _toy(n=64, d=16, k=8, seed=0):
    rng = np.random.RandomState(seed)
    xs = rng.randn(n, d).astype(np.float32)
    ys = rng.randn(n, d).astype(np.float32)
    sim = (rng.rand(n) < 0.5).astype(np.int32)
    L = 0.3 * rng.randn(k, d).astype(np.float32)
    return jnp.asarray(L), jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(sim)


class TestObjective:
    def test_matches_M_form(self):
        L, xs, ys, _ = _toy()
        d2_L = dml.mahalanobis_sqdist(L, xs, ys)
        d2_M = dml.mahalanobis_sqdist_M(dml.M_from_L(L), xs, ys)
        np.testing.assert_allclose(d2_L, d2_M, rtol=1e-4, atol=1e-5)

    def test_pair_losses_structure(self):
        L, xs, ys, sim = _toy()
        losses = dml.pair_losses(L, xs, ys, sim, lam=2.0, margin=1.0)
        d2 = dml.mahalanobis_sqdist(L, xs, ys)
        expected = np.where(np.asarray(sim) == 1, np.asarray(d2),
                            2.0 * np.maximum(0.0, 1.0 - np.asarray(d2)))
        np.testing.assert_allclose(losses, expected, rtol=1e-5, atol=1e-6)

    def test_analytic_grad_matches_autodiff(self):
        L, xs, ys, sim = _toy()
        g_auto = jax.grad(dml.objective)(L, xs, ys, sim, 1.5, 1.0)
        g_analytic = dml.analytic_grad(L, xs, ys, sim, 1.5, 1.0)
        np.testing.assert_allclose(g_auto, g_analytic, rtol=1e-4, atol=1e-5)

    def test_zero_L_hinge_fully_active(self):
        _, xs, ys, sim = _toy()
        L0 = jnp.zeros((8, 16))
        losses = dml.pair_losses(L0, xs, ys, sim, lam=1.0, margin=1.0)
        # similar pairs -> 0 loss, dissimilar -> full margin
        np.testing.assert_allclose(
            losses, np.where(np.asarray(sim) == 1, 0.0, 1.0), atol=1e-6)

    def test_M_from_L_is_psd(self):
        L, *_ = _toy()
        w = np.linalg.eigvalsh(np.asarray(dml.M_from_L(L)))
        assert (w >= -1e-5).all()

    def test_psd_project(self):
        rng = np.random.RandomState(0)
        A = rng.randn(12, 12).astype(np.float32)
        A = 0.5 * (A + A.T)
        P = np.asarray(dml.psd_project(jnp.asarray(A)))
        w = np.linalg.eigvalsh(P)
        assert (w >= -1e-5).all()
        # projection is idempotent
        P2 = np.asarray(dml.psd_project(jnp.asarray(P)))
        np.testing.assert_allclose(P, P2, atol=1e-4)


class TestTriplet:
    def test_triplet_margin_semantics(self):
        rng = np.random.RandomState(1)
        a = jnp.asarray(rng.randn(32, 16).astype(np.float32))
        p = a + 0.01  # positives essentially at the anchor
        n = jnp.asarray(rng.randn(32, 16).astype(np.float32)) * 10.0
        L = jnp.eye(8, 16)
        losses = dml.triplet_losses(L, a, p, n, margin=1.0)
        # far negatives, near positives -> hinge inactive for most
        assert float(jnp.mean(losses == 0.0)) > 0.5


class TestEval:
    def test_average_precision_perfect(self):
        scores = jnp.asarray([3.0, 2.0, 1.0, 0.0])
        labels = jnp.asarray([1, 1, 0, 0])
        assert float(dml.average_precision(scores, labels)) == pytest.approx(1.0)

    def test_average_precision_random_is_half(self):
        rng = np.random.RandomState(0)
        scores = jnp.asarray(rng.randn(2000).astype(np.float32))
        labels = jnp.asarray((rng.rand(2000) < 0.5).astype(np.int32))
        ap = float(dml.average_precision(scores, labels))
        assert 0.4 < ap < 0.6

    def test_pr_curve_monotone_recall(self):
        rng = np.random.RandomState(0)
        prec, rec = dml.precision_recall_curve(
            rng.randn(500), (rng.rand(500) < 0.5).astype(int))
        assert (np.diff(rec) >= -1e-9).all()
        assert rec[-1] == pytest.approx(1.0)


class TestTrainingImprovesMetric:
    def test_sgd_on_blobs_beats_euclidean(self):
        cfg = pairdata.PairDatasetConfig(
            n_samples=600, feat_dim=32, n_classes=5, noise=1.2, seed=3)
        train_pairs, eval_pairs = pairdata.train_eval_split(
            cfg, 2000, 2000, 500, 500)
        from repro.core.ps.trainer import train_dml_single
        dcfg = dml.DMLConfig(feat_dim=32, proj_dim=16)
        L, hist = train_dml_single(dcfg, train_pairs, steps=150,
                                   batch_size=256, lr=5e-2)
        xs = jnp.asarray(eval_pairs["xs"]); ys = jnp.asarray(eval_pairs["ys"])
        labels = jnp.asarray(eval_pairs["sim"])
        ap_learned = float(dml.average_precision(dml.pair_scores(L, xs, ys), labels))
        ap_euclid = float(dml.average_precision(
            dml.pair_scores_euclidean(xs, ys), labels))
        assert hist[-1]["loss"] < hist[0]["loss"]
        assert ap_learned > ap_euclid + 0.02


class TestPairSampling:
    """data/pairs.py dedup satellite: self-pairs are masked, duplicate
    constraints are dropped, and seeded draws are deterministic."""

    def _labels(self, n=500, c=7, seed=0):
        return np.random.RandomState(seed).randint(0, c, n).astype(np.int32)

    def test_no_self_pairs_and_no_duplicates(self):
        y = self._labels()
        idx = pairdata.sample_pair_indices(y, 800, 800, seed=0)
        assert (idx["a"] != idx["b"]).all()
        # unordered (a, b) constraints are unique within each of S and D
        for want in (1, 0):
            m = idx["sim"] == want
            lo = np.minimum(idx["a"][m], idx["b"][m])
            hi = np.maximum(idx["a"][m], idx["b"][m])
            keys = lo * len(y) + hi
            assert len(np.unique(keys)) == len(keys)

    def test_labels_respected(self):
        y = self._labels()
        idx = pairdata.sample_pair_indices(y, 400, 400, seed=1)
        sim = idx["sim"] == 1
        assert (y[idx["a"][sim]] == y[idx["b"][sim]]).all()
        assert (y[idx["a"][~sim]] != y[idx["b"][~sim]]).all()

    def test_seeded_determinism(self):
        y = self._labels()
        i1 = pairdata.sample_pair_indices(y, 500, 500, seed=42)
        i2 = pairdata.sample_pair_indices(y, 500, 500, seed=42)
        for k in ("a", "b", "sim"):
            np.testing.assert_array_equal(i1[k], i2[k])
        i3 = pairdata.sample_pair_indices(y, 500, 500, seed=43)
        assert not np.array_equal(i1["a"], i3["a"])

    def test_sample_pairs_matches_contract(self):
        rng = np.random.RandomState(0)
        x = rng.randn(300, 8).astype(np.float32)
        y = self._labels(300, 5)
        pairs = pairdata.sample_pairs(x, y, 200, 200, seed=2)
        assert pairs["xs"].shape == (400, 8)
        assert pairs["sim"].sum() == 200
        # no self-pair can produce an identical feature row pair here
        assert (np.abs(pairs["xs"] - pairs["ys"]).sum(1) > 0).all()

    def test_exhaustion_raises(self):
        y = np.zeros(8, np.int32)       # one class: max C(8,2)=28 pairs
        with pytest.raises(ValueError, match="distinct"):
            pairdata.sample_pair_indices(y, 29, 0, seed=0)

    def test_near_exhaustion_fills(self):
        y = np.zeros(10, np.int32)      # exactly C(10,2)=45 similar pairs
        idx = pairdata.sample_pair_indices(y, 45, 0, seed=0)
        lo = np.minimum(idx["a"], idx["b"])
        hi = np.maximum(idx["a"], idx["b"])
        assert len(np.unique(lo * 10 + hi)) == 45

    def test_batches_have_distinct_constraints(self):
        y = self._labels(400, 6)
        idx = pairdata.sample_pair_indices(y, 600, 600, seed=0)
        stream = pairdata.pair_batches(
            {"a": idx["a"], "b": idx["b"], "sim": idx["sim"]},
            batch_size=128, seed=0, balanced=False)
        batch = next(stream)
        keys = np.asarray(batch["a"]) * 400 + np.asarray(batch["b"])
        assert len(np.unique(keys)) == len(keys)


class TestDeviceFeatureStream:
    CFG = pairdata.PairDatasetConfig(n_samples=0, feat_dim=96, n_classes=6,
                                     kind="llc_like", seed=3)

    def test_chunks_are_seeded_and_disjoint(self):
        x0, y0 = pairdata.llc_like_chunk(self.CFG, 0, 64)
        x0b, y0b = pairdata.llc_like_chunk(self.CFG, 0, 64)
        x1, _ = pairdata.llc_like_chunk(self.CFG, 1, 64)
        assert x0.shape == (64, 96) and x0.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(x0), np.asarray(x0b))
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y0b))
        assert not np.array_equal(np.asarray(x0), np.asarray(x1))
        assert 0 <= int(y0.min()) and int(y0.max()) < 6

    def test_llc_like_class_structure(self):
        x, y = map(np.asarray, pairdata.llc_like_chunk(self.CFG, 0, 600))
        assert (x >= 0).all()
        # same class -> same support; the support density is 1 - sparsity
        for c in np.unique(y):
            nz = x[y == c] > 0
            assert (nz == nz[0]).all()
        assert abs((x > 0).mean() - (1 - self.CFG.sparsity)) < 0.05

    def test_other_kinds_rejected(self):
        import dataclasses
        with pytest.raises(ValueError, match="llc_like"):
            pairdata.llc_like_chunk(
                dataclasses.replace(self.CFG, kind="class_blobs"), 0, 8)


class TestIndexPairSource:
    def test_worker_streams_partition_and_gather(self):
        cfg = pairdata.PairDatasetConfig(n_samples=200, feat_dim=16,
                                         n_classes=4, seed=0)
        x, y = pairdata.make_features(cfg)
        idx = pairdata.sample_pair_indices(y, 300, 300, seed=1)
        src = pairdata.IndexPairSource(jnp.asarray(x), idx)
        streams = src.worker_streams(3, 32, seed=5)
        assert len(streams) == 3
        b = next(streams[0])
        assert b["xs"].shape == b["ys"].shape == (32, 16)
        # balanced S/D and rows gathered from the feature store
        assert int(b["sim"].sum()) == 16
        rows = {tuple(r) for r in np.round(x, 5)}
        assert all(tuple(r) in rows for r in np.round(np.asarray(b["xs"]), 5))

    def test_trainer_accepts_source(self):
        from repro.core.ps import sync
        from repro.core.ps.trainer import (DMLTrainConfig,
                                           train_dml_distributed)
        cfg = pairdata.PairDatasetConfig(n_samples=300, feat_dim=16,
                                         n_classes=4, seed=0)
        x, y = pairdata.make_features(cfg)
        src = pairdata.IndexPairSource(
            jnp.asarray(x), pairdata.sample_pair_indices(y, 400, 400))
        tcfg = DMLTrainConfig(dml=dml.DMLConfig(feat_dim=16, proj_dim=8),
                              ps=sync.PSConfig(n_workers=1), batch_size=64,
                              steps=5, lr=1e-2, log_every=1)
        L, hist = train_dml_distributed(tcfg, src)
        assert L.shape == (8, 16) and len(hist) == 5
        assert np.isfinite([h["loss"] for h in hist]).all()

    @pytest.mark.parametrize("n_workers", [1, 3])
    @pytest.mark.parametrize("store", ["device", "host"])
    def test_batches_are_the_stores_rows(self, store, n_workers,
                                         monkeypatch):
        """Each worker's batch holds, bit for bit, the rows numpy indexing
        gives for its seed's draw; a device store gathers each batch in one
        ``pair_rows`` call, a host store in none."""
        cfg = pairdata.PairDatasetConfig(n_samples=500, feat_dim=24,
                                         n_classes=5, seed=2)
        x, y = pairdata.make_features(cfg)
        idx = pairdata.sample_pair_indices(y, 600, 600, seed=3)
        calls = []
        pair_rows = pairdata.pair_rows
        monkeypatch.setattr(pairdata, "pair_rows", lambda *a, **k: (
            calls.append(1), pair_rows(*a, **k))[1])
        feats = jnp.asarray(x) if store == "device" else x
        streams = pairdata.IndexPairSource(feats, idx).worker_streams(
            n_workers, 40, seed=7)
        shards = partition_pairs(idx, n_workers)
        for w, (stream, shard) in enumerate(zip(streams, shards)):
            draws = pairdata._batch_draws(shard["sim"], 40, 7 + w, True)
            for _ in range(3):
                b, sel = next(stream), next(draws)
                assert b["xs"].shape == b["ys"].shape == (40, 24)
                assert b["sim"].dtype == jnp.int32
                np.testing.assert_array_equal(np.asarray(b["xs"]),
                                              x[shard["a"][sel]])
                np.testing.assert_array_equal(np.asarray(b["ys"]),
                                              x[shard["b"][sel]])
                np.testing.assert_array_equal(np.asarray(b["sim"]),
                                              shard["sim"][sel])
        assert len(calls) == (3 * n_workers if store == "device" else 0)

    def test_device_store_past_int32_is_refused(self):
        from unittest import mock
        store = mock.Mock(spec=jax.Array)
        store.shape = (2 ** 31, 24)
        idx = pairdata.sample_pair_indices(np.arange(40) % 4, 20, 20)
        with pytest.raises(ValueError, match="int32"):
            pairdata.pair_batches_from_indices(store, idx, 8)
        store.shape = (2 ** 31 - 1, 24)
        pairdata.pair_batches_from_indices(store, idx, 8)   # made, not run

    def test_one_worker_trainer_matches_the_stacked_step(self):
        """One worker's batches reach the step unstacked; ``L`` and the
        logged losses match stacked batches through the step as it is
        built, and the step program keeps the name the benchmark's trace
        reduction looks for."""
        import importlib.util
        import os
        from repro.core.ps import sync, trainer
        from repro.core import losses
        cfg = pairdata.PairDatasetConfig(n_samples=300, feat_dim=16,
                                         n_classes=4, seed=0)
        x, y = pairdata.make_features(cfg)
        src = pairdata.IndexPairSource(
            jnp.asarray(x), pairdata.sample_pair_indices(y, 400, 400))
        tcfg = trainer.DMLTrainConfig(
            dml=dml.DMLConfig(feat_dim=16, proj_dim=8),
            ps=sync.PSConfig(n_workers=1, seed=3), batch_size=64, steps=12,
            lr=1e-2, log_every=5)
        L, hist = trainer.train_dml_distributed(tcfg, src)

        def loss_fn(p, b):
            return losses.dml_pair_loss(p, b, lam=tcfg.dml.lam,
                                        margin=tcfg.dml.margin)

        opt = sgd(tcfg.lr)
        mesh = sync.make_worker_mesh(1, tcfg.ps.axis)
        state = sync.init_state(
            opt, dml.init_params(tcfg.dml, jax.random.PRNGKey(3)), tcfg.ps)
        step = sync.make_train_step(loss_fn, opt, tcfg.ps, mesh)
        batches = trainer.stack_worker_streams(
            src.worker_streams(1, 64, seed=3))
        want = []
        for t in range(12):
            state, m = step(state, next(batches))
            if t % 5 == 0 or t == 11:
                want.append(float(m["loss"]))
        np.testing.assert_allclose([h["loss"] for h in hist], want,
                                   rtol=1e-6)
        np.testing.assert_allclose(L, sync.worker_mean(state.params),
                                   rtol=1e-6)

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "chip", "harness",
            "trace_metrics.py")
        spec = importlib.util.spec_from_file_location("_trace_metrics", path)
        tm = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tm)
        def program(lowered):
            return lowered.as_text().split("module @", 1)[1].split()[0]

        one = trainer._one_worker_step(step)
        name = program(one.lower(state, next(src.worker_streams(1, 64, 3)[0])))
        assert tm.STEP_PROGRAM.search(name), name
        gather = program(pairdata.pair_rows.lower(
            src.features, np.zeros((3, 64), np.int32)))
        assert not tm.STEP_PROGRAM.search(gather), gather
