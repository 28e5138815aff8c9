"""Closed-loop hard-pair mining: miner label filters, semi-hard band,
stream determinism, engine-cache refresh semantics, and mined-vs-uniform
convergence on a tiny dataset."""

import numpy as np
import pytest

from repro.core import dml, eval_tasks
from repro.core.ps import sync
from repro.core.ps.trainer import DMLTrainConfig, train_dml_distributed
from repro.data import pairs as pairdata
from repro.mining import (ClosedLoopConfig, ClosedLoopTrainer,
                          CurriculumSchedule, HardPairMiner, MinerConfig,
                          MinedPairSource)
from repro.serve import ExactIndex, RetrievalEngine


def _blobs(n=600, d=16, c=6, noise=0.3, seed=0):
    cfg = pairdata.PairDatasetConfig(n_samples=n, feat_dim=d, n_classes=c,
                                     kind="class_blobs", noise=noise,
                                     seed=seed)
    return pairdata.make_features(cfg)


def _miner(x, y, cfg=None, L=None):
    if L is None:
        L = np.eye(x.shape[1], dtype=np.float32)
    engine = RetrievalEngine(ExactIndex.build(L, x))
    return HardPairMiner(engine, x, y, cfg, warmup=False)


class TestMinerFilters:
    def test_label_correctness(self):
        x, y = _blobs()
        m = _miner(x, y, MinerConfig(k_neighbors=15, max_negatives=2,
                                     max_positives=2))
        res = m.mine(n_queries=200, seed=0)
        p = res.pairs
        assert res.n_pairs > 0
        neg = p["sim"] == 0
        pos = p["sim"] == 1
        # every hard negative is different-class, every positive same
        assert (y[p["a"][neg]] != y[p["b"][neg]]).all()
        assert (y[p["a"][pos]] == y[p["b"][pos]]).all()
        # never a self-pair
        assert (p["a"] != p["b"]).all()
        # stats account for every pair
        assert res.stats["n_hard_neg"] + res.stats["n_hard_pos"] \
            == res.n_pairs

    def test_positives_are_knn_violations(self):
        """Mined positives are same-class rows *outside* the anchor's
        current neighborhood (the pairs a kNN eval scores wrong)."""
        x, y = _blobs(noise=1.5)      # overlap so violations exist
        k = 10
        m = _miner(x, y, MinerConfig(k_neighbors=k, max_negatives=0,
                                     max_positives=3))
        res = m.mine(n_queries=150, seed=1)
        p = res.pairs
        assert res.n_pairs > 0
        _, nbr = m.engine.search(x[p["a"]], k_top=k + 1)
        for row, b in zip(np.asarray(nbr), p["b"]):
            assert b not in row       # outside the served neighborhood

    def test_semi_hard_band_respects_margin(self):
        x, y = _blobs(n=400, noise=1.0)
        k, margin = 20, 2.0
        m = _miner(x, y, MinerConfig(k_neighbors=k, margin=margin,
                                     semi_hard=True,
                                     fallback_nearest=False,
                                     max_negatives=3, max_positives=0))
        res = m.mine(n_queries=150, seed=0)
        p = res.pairs
        assert res.n_pairs > 0
        assert res.stats["n_fallback_neg"] == 0
        # recompute each anchor's neighborhood under the same (identity)
        # metric and check every mined negative sits in the band
        # [d(farthest same-class in neighborhood), +margin)
        d_all, i_all = m.engine.search(x[p["a"]], k_top=k + 1)
        for row_d, row_i, a, b in zip(d_all, i_all, p["a"], p["b"]):
            keep = row_i != a
            row_d, row_i = row_d[keep], row_i[keep]
            same = y[row_i] == y[a]
            d_pos = row_d[same].max() if same.any() else 0.0
            d_neg = float(np.sum((x[a] - x[b]) ** 2))
            assert d_pos <= d_neg + 1e-4
            assert d_neg < d_pos + margin + 1e-4

    def test_fallback_covers_out_of_band_anchors(self):
        # well-separated blobs + a neighborhood wide enough to reach
        # other classes: nearest negatives sit far outside the band, so
        # strict semi-hard starves and fallback kicks in
        x, y = _blobs(n=300, noise=0.05)
        m_strict = _miner(x, y, MinerConfig(k_neighbors=80, margin=1e-6,
                                            fallback_nearest=False,
                                            max_negatives=1,
                                            max_positives=0))
        m_fb = _miner(x, y, MinerConfig(k_neighbors=80, margin=1e-6,
                                        fallback_nearest=True,
                                        max_negatives=1,
                                        max_positives=0))
        r_strict = m_strict.mine(n_queries=100, seed=0)
        r_fb = m_fb.mine(n_queries=100, seed=0)
        assert r_fb.stats["n_hard_neg"] > r_strict.stats["n_hard_neg"]
        assert r_fb.stats["n_fallback_neg"] > 0

    def test_miner_deterministic(self):
        x, y = _blobs()
        r1 = _miner(x, y).mine(n_queries=100, seed=7)
        r2 = _miner(x, y).mine(n_queries=100, seed=7)
        for k in ("a", "b", "sim"):
            np.testing.assert_array_equal(r1.pairs[k], r2.pairs[k])

    def test_engine_qps_surfaced(self):
        x, y = _blobs(n=300)
        m = _miner(x, y)
        res = m.mine(n_queries=64, seed=0)
        assert res.stats["engine_qps"] > 0
        assert res.stats["mine_busy_s"] > 0
        assert m.engine.stats()["n_queries"] >= 64


class TestMinedPairSource:
    def _source(self, x, y, pool):
        src = MinedPairSource(x, y, CurriculumSchedule(
            warmup_steps=1, ramp_steps=2, max_mined_frac=0.5))
        src.set_pool(pool)
        return src

    def test_deterministic_under_seed(self):
        x, y = _blobs(n=400)
        pool = _miner(x, y).mine(n_queries=100, seed=0)
        s1 = self._source(x, y, pool).worker_streams(2, 32, seed=5)
        s2 = self._source(x, y, pool).worker_streams(2, 32, seed=5)
        for _ in range(6):
            for a, b in zip(s1, s2):
                ba, bb = next(a), next(b)
                for k in ("xs", "ys", "sim"):
                    np.testing.assert_array_equal(np.asarray(ba[k]),
                                                  np.asarray(bb[k]))

    def test_batch_contract_and_curriculum(self):
        x, y = _blobs(n=400)
        pool = _miner(x, y).mine(n_queries=100, seed=0)
        src = self._source(x, y, pool)
        (stream,) = src.worker_streams(1, 64, seed=0)
        b0 = next(stream)             # warmup: pure uniform
        assert b0["xs"].shape == (64, x.shape[1])
        assert b0["sim"].shape == (64,)
        assert set(np.asarray(b0["sim"]).tolist()) <= {0, 1}
        assert src.schedule.mined_frac(0) == 0.0
        assert src.schedule.mined_frac(3) == 0.5

    def test_pool_swap_picked_up_mid_stream(self):
        x, y = _blobs(n=400)
        pool = _miner(x, y).mine(n_queries=100, seed=0)
        src = self._source(x, y, pool)
        (stream,) = src.worker_streams(1, 32, seed=0)
        next(stream)
        v = src.pool_version
        src.set_pool({"a": np.array([0]), "b": np.array([1]),
                      "sim": np.array([0])})
        assert src.pool_version == v + 1
        next(stream)                  # no restart needed

    def test_trainer_accepts_source(self):
        x, y = _blobs(n=300, d=8, c=4)
        pool = _miner(x, y).mine(n_queries=64, seed=0)
        src = self._source(x, y, pool)
        cfg = DMLTrainConfig(dml=dml.DMLConfig(feat_dim=8, proj_dim=4),
                             ps=sync.PSConfig(n_workers=1),
                             batch_size=64, steps=8, lr=1e-2,
                             log_every=4)
        L, hist = train_dml_distributed(cfg, src)
        assert L.shape == (4, 8)
        # mined batches are deliberately harder than uniform ones, so
        # the raw loss value is not monotone — just pin that the run
        # trained on the source's batches end to end
        assert len(hist) == 3 and np.isfinite(hist[-1]["loss"])


class TestClosedLoop:
    def _cfg(self, d=16, steps=30, **kw):
        return ClosedLoopConfig(
            train=DMLTrainConfig(dml=dml.DMLConfig(feat_dim=d, proj_dim=8),
                                 ps=sync.PSConfig(n_workers=1),
                                 batch_size=64, steps=steps, lr=1e-2,
                                 log_every=10),
            miner=MinerConfig(k_neighbors=10),
            schedule=CurriculumSchedule(warmup_steps=4, ramp_steps=8,
                                        max_mined_frac=0.5),
            mine_queries=128, **kw)

    def test_refresh_bumps_version_and_flushes_cache(self):
        x, y = _blobs(n=400)
        clt = ClosedLoopTrainer(self._cfg(refresh_every=10), x, y)
        eng = clt.engine
        q = x[:4]
        eng.search(q)
        eng.search(q)                 # second hit comes from the LRU
        assert eng.cache_hits > 0 and len(eng._cache) > 0
        v0 = eng.index.version
        L_new = 0.1 * np.ones((8, 16), np.float32)
        clt.refresh(L_new, step=0)
        assert eng.index.version > v0
        hits0 = eng.cache_hits
        eng.search(q)                 # lazy flush fires here
        assert eng.cache_hits == hits0
        assert clt.source.pool_size > 0

    def test_frozen_base_refresh_rebuilds(self):
        x, y = _blobs(n=300)
        clt = ClosedLoopTrainer(self._cfg(index="exact",
                                          refresh_every=10), x, y)
        idx0 = clt.engine.index
        clt.refresh(0.1 * np.ones((8, 16), np.float32), step=0)
        assert clt.engine.index is not idx0

    def test_mutable_ivf_loop_runs(self):
        x, y = _blobs(n=512, c=4)
        cfg = self._cfg(steps=20, index="mutable-ivf",
                        index_kwargs=dict(n_clusters=8, nprobe=8),
                        refresh_every=8)
        clt = ClosedLoopTrainer(cfg, x, y)
        L, hist = clt.run()
        assert hist["summary"]["n_refreshes"] >= 2
        # each swap_metric refresh rebuilt the IVF base under a fresh L
        assert clt.engine.index.n_swaps >= 1
        assert np.isfinite(hist["steps"][-1]["loss"])

    def test_plateau_policy_triggers(self):
        x, y = _blobs(n=300)
        # loss on separated blobs flattens fast; the plateau policy must
        # fire even with periodic refresh disabled
        cfg = self._cfg(steps=40, refresh_every=0, plateau_window=6,
                        plateau_tol=0.5, min_refresh_gap=5)
        _, hist = ClosedLoopTrainer(cfg, x, y).run()
        assert hist["summary"]["n_refreshes"] >= 2

    def test_history_records_staleness(self):
        x, y = _blobs(n=300)
        _, hist = ClosedLoopTrainer(self._cfg(refresh_every=10), x,
                                    y).run()
        stal = [h["staleness"] for h in hist["steps"]]
        assert max(stal) < 10
        assert "mean_staleness" in hist["summary"]
        assert hist["summary"]["total_mined_pairs"] > 0

    def test_no_policy_rejected(self):
        with pytest.raises(ValueError, match="staleness policy"):
            self._cfg(refresh_every=0, plateau_window=0)

    def test_one_worker_runs_the_trainers_loop(self, monkeypatch):
        """The closed loop's steps are the trainer's: one worker's batch
        reaches the step unstacked, and every step is the trainer's
        ``train.batch`` / ``train.step`` spans."""
        from repro.core.ps import trainer
        stacked, spans = [], []
        stack, annotate = trainer._stack_batches, trainer.annotate

        def spy_stack(bs):
            stacked.append(len(bs))
            return stack(bs)

        def spy_annotate(name, **attrs):
            spans.append(name)
            return annotate(name, **attrs)

        monkeypatch.setattr(trainer, "_stack_batches", spy_stack)
        monkeypatch.setattr(trainer, "annotate", spy_annotate)
        x, y = _blobs(n=300)
        _, hist = ClosedLoopTrainer(self._cfg(steps=12, refresh_every=5),
                                    x, y).run()
        assert stacked == []
        assert spans.count("train.step") == spans.count("train.batch") == 12
        assert hist["summary"]["n_refreshes"] == 3

    # refresh steps and every step's loss of the tiny config at
    # refresh_every 10, recorded when the closed loop still built its
    # own step and stacked its one worker's batches
    GOLDEN_REFRESH_STEPS = [0, 10, 20]
    GOLDEN_LOSSES = [
        0.6092922687530518, 0.5941104292869568, 0.5559690594673157,
        0.7475161552429199, 0.5922776460647583, 0.647750973701477,
        0.6268375515937805, 0.7137371301651001, 0.7096372842788696,
        0.9414099454879761, 0.9851362705230713, 0.92722487449646,
        0.936704158782959, 0.8630478382110596, 0.8654141426086426,
        0.9407175779342651, 0.8539681434631348, 0.920179009437561,
        0.8968777656555176, 0.9677304029464722, 0.8992866277694702,
        0.9397128820419312, 0.9389328956604004, 0.8054518699645996,
        0.9553326964378357, 0.8372068405151367, 0.9325768947601318,
        0.9138758182525635, 0.8993270993232727, 1.0445809364318848]

    def test_matches_the_recorded_run(self):
        """The closed loop's refreshes and losses are the recorded ones:
        moving its steps into the trainer's loop changed no number."""
        import dataclasses
        cfg = self._cfg(refresh_every=10)
        cfg = dataclasses.replace(
            cfg, train=dataclasses.replace(cfg.train, log_every=1))
        x, y = _blobs(n=300)
        _, hist = ClosedLoopTrainer(cfg, x, y).run()
        assert ([r["step"] for r in hist["refreshes"]]
                == self.GOLDEN_REFRESH_STEPS)
        assert [h["step"] for h in hist["steps"]] == list(range(30))
        np.testing.assert_allclose([h["loss"] for h in hist["steps"]],
                                   self.GOLDEN_LOSSES, rtol=1e-5)


class TestConvergenceSmoke:
    def test_mined_not_worse_than_uniform_tiny(self):
        """Tiny-scale version of benchmarks/mining_convergence.py: at an
        equal (small) step budget, mined+curriculum ends at least as
        accurate as uniform sampling."""
        cfg = pairdata.PairDatasetConfig(
            n_samples=2000, feat_dim=48, n_classes=32,
            kind="noisy_subspace", noise=0.3, seed=0)
        x, y = pairdata.make_features(cfg)
        tr_x, tr_y, te_x, te_y = x[:1600], y[:1600], x[1600:], y[1600:]
        tcfg = DMLTrainConfig(
            dml=dml.DMLConfig(feat_dim=48, proj_dim=12),
            ps=sync.PSConfig(n_workers=1), batch_size=128, steps=60,
            lr=3e-3, log_every=20)
        idx = pairdata.sample_pair_indices(tr_y, 8000, 8000, seed=1)
        uni = {"xs": tr_x[idx["a"]], "ys": tr_x[idx["b"]],
               "sim": idx["sim"]}
        L_u, _ = train_dml_distributed(tcfg, uni)
        ccfg = ClosedLoopConfig(
            train=tcfg,
            miner=MinerConfig(k_neighbors=15, max_negatives=1,
                              max_positives=3),
            schedule=CurriculumSchedule(warmup_steps=5, ramp_steps=10,
                                        max_mined_frac=0.7),
            refresh_every=10, mine_queries=1600)
        L_m, hist = ClosedLoopTrainer(ccfg, tr_x, tr_y).run()
        acc_u = eval_tasks.knn_accuracy(L_u, tr_x, tr_y, te_x, te_y, k=5)
        acc_m = eval_tasks.knn_accuracy(L_m, tr_x, tr_y, te_x, te_y, k=5)
        assert hist["summary"]["n_refreshes"] >= 4
        assert acc_m >= acc_u - 0.02, (acc_m, acc_u)


class TestMinerFrontend:
    def test_frontend_routed_mining_equals_direct(self):
        """Mining through the scheduler's ``mining`` class must produce
        the exact same pairs as hitting the engine directly — the front
        end shapes the load, it must not change the answers."""
        from repro.serve import RequestScheduler
        x, y = _blobs(n=300)
        k = 10
        L = np.eye(x.shape[1], dtype=np.float32)
        engine = RetrievalEngine(ExactIndex.build(L, x), k_top=k + 1)
        cfg = MinerConfig(k_neighbors=k, max_negatives=2,
                          max_positives=2)
        direct = HardPairMiner(engine, x, y, cfg, warmup=False)
        r_direct = direct.mine(n_queries=64, seed=3)

        sched = RequestScheduler(engine, max_wait_ms=0.0, degrade=False)
        try:
            routed = HardPairMiner(engine, x, y, cfg, warmup=False,
                                   frontend=sched)
            r_routed = routed.mine(n_queries=64, seed=3)
        finally:
            sched.close()
        assert r_routed.stats["n_dropped"] == 0
        for key in ("a", "b", "sim"):
            np.testing.assert_array_equal(r_direct.pairs[key],
                                          r_routed.pairs[key])

    def test_shed_anchors_mine_nothing_and_are_counted(self):
        """Anchors the front end rejects come back unserved: they must
        be dropped (never mined into fake pairs) and counted."""
        from concurrent.futures import Future
        x, y = _blobs(n=300)
        k = 10
        L = np.eye(x.shape[1], dtype=np.float32)
        engine = RetrievalEngine(ExactIndex.build(L, x), k_top=k + 1)

        class SheddingFrontend:
            """Every 2nd submit rejected at admission, like a full
            mining queue would."""
            def __init__(self):
                self.n = 0

            def submit(self, row, k_top, priority):
                self.n += 1
                if self.n % 2 == 0:
                    raise RuntimeError("queue full")
                fut = Future()
                d, i = engine.search(row, k_top=k_top)
                fut.set_result((d, i))
                return fut

        cfg = MinerConfig(k_neighbors=k, max_negatives=2,
                          max_positives=2)
        m = HardPairMiner(engine, x, y, cfg, warmup=False,
                          frontend=SheddingFrontend())
        res = m.mine(n_queries=64, seed=3)
        assert res.stats["n_dropped"] == 32
        assert res.n_pairs > 0
        # every surviving pair references only SERVED anchors — no -1
        # ids or inf distances leaked into the pair set
        assert (res.pairs["a"] >= 0).all() and (res.pairs["b"] >= 0).all()

    def test_oversized_neighborhood_rejected_with_frontend(self):
        from repro.serve import RequestScheduler
        x, y = _blobs(n=100)
        engine = RetrievalEngine(ExactIndex.build(
            np.eye(x.shape[1], dtype=np.float32), x), k_top=5)
        sched = RequestScheduler(engine, max_wait_ms=0.0, degrade=False)
        try:
            with pytest.raises(ValueError, match="k_top"):
                HardPairMiner(engine, x, y,
                              MinerConfig(k_neighbors=10),
                              warmup=False, frontend=sched)
        finally:
            sched.close()


class TestClosedLoopRouter:
    def _cfg(self, d=8, **kw):
        return ClosedLoopConfig(
            train=DMLTrainConfig(dml=dml.DMLConfig(feat_dim=d, proj_dim=4),
                                 ps=sync.PSConfig(n_workers=1),
                                 batch_size=64, steps=10, lr=1e-2,
                                 log_every=10),
            miner=MinerConfig(k_neighbors=10),
            schedule=CurriculumSchedule(warmup_steps=2, ramp_steps=4,
                                        max_mined_frac=0.5),
            mine_queries=64, refresh_every=10, **kw)

    def test_refresh_promotes_through_shadow(self):
        """A metric-swapping refresh registers the fresh L as the
        tenant's shadow arm, mirrors probe traffic, and promotes — the
        serving tenant's metric tracks training via the shadow path."""
        from repro.serve import TenantRouter
        x, y = _blobs(n=200, d=8, c=4)
        router = TenantRouter(x, k_top=10)
        router.add_tenant("prod", np.eye(8, dtype=np.float32))
        router.search("prod", x[0])
        fp0 = router.tenant("prod").fingerprint

        clt = ClosedLoopTrainer(self._cfg(), x, y, router=router,
                                tenant="prod", shadow_probe=4)
        L_new = (0.1 * np.random.RandomState(3)
                 .randn(4, 8)).astype(np.float32)
        rec = clt.refresh(L_new, step=10)
        assert rec["promoted_tenant"] == "prod"
        assert rec["shadow"]["n_mirrored"] >= 1
        t = router.tenant("prod")
        assert t.fingerprint != fp0 and t.shadow is None
        np.testing.assert_array_equal(t.L, L_new)
        # the live tenant now answers under the promoted metric
        _, ids = router.search("prod", x[:3])
        eng = RetrievalEngine(ExactIndex.build(L_new, x), k_top=10)
        _, o_ids = eng.search(x[:3])
        np.testing.assert_array_equal(ids, np.asarray(o_ids))

    def test_router_validation(self):
        from repro.serve import TenantRouter
        x, y = _blobs(n=120, d=8, c=4)
        router = TenantRouter(x)
        router.add_tenant("prod", np.eye(8, dtype=np.float32))
        with pytest.raises(ValueError, match="together"):
            ClosedLoopTrainer(self._cfg(), x, y, router=router)
        with pytest.raises(Exception):
            ClosedLoopTrainer(self._cfg(), x, y, router=router,
                              tenant="nope")
        wrong = TenantRouter(np.zeros((50, 6), np.float32))
        wrong.add_tenant("prod", np.eye(6, dtype=np.float32))
        with pytest.raises(ValueError, match="d_in"):
            ClosedLoopTrainer(self._cfg(), x, y, router=wrong,
                              tenant="prod")
