"""The paper's motivating applications (§1): the learned metric must improve
kNN classification and k-means clustering over raw Euclidean distance."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dml, eval_tasks
from repro.core.ps.sync import PSConfig
from repro.core.ps.trainer import DMLTrainConfig, train_dml_distributed
from repro.data import pairs as pairdata


@pytest.fixture(scope="module")
def trained_metric():
    cfg = pairdata.PairDatasetConfig(
        n_samples=1200, feat_dim=48, n_classes=6, kind="noisy_subspace",
        noise=0.5, seed=0)
    x, y = pairdata.make_features(cfg)
    train_x, train_y = x[:900], y[:900]
    test_x, test_y = x[900:], y[900:]
    idx = pairdata.sample_pair_indices(train_y, 4000, 4000, seed=1)
    train_pairs = {"xs": train_x[idx["a"]], "ys": train_x[idx["b"]],
                   "sim": idx["sim"]}
    tcfg = DMLTrainConfig(dml=dml.DMLConfig(feat_dim=48, proj_dim=24),
                          ps=PSConfig(n_workers=1, seed=0), batch_size=256,
                          steps=250, lr=2e-2)
    L, _ = train_dml_distributed(tcfg, train_pairs)
    return L, train_x, train_y, test_x, test_y


class TestKNN:
    def test_learned_metric_beats_euclidean(self, trained_metric):
        L, train_x, train_y, test_x, test_y = trained_metric
        acc_l = eval_tasks.knn_accuracy(L, train_x, train_y, test_x, test_y)
        acc_e = eval_tasks.knn_accuracy(None, train_x, train_y,
                                        test_x, test_y)
        assert acc_l > acc_e + 0.1, (acc_l, acc_e)
        assert acc_l > 0.8

    def test_topk_selection_matches_full_argsort(self):
        """Regression: knn_classify uses lax.top_k (k-selection) instead
        of a full argsort over the (n_test, n_train) distance matrix —
        the neighbor sets and predictions must agree with the old path."""
        from repro.kernels.pairwise_dist import metric_sqdist_matrix
        rng = np.random.RandomState(3)
        train_x = rng.randn(160, 24).astype(np.float32)
        train_y = rng.randint(0, 5, 160).astype(np.int32)
        test_x = rng.randn(48, 24).astype(np.float32)
        L = 0.4 * rng.randn(12, 24).astype(np.float32)
        for k in (1, 5, 16):
            D = metric_sqdist_matrix(L, jnp.asarray(test_x),
                                     jnp.asarray(train_x))
            nn_old = np.asarray(jnp.argsort(D, axis=1)[:, :k])
            _, nn_new = jax.lax.top_k(-D, k)
            np.testing.assert_array_equal(np.asarray(nn_new), nn_old)
            pred = eval_tasks.knn_classify(L, train_x, train_y, test_x,
                                           k=k)
            votes = train_y[nn_old]
            expect = np.array([np.argmax(np.bincount(v, minlength=5))
                               for v in votes])
            np.testing.assert_array_equal(np.asarray(pred), expect)

    def test_knn_perfect_on_separated_data(self):
        rng = np.random.RandomState(0)
        centers = 10 * rng.randn(3, 8)
        y = rng.randint(0, 3, 120)
        x = centers[y] + 0.1 * rng.randn(120, 8)
        acc = eval_tasks.knn_accuracy(None, x[:80], y[:80], x[80:], y[80:],
                                      k=3)
        assert acc == 1.0


class TestClustering:
    def test_learned_metric_improves_purity(self, trained_metric):
        L, train_x, train_y, _, _ = trained_metric
        a_l, _ = eval_tasks.metric_kmeans(L, train_x, 6, seed=0)
        a_e, _ = eval_tasks.metric_kmeans(None, train_x, 6, seed=0)
        p_l = eval_tasks.clustering_purity(a_l, train_y)
        p_e = eval_tasks.clustering_purity(a_e, train_y)
        assert p_l > p_e + 0.1, (p_l, p_e)

    def test_purity_bounds(self):
        labels = np.array([0, 0, 1, 1])
        assert eval_tasks.clustering_purity(np.array([0, 0, 1, 1]),
                                            labels) == 1.0
        assert eval_tasks.clustering_purity(np.array([0, 0, 0, 0]),
                                            labels) == 0.5


class TestTripletExtension:
    """Paper §4: the framework 'can be easily extended to support
    triple-wise constraints' — train with the triplet objective end to end."""

    def test_triplet_training_beats_euclidean(self):
        from repro.core import losses
        from repro.optim import sgd
        cfg = pairdata.PairDatasetConfig(
            n_samples=900, feat_dim=32, n_classes=5, kind="noisy_subspace",
            noise=0.5, seed=3)
        x, y = pairdata.make_features(cfg)
        tr_x, tr_y, te_x, te_y = x[:700], y[:700], x[700:], y[700:]
        tri = pairdata.sample_triplet_indices(tr_y, 6000, seed=0)
        stream = pairdata.triplet_batches_from_indices(tr_x, tri, 256, seed=0)
        dcfg = dml.DMLConfig(feat_dim=32, proj_dim=16)
        L = dml.init_params(dcfg, jax.random.PRNGKey(0))
        opt = sgd(2e-2)
        opt_state = opt.init(L)

        @jax.jit
        def step(L, opt_state, batch):
            (loss, _), g = jax.value_and_grad(
                lambda p, b: losses.dml_triplet_loss(p, b), has_aux=True)(
                    L, batch)
            u, opt_state = opt.update(g, opt_state, L)
            return L + u, opt_state, loss

        first = last = None
        for t in range(200):
            L, opt_state, loss = step(L, opt_state, next(stream))
            first = float(loss) if first is None else first
            last = float(loss)
        assert last < 0.5 * first
        acc_l = eval_tasks.knn_accuracy(L, tr_x, tr_y, te_x, te_y)
        acc_e = eval_tasks.knn_accuracy(None, tr_x, tr_y, te_x, te_y)
        assert acc_l > acc_e + 0.05, (acc_l, acc_e)
