"""Quickstart: learn a Mahalanobis metric with the paper's Eq. 4 objective.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp

from repro.core import dml
from repro.core.ps.sync import PSConfig
from repro.core.ps.trainer import DMLTrainConfig, train_dml_distributed
from repro.data import pairs as pairdata


def main():
    # class-structured features where Euclidean distance is misleading
    cfg = pairdata.PairDatasetConfig(
        n_samples=2000, feat_dim=64, n_classes=8, kind="noisy_subspace",
        noise=0.5, seed=0)
    train_pairs, eval_pairs = pairdata.train_eval_split(
        cfg, n_train_sim=4000, n_train_dis=4000,
        n_eval_sim=1000, n_eval_dis=1000)

    # the paper's reformulated objective:  M = L^T L,  hinge on dissimilars
    dml_cfg = dml.DMLConfig(feat_dim=64, proj_dim=32, lam=1.0, margin=1.0)
    cfg = DMLTrainConfig(dml=dml_cfg, ps=PSConfig(n_workers=1, seed=0),
                         batch_size=512, steps=300, lr=2e-2)
    L, history = train_dml_distributed(cfg, train_pairs)
    print(f"objective: {history[0]['loss']:.3f} -> {history[-1]['loss']:.3f}")

    xs, ys = jnp.asarray(eval_pairs["xs"]), jnp.asarray(eval_pairs["ys"])
    labels = jnp.asarray(eval_pairs["sim"])
    ap_learned = float(dml.average_precision(dml.pair_scores(L, xs, ys), labels))
    ap_euclid = float(dml.average_precision(
        dml.pair_scores_euclidean(xs, ys), labels))
    print(f"held-out AP: learned metric {ap_learned:.3f} "
          f"vs euclidean {ap_euclid:.3f}")
    assert ap_learned > ap_euclid


if __name__ == "__main__":
    main()
