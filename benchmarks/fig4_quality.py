"""Fig. 4 reproduction: metric quality — ours (Eq. 4) vs Xing2002 (Eq. 1 PGD
+ eigendecomposition), ITML, KISS and raw Euclidean. Average precision and
precision-recall on held-out pairs, plus single-thread training time.

Paper claims validated:
  * ours reaches the highest AP,
  * Xing2002 is drastically slower per unit of quality (O(d^3) projection),
  * KISS is fast but notably worse,
  * everything learned beats raw Euclidean.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import dml_paper
from repro.core import dml, itml, kiss, xing2002
from repro.core.ps.sync import PSConfig
from repro.core.ps.trainer import DMLTrainConfig, train_dml_distributed
from repro.data import pairs as pairdata

ART = os.path.join(os.path.dirname(__file__), "artifacts")


def evaluate(scores, labels):
    ap = float(dml.average_precision(scores, labels))
    prec, rec = dml.precision_recall_curve(np.asarray(scores),
                                           np.asarray(labels), n_points=25)
    return ap, prec.tolist(), rec.tolist()


def run(scale: int = 8, steps: int = 250, seed: int = 0):
    exp = dml_paper.scaled_down(dml_paper.MNIST, scale)
    d, k = exp.dml.feat_dim, exp.dml.proj_dim
    data_cfg = pairdata.PairDatasetConfig(
        n_samples=exp.n_samples, feat_dim=d, n_classes=10,
        kind="noisy_subspace", seed=seed)
    train_pairs, eval_pairs = pairdata.train_eval_split(
        data_cfg, exp.n_similar, exp.n_dissimilar, 2000, 2000)
    xs = jnp.asarray(eval_pairs["xs"])
    ys = jnp.asarray(eval_pairs["ys"])
    labels = jnp.asarray(eval_pairs["sim"])
    txs = jnp.asarray(train_pairs["xs"])
    tys = jnp.asarray(train_pairs["ys"])
    tsim = jnp.asarray(train_pairs["sim"])
    out = {}

    # ours (Eq. 4, SGD)
    t0 = time.perf_counter()
    tcfg = DMLTrainConfig(dml=exp.dml, ps=PSConfig(n_workers=1, seed=seed),
                          batch_size=exp.batch_size, steps=steps, lr=5e-2)
    L, _ = train_dml_distributed(tcfg, train_pairs)
    t_ours = time.perf_counter() - t0
    ap, pr, rc = evaluate(dml.pair_scores(L, xs, ys), labels)
    out["ours"] = {"ap": ap, "train_s": t_ours, "precision": pr, "recall": rc}

    # Xing2002: PGD + eigendecomposition per step
    t0 = time.perf_counter()
    xcfg = xing2002.XingConfig(feat_dim=d, lr=5e-2, steps=steps // 5)
    M_x, _ = xing2002.fit(xcfg, txs, tys, tsim, batch_size=exp.batch_size)
    t_xing = time.perf_counter() - t0
    ap, pr, rc = evaluate(dml.pair_scores_M(M_x, xs, ys), labels)
    out["xing2002"] = {"ap": ap, "train_s": t_xing, "precision": pr,
                       "recall": rc, "steps": steps // 5}

    # ITML
    t0 = time.perf_counter()
    icfg = itml.ITMLConfig(feat_dim=d, gamma=1e-3, sweeps=2)
    n_c = min(4000, txs.shape[0])
    M_i = itml.fit(icfg, txs[:n_c], tys[:n_c], tsim[:n_c])
    t_itml = time.perf_counter() - t0
    ap, pr, rc = evaluate(dml.pair_scores_M(M_i, xs, ys), labels)
    out["itml"] = {"ap": ap, "train_s": t_itml, "precision": pr, "recall": rc}

    # KISS (one-shot)
    t0 = time.perf_counter()
    kcfg = kiss.KISSConfig(feat_dim=d, pca_dim=min(k, d // 2), ridge=1e-4)
    M_k, proj = kiss.fit(kcfg, txs, tys, tsim)
    t_kiss = time.perf_counter() - t0
    exs = xs @ proj if proj is not None else xs
    eys = ys @ proj if proj is not None else ys
    ap, pr, rc = evaluate(dml.pair_scores_M(M_k, exs, eys), labels)
    out["kiss"] = {"ap": ap, "train_s": t_kiss, "precision": pr, "recall": rc}

    # Euclidean baseline
    ap, pr, rc = evaluate(dml.pair_scores_euclidean(xs, ys), labels)
    out["euclidean"] = {"ap": ap, "train_s": 0.0, "precision": pr,
                        "recall": rc}

    for name, r in out.items():
        print(f"fig4: {name:10s} AP={r['ap']:.4f} train={r['train_s']:.1f}s")
    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, "fig4_quality.json"), "w") as f:
        json.dump(out, f, indent=1)
    return out


def main():
    out = run()
    assert out["ours"]["ap"] >= max(v["ap"] for k, v in out.items()
                                    if k != "ours") - 0.02, \
        "ours should be at or near the best AP (paper Fig. 4)"
    assert out["ours"]["ap"] > out["euclidean"]["ap"]
    assert out["ours"]["train_s"] < out["xing2002"]["train_s"], \
        "Eq.4 must be faster than Eq.1+eigendecomposition per quality"


if __name__ == "__main__":
    main()
