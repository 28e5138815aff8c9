"""End-to-end driver: train the paper's ImageNet-1M metric (21.5M params,
d=21504, k=1000 — Table 1's third row) for a few hundred steps with the
index-based pair pipeline, lr schedule, checkpointing, and optionally the
fused Pallas loss kernel or the multi-worker PS trainer.

Pairs are stored as INDICES into the feature store — at the paper's scale
(200M pairs x 21.5k dims) materialized pairs would be tens of terabytes.

Run:  PYTHONPATH=src python examples/train_imnet1m_dml.py \
          [--steps 300] [--workers 1] [--sync local --tau 8] [--fused]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint, restore_checkpoint
from repro.configs import dml_paper
from repro.core import dml, losses
from repro.core.ps import sync as ps_sync
from repro.core.ps.trainer import stack_worker_streams
from repro.data import pairs as pairdata
from repro.optim import sgd, schedules


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--sync", type=str, default="bsp",
                    choices=["bsp", "local", "ssp"])
    ap.add_argument("--tau", type=int, default=8)
    ap.add_argument("--batch", type=int, default=500)
    ap.add_argument("--samples", type=int, default=10000,
                    help="synthetic stand-in for the 1M LLC images")
    ap.add_argument("--fused", action="store_true",
                    help="use the Pallas fused pair-loss kernel (compiled on "
                         "TPU, interpreted elsewhere)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", type=str, default="/tmp/repro_imnet1m")
    args = ap.parse_args()

    exp = dml_paper.IMNET_1M
    print(f"config: d={exp.dml.feat_dim} k={exp.dml.proj_dim} "
          f"params={exp.dml.feat_dim*exp.dml.proj_dim/1e6:.1f}M "
          f"(paper Table 1: 21.5M)")

    data_cfg = pairdata.PairDatasetConfig(
        n_samples=args.samples, feat_dim=exp.dml.feat_dim, n_classes=100,
        kind="noisy_subspace", noise=0.8, seed=0)
    print("generating LLC-like features (noisy-subspace variant: class "
          "signal in a d/8 subspace + dominant noise dims, so raw Euclidean "
          "fails — the regime the paper targets)...", flush=True)
    features, labels = pairdata.make_features(data_cfg)
    n_hold = args.samples // 5
    train_idx = pairdata.sample_pair_indices(labels[:-n_hold], 50_000,
                                             50_000, seed=1)
    eval_idx = pairdata.sample_pair_indices(labels[-n_hold:], 5_000, 5_000,
                                            seed=2)
    hold = features[-n_hold:]
    eval_pairs = {"xs": hold[eval_idx["a"]], "ys": hold[eval_idx["b"]],
                  "sim": eval_idx["sim"]}

    opt = sgd(schedules.inverse_time(args.lr, 1e-3))
    t0 = time.time()
    hist = []

    if args.workers > 1:
        # partition pair indices over workers (paper §4.1) and run the SPMD
        # PS trainer under the chosen consistency model
        batches = stack_worker_streams(pairdata.IndexPairSource(
            features[:-n_hold], train_idx).worker_streams(
                args.workers, args.batch, seed=10))
        ps_cfg = ps_sync.PSConfig(n_workers=args.workers, sync=args.sync,
                                  tau=args.tau, staleness=max(2, args.tau))
        mesh = ps_sync.make_worker_mesh(args.workers)
        L0 = dml.init_params(exp.dml, jax.random.PRNGKey(0))
        state = ps_sync.init_state(opt, L0, ps_cfg)
        step_fn = ps_sync.make_train_step(
            lambda p, b: losses.dml_pair_loss(p, b, lam=exp.dml.lam,
                                              margin=exp.dml.margin),
            opt, ps_cfg, mesh)
        for t in range(args.steps):
            state, metrics = step_fn(state, next(batches))
            hist.append({"step": t, "loss": float(metrics["loss"])})
            if t % 20 == 0:
                print(f"  step {t}: loss={hist[-1]['loss']:.4f}", flush=True)
        L = ps_sync.worker_mean(state.params)
    else:
        if args.fused:
            from repro.kernels.dml_pair import dml_pair_loss_fused
            loss_fn = lambda p, b: (dml_pair_loss_fused(
                p, b["xs"], b["ys"], b["sim"], exp.dml.lam,
                exp.dml.margin), {})
        else:
            loss_fn = lambda p, b: losses.dml_pair_loss(
                p, b, lam=exp.dml.lam, margin=exp.dml.margin)
        L = dml.init_params(exp.dml, jax.random.PRNGKey(0))
        # scale-aware init: bring initial ||Lz||^2 to O(margin) so both the
        # similar pull and the dissimilar hinge are active from step 0
        probe = next(pairdata.pair_batches_from_indices(
            features[:-n_hold], train_idx, 256, seed=99))
        d2 = float(jnp.mean(dml.mahalanobis_sqdist(L, probe["xs"], probe["ys"])))
        L = L * jnp.sqrt(2.0 * exp.dml.margin / max(d2, 1e-9))
        print(f"  init rescale: mean d2 {d2:.1f} -> ~{2*exp.dml.margin}")
        opt_state = opt.init(L)

        @jax.jit
        def step(L, opt_state, batch):
            (loss, _), g = jax.value_and_grad(
                lambda p, b: loss_fn(p, b), has_aux=True)(L, batch)
            updates, opt_state = opt.update(g, opt_state, L)
            return L + updates, opt_state, loss

        stream = pairdata.pair_batches_from_indices(
            features[:-n_hold], train_idx, args.batch, seed=0)
        for t in range(args.steps):
            L, opt_state, loss = step(L, opt_state, next(stream))
            hist.append({"step": t, "loss": float(loss)})
            if t % 20 == 0:
                print(f"  step {t}: loss={hist[-1]['loss']:.4f}", flush=True)

    wall = time.time() - t0
    print(f"trained {args.steps} steps in {wall:.0f}s "
          f"({wall/args.steps*1e3:.0f} ms/step) "
          f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")

    save_checkpoint(args.ckpt, step=args.steps, tree={"L": L})
    restored, _ = restore_checkpoint(args.ckpt, {"L": L})
    np.testing.assert_array_equal(np.asarray(restored["L"]), np.asarray(L))
    print(f"checkpoint round-trip OK -> {args.ckpt}")

    xs, ys = jnp.asarray(eval_pairs["xs"]), jnp.asarray(eval_pairs["ys"])
    lab = jnp.asarray(eval_pairs["sim"])
    ap_l = float(dml.average_precision(dml.pair_scores(L, xs, ys), lab))
    ap_e = float(dml.average_precision(dml.pair_scores_euclidean(xs, ys), lab))
    print(f"held-out AP: learned {ap_l:.3f} vs euclidean {ap_e:.3f} "
          f"(paper Fig. 4c: learned metric ≫ euclidean)")


if __name__ == "__main__":
    main()
