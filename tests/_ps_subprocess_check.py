"""Multi-device PS sync checks, run in a subprocess with 8 host devices.

Invoked by tests/test_ps_sync.py. Exits non-zero on failure; prints a JSON
summary on success. Kept standalone so the main pytest process stays at one
device (dry-run rule: never force device count globally).
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import dml  # noqa: E402
from repro.core import losses as losses_mod  # noqa: E402
from repro.core.ps import sync, trainer  # noqa: E402
from repro.data import pairs as pairdata  # noqa: E402
from repro.optim import sgd  # noqa: E402


def stacked_batches(pairs, n_workers, batch_size, seed):
    """The workers' batches stacked on a leading (P, ...) axis, as the
    trainer feeds several workers' steps."""
    return trainer._step_batches(trainer.make_worker_streams(
        pairs, n_workers, batch_size, seed))


def main():
    assert jax.device_count() == 8, jax.device_count()
    out = {}

    cfg = pairdata.PairDatasetConfig(
        n_samples=400, feat_dim=24, n_classes=4, noise=1.0, seed=0)
    train_pairs, eval_pairs = pairdata.train_eval_split(cfg, 1500, 1500, 400, 400)
    dcfg = dml.DMLConfig(feat_dim=24, proj_dim=12)

    # --- BSP with P workers must equal single-device SGD on the merged batch
    # (sanity of the "server aggregation = all-reduce" mapping).
    ps_cfg = sync.PSConfig(n_workers=4, sync="bsp")
    tcfg = trainer.DMLTrainConfig(dml=dcfg, ps=ps_cfg, batch_size=128,
                                  steps=60, lr=5e-2)
    L_bsp, hist_bsp = trainer.train_dml_distributed(tcfg, train_pairs)
    assert hist_bsp[-1]["loss"] < hist_bsp[0]["loss"], "BSP loss did not drop"
    out["bsp_loss_first"] = hist_bsp[0]["loss"]
    out["bsp_loss_last"] = hist_bsp[-1]["loss"]

    # BSP keeps worker copies bit-identical
    state = sync.init_state(sgd(0.05), dml.init_params(dcfg, jax.random.PRNGKey(0)),
                            ps_cfg)
    mesh = sync.make_worker_mesh(4)
    step = sync.make_train_step(lambda p, b: losses_mod.dml_pair_loss(p, b),
                                sgd(0.05), ps_cfg, mesh)
    batches = stacked_batches(train_pairs, 4, 64, seed=0)
    for _ in range(3):
        state, _ = step(state, next(batches))
    pstack = np.asarray(state.params)
    for w in range(1, 4):
        np.testing.assert_allclose(pstack[0], pstack[w], rtol=0, atol=0)
    out["bsp_identical"] = True

    # --- Local SGD (tau=5): copies drift between syncs, merge on sync steps
    ps_local = sync.PSConfig(n_workers=4, sync="local", tau=5)
    tcfg_l = trainer.DMLTrainConfig(dml=dcfg, ps=ps_local, batch_size=128,
                                    steps=60, lr=5e-2)
    L_loc, hist_loc = trainer.train_dml_distributed(tcfg_l, train_pairs)
    assert hist_loc[-1]["loss"] < hist_loc[0]["loss"], "local-SGD loss did not drop"
    out["local_loss_last"] = hist_loc[-1]["loss"]

    state = sync.init_state(sgd(0.05), dml.init_params(dcfg, jax.random.PRNGKey(1)),
                            ps_local)
    step_l = sync.make_train_step(lambda p, b: losses_mod.dml_pair_loss(p, b),
                                  sgd(0.05), ps_local, mesh)
    batches = stacked_batches(train_pairs, 4, 64, seed=1)
    # after 2 steps (not a sync step), copies must differ
    for _ in range(2):
        state, _ = step_l(state, next(batches))
    pstack = np.asarray(state.params)
    assert np.abs(pstack[0] - pstack[1]).max() > 1e-7, "local copies did not drift"
    # after 5 steps (sync step), copies must coincide
    for _ in range(3):
        state, _ = step_l(state, next(batches))
    pstack = np.asarray(state.params)
    np.testing.assert_allclose(pstack[0], pstack[3], atol=1e-6)
    out["local_drift_and_merge"] = True

    # --- SSP (s=3) converges too
    ps_ssp = sync.PSConfig(n_workers=4, sync="ssp", staleness=3)
    tcfg_s = trainer.DMLTrainConfig(dml=dcfg, ps=ps_ssp, batch_size=128,
                                    steps=60, lr=5e-2)
    L_ssp, hist_ssp = trainer.train_dml_distributed(tcfg_s, train_pairs)
    assert hist_ssp[-1]["loss"] < hist_ssp[0]["loss"], "SSP loss did not drop"
    out["ssp_loss_last"] = hist_ssp[-1]["loss"]

    # --- all three beat Euclidean on held-out AP
    xs, ys = jnp.asarray(eval_pairs["xs"]), jnp.asarray(eval_pairs["ys"])
    lab = jnp.asarray(eval_pairs["sim"])
    ap_e = float(dml.average_precision(dml.pair_scores_euclidean(xs, ys), lab))
    for name, L in [("bsp", L_bsp), ("local", L_loc), ("ssp", L_ssp)]:
        ap = float(dml.average_precision(dml.pair_scores(L, xs, ys), lab))
        out[f"ap_{name}"] = ap
        assert ap > ap_e, f"{name}: AP {ap} <= euclidean {ap_e}"
    out["ap_euclidean"] = ap_e

    print("PS_CHECK_OK " + json.dumps(out))


if __name__ == "__main__":
    main()
