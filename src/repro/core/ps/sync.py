"""Parameter-server synchronization strategies, mapped to TPU/JAX SPMD.

The paper's system (§4): P workers each hold a local copy ``L_p`` of the
metric; a central server aggregates gradient pushes and broadcasts fresh
parameters; threads run best-effort (fully asynchronous). On a TPU mesh there
is no asynchronous message bus — instead we express the *consistency models*
the PS literature compares (paper §2) as deterministic SPMD programs over a
``workers`` mesh axis:

  * ``bsp``   — Bulk-Synchronous Parallel: gradients are all-reduced (pmean)
                every step; all ``L_p`` stay bit-identical. This is the
                Hadoop/Spark strawman the paper argues against.
  * ``local`` — Local SGD: each worker takes ``tau`` local steps between
                parameter all-reduces. tau plays the role of the *average
                staleness* of the paper's asynchronous PS: copies drift and
                are re-merged. The step computes the merge every step and
                keeps it every ``tau``th, so the semantics are local SGD's
                but the all-reduce traffic is not divided by tau.
  * ``ssp``   — Stale Synchronous Parallel (Ho et al. 2013): every step the
                global mean gradient is computed, but each worker applies a
                randomly *delayed* copy of it (delay <= s drawn from a
                deterministic per-worker PRNG), via an s-slot ring buffer;
                every ``s`` steps parameters are forcibly re-averaged so the
                divergence stays bounded — the SSP bound, in SPMD form.

The per-worker parameter copies are materialized as a leading ``(P, ...)``
axis sharded over the worker mesh axis — i.e. worker p's shard *is* its local
copy. The "central server" is the all-reduce epilogue plus an optional
server-side optimizer applied to aggregated updates.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.optim import Optimizer, apply_updates


@dataclasses.dataclass(frozen=True)
class PSConfig:
    n_workers: int
    sync: str = "bsp"        # bsp | local | ssp
    tau: int = 1             # local-SGD sync period (sync="local")
    staleness: int = 0       # SSP bound s (sync="ssp")
    axis: str = "workers"    # mesh axis name that indexes workers
    seed: int = 0

    def __post_init__(self):
        if self.sync not in ("bsp", "local", "ssp"):
            raise ValueError(f"unknown sync mode {self.sync!r}")
        if self.sync == "ssp" and self.staleness < 1:
            raise ValueError("ssp requires staleness >= 1")
        if self.sync == "local" and self.tau < 1:
            raise ValueError("local requires tau >= 1")


class PSState(NamedTuple):
    params: Any        # (P, ...) worker-stacked parameter copies
    opt_state: Any     # (P, ...) worker-stacked optimizer states
    step: jax.Array    # scalar, replicated
    grad_ring: Any     # (P, s, ...) delayed-gradient ring buffer (ssp) or None
    rng: jax.Array     # scalar PRNG key, replicated


def make_worker_mesh(n_workers: int, axis: str = "workers") -> Mesh:
    """1-D mesh over the first n_workers local devices (laptop-scale tests).

    Production runs instead pass the pod mesh and use its data axis.
    """
    devs = np.array(jax.devices()[:n_workers])
    return Mesh(devs, (axis,))


def replicate_for_workers(params, n_workers: int):
    """Stack identical copies along a new leading worker axis."""
    return jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (n_workers,) + p.shape), params)


def worker_mean(params_stacked):
    """Host-side: collapse worker copies to their mean (final model)."""
    return jax.tree.map(lambda p: jnp.mean(p, axis=0), params_stacked)


def init_state(opt: Optimizer, params, cfg: PSConfig) -> PSState:
    """Build the worker-stacked PS state from single-copy params."""
    opt_state = opt.init(params)
    pstack = replicate_for_workers(params, cfg.n_workers)
    ostack = replicate_for_workers(opt_state, cfg.n_workers)
    if cfg.sync == "ssp":
        ring = jax.tree.map(
            lambda p: jnp.zeros((cfg.n_workers, cfg.staleness) + p.shape, p.dtype),
            params)
    else:
        ring = None
    return PSState(params=pstack, opt_state=ostack,
                   step=jnp.zeros((), jnp.int32), grad_ring=ring,
                   rng=jax.random.PRNGKey(cfg.seed))


def state_sharding(mesh: Mesh, cfg: PSConfig, state: PSState):
    """NamedShardings for a PSState: worker-stacked leaves on the worker axis."""
    ax = cfg.axis

    def spec_like(x, stacked):
        return NamedSharding(mesh, P(ax) if stacked else P())

    return PSState(
        params=jax.tree.map(lambda x: NamedSharding(mesh, P(ax)), state.params),
        opt_state=jax.tree.map(lambda x: NamedSharding(
            mesh, P(ax) if x.ndim >= 1 and x.shape[0] == cfg.n_workers else P()),
            state.opt_state),
        step=NamedSharding(mesh, P()),
        grad_ring=jax.tree.map(lambda x: NamedSharding(mesh, P(ax)),
                               state.grad_ring) if state.grad_ring is not None else None,
        rng=NamedSharding(mesh, P()),
    )


def make_train_step(loss_fn: Callable, opt: Optimizer, cfg: PSConfig,
                    mesh: Mesh) -> Callable:
    """Build the jitted SPMD PS step: (state, batch) -> (state, metrics).

    ``batch`` must have a leading (P, local_batch, ...) worker axis.
    ``loss_fn(params, batch) -> (scalar, aux)``.
    """
    ax = cfg.axis

    def _local(tree):       # strip the size-1 local worker dim
        return jax.tree.map(lambda x: x[0], tree)

    def _stack(tree):       # restore the size-1 local worker dim
        return jax.tree.map(lambda x: x[None], tree)

    def step_fn(state: PSState, batch):
        params = _local(state.params)
        opt_state = _local(state.opt_state)
        batch_l = _local(batch)
        step = state.step

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch_l)

        if cfg.sync == "bsp":
            # server aggregates every step: exact synchronous data-parallel
            grads = jax.lax.pmean(grads, ax)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            ring = None

        elif cfg.sync == "local":
            # worker steps on its own; server merge every tau steps
            updates, opt_state = opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            do_sync = (step + 1) % cfg.tau == 0
            synced = jax.lax.pmean(params, ax)
            params = jax.tree.map(
                lambda s, p: jnp.where(do_sync, s, p), synced, params)
            ring = None

        else:  # ssp — bounded-staleness delayed global gradients
            s = cfg.staleness
            gbar = jax.lax.pmean(grads, ax)                   # server aggregate
            ring = _local(state.grad_ring)                    # (s, ...)
            slot = step % s
            ring = jax.tree.map(lambda r, g: r.at[slot].set(g), ring, gbar)
            # worker-specific delay in [0, s-1], deterministic
            widx = jax.lax.axis_index(ax)
            key = jax.random.fold_in(jax.random.fold_in(state.rng, step), widx)
            delay = jax.random.randint(key, (), 0, s)
            delay = jnp.minimum(delay, step)                  # warmup guard
            read = (step - delay) % s
            g_stale = jax.tree.map(lambda r: r[read], ring)
            updates, opt_state = opt.update(g_stale, opt_state, params)
            params = apply_updates(params, updates)
            # SSP bound: force re-average every s steps
            do_sync = (step + 1) % s == 0
            synced = jax.lax.pmean(params, ax)
            params = jax.tree.map(
                lambda sy, p: jnp.where(do_sync, sy, p), synced, params)
            ring = _stack(ring)

        metrics = {
            "loss": jax.lax.pmean(loss, ax),
            **{k: jax.lax.pmean(v, ax) for k, v in aux.items()},
        }
        new_state = PSState(params=_stack(params), opt_state=_stack(opt_state),
                            step=step + 1, grad_ring=ring, rng=state.rng)
        return new_state, metrics

    ring_spec = P(ax) if cfg.sync == "ssp" else None
    state_specs = PSState(params=P(ax), opt_state=P(ax), step=P(),
                          grad_ring=ring_spec, rng=P())
    shmapped = jax.shard_map(
        step_fn, mesh=mesh,
        in_specs=(state_specs, P(ax)),
        out_specs=(state_specs, P()),
        check_vma=False,
    )
    return jax.jit(shmapped)
