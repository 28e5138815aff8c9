"""High-level distributed DML training loops built on the PS sync layer.

``train_dml_distributed`` is the production-shaped entry point: it takes a
pair dataset, partitions it over workers (paper §4.1), builds the SPMD PS
step for the requested consistency model and runs it, returning the merged
metric plus the objective trace.

Both loops are shape-agnostic in ``d_out``: the trained factor is whatever
``DMLConfig.proj_dim`` / ``l_rank`` says — square (d, d) or low-rank
rectangular (d', d) — and the PS update path (sync.py) treats L as an
opaque pytree leaf, so rank never appears in the sync logic. A low-rank
L drops straight into ``swap_metric`` / index builds; M = L^T L stays PSD
by construction at any rank (no projection step anywhere).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dml, losses
from repro.core.ps import sync
from repro.data.loader import partition_pairs
from repro.data.pairs import pair_batches
from repro.obs import annotate, annotate_step
from repro.optim import Optimizer, sgd


@dataclasses.dataclass(frozen=True)
class DMLTrainConfig:
    dml: dml.DMLConfig
    ps: sync.PSConfig
    batch_size: int = 1000        # per-worker pairs per step (paper: 100/1000)
    steps: int = 200
    lr: float = 1e-2
    log_every: int = 10


def stack_worker_streams(streams) -> Iterator[dict]:
    """Zip per-worker batch streams into (P, B, ...) stacked batches."""
    while True:
        yield _stacked([next(s) for s in streams])


def _stacked(bs) -> dict:
    """The ``train.stack`` profiler span; its result goes straight to the
    stream's ``yield``, so no local holds it while the next is made."""
    with annotate("train.stack"):
        return _stack_batches(bs)


@jax.jit
def _stack_batches(bs):
    """Every key of the workers' batches stacked, in one program."""
    return {k: jnp.stack([b[k] for b in bs]) for k in bs[0]}


def _step_batches(streams) -> Iterator[dict]:
    """The trainer's batches: several workers' stacked, one worker's as its
    stream yields it, for a step made by ``_one_worker_step``."""
    join = _stacked if len(streams) > 1 else _unstacked
    while True:
        yield join([next(s) for s in streams])


def _unstacked(bs) -> dict:
    """The ``train.stack`` span of one worker, which stacks nothing."""
    with annotate("train.stack"):
        return bs[0]


def _one_worker_step(step):
    """``step`` for one worker's batch without its worker axis. The size-1
    axis is added inside the program, where XLA folds it into its
    consumers; stacked outside, it is a copy of the whole batch. The
    state is donated: the trainer holds no other reference to it, and a
    step queued behind others then writes its ``L`` over its input's
    instead of holding a new one. Profiles find the step program by
    ``step_fn`` in its name."""
    @functools.partial(jax.jit, donate_argnums=0)
    def step_fn(state, batch):
        return step(state, jax.tree.map(lambda x: x[None], batch))
    return step_fn


def make_worker_streams(pairs, n_workers: int, batch_size: int, seed: int):
    """Per-worker batch iterators from either pair representation.

    ``pairs`` is pluggable: a pre-sampled pair dict (partitioned over
    workers as in paper §4.1, then streamed with ``pair_batches``) or any
    object with ``worker_streams(n_workers, batch_size, seed)`` — e.g.
    ``mining/stream.MinedPairSource``, whose batches mix uniform and
    index-mined hard pairs under a curriculum.
    """
    if hasattr(pairs, "worker_streams"):
        return pairs.worker_streams(n_workers, batch_size, seed)
    shards = partition_pairs(pairs, n_workers)
    return [pair_batches(s, batch_size, seed=seed + i)
            for i, s in enumerate(shards)]


def _stacked_batches(shards, batch_size, seed) -> Iterator[dict]:
    """Back-compat shim: stream pre-partitioned pair-dict shards."""
    return stack_worker_streams(
        [pair_batches(s, batch_size, seed=seed + i)
         for i, s in enumerate(shards)])


def train_dml_distributed(cfg: DMLTrainConfig, pairs,
                          opt: Optional[Optimizer] = None,
                          mesh=None, rng=None, step_hook=None):
    """Distributed DML training (paper §4) under a chosen sync model.

    ``pairs`` is either a pair dict (the uniform path) or a pluggable
    pair source (see ``make_worker_streams``). ``step_hook(step, L)``,
    if given, is called with the merged metric at every logged step and
    its return value (when not None) lands in that history record under
    ``"hook"`` — e.g. a periodic kNN eval.

    Returns (L_merged, history) — history is a list of per-step metric dicts.
    """
    opt = opt or sgd(cfg.lr)
    mesh = mesh or sync.make_worker_mesh(cfg.ps.n_workers, cfg.ps.axis)
    # seed from the config's explicit seed: dataclass __hash__ varies across
    # Python processes/versions, which silently unseeded distributed runs
    rng = rng if rng is not None else jax.random.PRNGKey(cfg.ps.seed)

    L0 = dml.init_params(cfg.dml, rng)
    state = sync.init_state(opt, L0, cfg.ps)

    def loss_fn(L, batch):
        return losses.dml_pair_loss(L, batch, lam=cfg.dml.lam,
                                    margin=cfg.dml.margin,
                                    compute_dtype=cfg.dml.compute_dtype)

    step_fn = sync.make_train_step(loss_fn, opt, cfg.ps, mesh)
    streams = make_worker_streams(pairs, cfg.ps.n_workers, cfg.batch_size,
                                  cfg.ps.seed)
    if len(streams) == 1:
        step_fn = _one_worker_step(step_fn)
    batches = _step_batches(streams)

    # each iteration's stages are profiler spans (``train`` over the step,
    # ``train.batch`` / ``train.step`` / ``train.log`` within it, and the
    # streams' ``train.draw`` / ``train.gather`` / ``train.stack``); they
    # reach a trace only while the profiler runs
    history = []
    in_flight = None
    for t in range(cfg.steps):
        with annotate_step("train", t):
            with annotate("train.batch"):
                batch = next(batches)
            with annotate("train.step"):
                state, metrics = step_fn(state, batch)
            del batch           # not alive while the next one is made
            # two steps queued at most: a host that runs ahead of the
            # device would keep every queued step's batch and L on it
            if in_flight is not None:
                jax.block_until_ready(in_flight)
            in_flight = metrics
            if t % cfg.log_every == 0 or t == cfg.steps - 1:
                with annotate("train.log"):
                    rec = {"step": t, **jax.tree.map(float, metrics)}
                    if step_hook is not None:
                        out = step_hook(t, sync.worker_mean(state.params))
                        if out is not None:
                            rec["hook"] = out
                    history.append(rec)
    L = sync.worker_mean(state.params)
    return L, history


def train_dml_single(dml_cfg: dml.DMLConfig, pairs: dict, steps: int = 200,
                     batch_size: int = 1000, lr: float = 1e-2, seed: int = 0,
                     opt: Optional[Optimizer] = None, eval_pairs=None,
                     eval_every: int = 0):
    """Single-device reference loop (the t_1 baseline of the speedup curves)."""
    opt = opt or sgd(lr)
    L = dml.init_params(dml_cfg, jax.random.PRNGKey(seed))
    opt_state = opt.init(L)

    def loss_fn(p, b):
        return losses.dml_pair_loss(p, b, lam=dml_cfg.lam, margin=dml_cfg.margin)

    @jax.jit
    def step(L, opt_state, batch):
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(L, batch)
        updates, opt_state = opt.update(g, opt_state, L)
        L = jax.tree.map(lambda p, u: p + u, L, updates)
        return L, opt_state, loss

    batches = pair_batches(pairs, batch_size, seed=seed)
    history = []
    for t in range(steps):
        L, opt_state, loss = step(L, opt_state, next(batches))
        rec = {"step": t, "loss": float(loss)}
        if eval_pairs is not None and eval_every and t % eval_every == 0:
            scores = dml.pair_scores(L, jnp.asarray(eval_pairs["xs"]),
                                     jnp.asarray(eval_pairs["ys"]))
            rec["ap"] = float(dml.average_precision(
                scores, jnp.asarray(eval_pairs["sim"])))
        history.append(rec)
    return L, history
