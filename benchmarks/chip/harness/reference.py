"""Plain references: Eq. 4 training and the exact k-NN scan.

Straightforward ``jax.numpy`` at full f32 precision (``HIGHEST``), written
from the paper (Xie & Xing 2014, Eq. 4) and the definition of a k-nearest
neighbour search. Nothing here imports the program or takes an array the
program made: the references start from the seed and the benchmark's own
data. ``dtype`` selects the precision; ``bfloat16`` gives the control, the
same computation one precision below what the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _dot_t(a, b, dtype):
    """a @ b.T, inputs and output in ``dtype`` (f32 at HIGHEST)."""
    prec = HIGHEST if dtype == jnp.float32 else None
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype),
                               (((a.ndim - 1,), (1,)), ((), ())),
                               precision=prec, preferred_element_type=dtype)


# -- training -----------------------------------------------------------------

def init_factor(ps_seed: int, d_out: int, d_in: int):
    """The trainer's documented initial factor: N(0, 1) / sqrt(d_in) from
    ``PRNGKey(ps_seed)``, shape (d_out, d_in), f32."""
    return (1.0 / np.sqrt(d_in)) * jax.random.normal(
        jax.random.PRNGKey(ps_seed), (d_out, d_in), jnp.float32)


def eq4_loss(L, xs, ys, sim, lam: float, margin: float):
    """Mean over the batch of ||L(x-y)||^2 for similar pairs and
    lam * max(0, margin - ||L(x-y)||^2) for dissimilar ones."""
    dtype = L.dtype
    z = xs.astype(dtype) - ys.astype(dtype)
    proj = _dot_t(z, L, dtype)
    d2 = jnp.sum(proj * proj, axis=-1)
    s = sim.astype(dtype)
    return jnp.mean(s * d2 + (1 - s) * lam * jnp.maximum(0, margin - d2))


@functools.partial(jax.jit, static_argnames=("lam", "margin", "lr"))
def _sgd_step(L, xs, ys, sim, *, lam, margin, lr):
    loss, g = jax.value_and_grad(eq4_loss)(L, xs, ys, sim, lam, margin)
    return (L - jnp.asarray(lr, L.dtype) * g).astype(L.dtype), loss


def train(L0, feats, steps, *, lr: float, lam: float, margin: float,
          dtype=jnp.float32):
    """SGD on Eq. 4 over ``steps``: a list of (a, b, sim) host index arrays
    into ``feats``, one per step (several workers' batches concatenated).
    Returns (losses (n,), L after the first step, L after the last), so
    that only two factors are kept whatever ``L``'s size."""
    L = L0.astype(dtype)
    losses, first = [], None
    for a, b, sim in steps:
        L, loss = _sgd_step(L, feats[jnp.asarray(a)], feats[jnp.asarray(b)],
                            jnp.asarray(sim), lam=lam, margin=margin, lr=lr)
        losses.append(loss)
        if first is None:
            first = L
    return np.array([float(x) for x in losses]), first, L


# -- exact k-NN ---------------------------------------------------------------

def project(L, x, dtype=jnp.float32):
    """(x L^T, its squared row norms) in ``dtype``."""
    p = _dot_t(x, L, dtype)
    return p, jnp.sum(p * p, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "blocks"))
def knn(qp, gp, gn, *, k: int, blocks: int):
    """Exact top-k by squared distance ``|q|^2 + |g|^2 - 2 q.g`` over the
    gallery in ``blocks`` equal row blocks. Returns (dists (Nq, k)
    ascending, ids (Nq, k)); equal distances go to the smaller id."""
    dtype = gp.dtype
    M, d = gp.shape
    rows = M // blocks
    qn = jnp.sum(qp * qp, axis=1, keepdims=True)

    def body(carry, blk):
        bd, bi = carry
        g, n, off = blk
        dist = jnp.maximum(qn + n[None, :] - 2 * _dot_t(qp, g, dtype), 0)
        cd = jnp.concatenate([bd, dist], axis=1)
        ci = jnp.concatenate(
            [bi, jnp.broadcast_to(off + jnp.arange(rows), dist.shape)], axis=1)
        # lax.top_k keeps the earlier position on ties: running best first,
        # then ascending ids, so ties resolve to the smaller id
        neg, pos = jax.lax.top_k(-cd, k)
        return (-neg, jnp.take_along_axis(ci, pos, axis=1)), None

    init = (jnp.full((qp.shape[0], k), jnp.inf, dtype),
            jnp.zeros((qp.shape[0], k), jnp.int32))
    (bd, bi), _ = jax.lax.scan(
        body, init, (gp.reshape(blocks, rows, d), gn.reshape(blocks, rows),
                     jnp.arange(blocks, dtype=jnp.int32) * rows))
    return bd, bi


@jax.jit
def dists_of(qp, gp, ids):
    """Squared distances of each query to the given rows, summed from the
    difference (no cancellation): (Nq, k)."""
    diff = qp[:, None, :] - gp[ids]
    return jnp.sum(diff * diff, axis=-1)
