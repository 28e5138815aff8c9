"""Jitted public wrapper for the fused DML pair kernel, with custom VJP.

Forward: the Pallas kernel (fused z / matmul / sumsq / hinge).
Backward: closed-form gradients — two dense matmuls on the saved projection
(XLA-optimal; no kernel needed):

    w_b    = sim_b - lam * (1 - sim_b) * 1{d2_b < margin}   (hinge weight)
    dL     = 2/B * (proj * w)^T @ z * g
    dz     = 2/B * w * (proj @ L) * g ;  dxs = dz, dys = -dz
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels._dispatch import (HIGHEST, LANE, SUBLANE, matmul_t,
                                     pad_axis, pick_block, round_up)
from repro.kernels.dml_pair.kernel import dml_pair_fused
from repro.kernels.dml_pair.ref import dml_pair_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def dml_pair_loss_fused(L, xs, ys, sim, lam: float = 1.0, margin: float = 1.0,
                        interpret=None):
    """Mean Eq. 4 objective via the Pallas kernel. Differentiable w.r.t.
    L, xs, ys (the latter two enable end-to-end deep metric learning).
    ``interpret`` None compiles on TPU and interprets elsewhere."""
    losses = _forward(L, xs, ys, sim, lam, margin, interpret)[0]
    return jnp.mean(losses)


def _forward(L, xs, ys, sim, lam, margin, interpret):
    k, d = L.shape
    B = xs.shape[0]
    # pad to tile boundaries (sim=1, x=y=0 padding contributes zero loss;
    # zero L rows/columns change no distance). A lane-dim tile under one
    # block spans the whole dim, which is always a legal block.
    bB = pick_block(B, 256, SUBLANE)
    bK = min(k, LANE)
    bD = min(d, 512)
    kP, dP, BP = round_up(k, bK), round_up(d, bD), round_up(B, bB)
    Lp = pad_axis(pad_axis(L, kP, 0), dP, 1)
    xsp = pad_axis(pad_axis(xs, dP, 1), BP, 0)
    ysp = pad_axis(pad_axis(ys, dP, 1), BP, 0)
    simp = pad_axis(sim, BP, 0, value=1)[:, None]
    losses, d2, proj = dml_pair_fused(
        Lp, xsp, ysp, simp, lam=lam, margin=margin,
        block_b=bB, block_k=bK, block_d=bD, interpret=interpret)
    return losses[:B, 0], d2[:B, 0], proj[:B, :k]


def _fwd(L, xs, ys, sim, lam, margin, interpret):
    losses, d2, proj = _forward(L, xs, ys, sim, lam, margin, interpret)
    return jnp.mean(losses), (L, xs, ys, sim, d2, proj)


def _bwd(lam, margin, interpret, res, g):
    L, xs, ys, sim, d2, proj = res
    B = xs.shape[0]
    simf = sim.astype(jnp.float32)
    active = (d2 < margin).astype(jnp.float32)
    w = simf - lam * (1.0 - simf) * active              # (B,)
    z = (xs - ys).astype(jnp.float32)
    scale = 2.0 * g / B
    pw = proj * w[:, None]                              # (B,k)
    dL = scale * matmul_t(pw.T, z.T, HIGHEST)           # (k,d)
    dz = scale * matmul_t(pw, L.T, HIGHEST)             # (B,d)
    return (dL.astype(L.dtype), dz.astype(xs.dtype), (-dz).astype(ys.dtype),
            None)


dml_pair_loss_fused.defvjp(_fwd, _bwd)


def dml_pair_loss_reference(L, xs, ys, sim, lam: float = 1.0,
                            margin: float = 1.0):
    """Oracle mean objective (pure jnp) for tests and CPU execution."""
    losses, _, _ = dml_pair_ref(L, xs, ys, sim, lam, margin)
    return jnp.mean(losses)
