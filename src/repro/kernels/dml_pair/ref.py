"""Pure-jnp oracle for the fused DML pair kernel (paper Eq. 4 hot spot)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels._dispatch import HIGHEST, matmul_t


def dml_pair_ref(L, xs, ys, sim, lam: float = 1.0, margin: float = 1.0):
    """Returns (losses (B,), sqdists (B,), proj (B, k)).

    losses[b] = sim_b * d2_b + (1-sim_b) * lam * max(0, margin - d2_b)
    where d2_b = ||L (xs_b - ys_b)||^2 computed in f32 at full precision.
    """
    z = (xs - ys).astype(jnp.float32)
    proj = matmul_t(z, L, HIGHEST)                      # (B, k)
    d2 = jnp.sum(jnp.square(proj), axis=-1)             # (B,)
    simf = sim.astype(jnp.float32)
    hinge = jnp.maximum(0.0, margin - d2)
    losses = simf * d2 + (1.0 - simf) * lam * hinge
    return losses, d2, proj
