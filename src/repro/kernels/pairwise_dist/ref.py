"""Pure-jnp oracle for the tiled pairwise-distance kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels._dispatch import (HIGHEST, LANE, matmul_t, pad_axis,
                                     round_up)


def pairwise_sqdist_ref(xp, yp, yn=None):
    """xp (N,k), yp (M,k) projected points (L @ x). Returns (N,M) f32:
    D[i,j] = ||xp_i - yp_j||^2. ``yn`` optionally supplies precomputed
    ||yp||^2 row norms (the retrieval index amortizes them)."""
    xp = xp.astype(jnp.float32)
    yp = yp.astype(jnp.float32)
    xn = jnp.sum(jnp.square(xp), axis=1)
    if yn is None:
        yn = jnp.sum(jnp.square(yp), axis=1)
    # XLA's CPU dot picks its summation order by matrix shape, so the
    # y rows go in lane-padded: a row's distance then does not depend on
    # how many rows share the call (a gallery before and after compaction
    # scores its rows bit-identically). No-op when M is a lane multiple.
    M = yp.shape[0]
    cross = matmul_t(xp, pad_axis(yp, round_up(M, LANE), 0), HIGHEST)[:, :M]
    return jnp.maximum(xn[:, None] + yn[None, :] - 2.0 * cross, 0.0)
