"""Public wrapper for the pairwise-distance kernel: projection + padding."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels._dispatch import (LANE, SUBLANE, matmul_t, pad_axis,
                                     pick_block, round_up)
from repro.kernels.pairwise_dist.kernel import pairwise_sqdist
from repro.kernels.pairwise_dist.ref import pairwise_sqdist_ref


def metric_sqdist_matrix(L, x, y, *, interpret=None,
                         use_kernel: bool = True):
    """All-pairs Mahalanobis distances: D[i,j] = ||L(x_i - y_j)||^2.

    Projects through L first (O((N+M) k d)), then runs the tiled kernel on
    the much smaller k-dimensional cross term. Any (N, M, k) runs the
    kernel: rows pad to the row tiles and k to a lane multiple with zeros
    (sliced off / distance-neutral). ``interpret`` None compiles on TPU
    and interprets elsewhere.
    """
    xp = matmul_t(x, L)
    yp = matmul_t(y, L)
    if not use_kernel:
        return pairwise_sqdist_ref(xp, yp)
    N, k = xp.shape
    M = yp.shape[0]
    bN = pick_block(N, 256, SUBLANE)
    bM = pick_block(M, 256, LANE)
    kP = round_up(k, LANE)
    bC = next(c for c in (512, 256, LANE) if kP % c == 0)
    xpad = pad_axis(pad_axis(xp, kP, 1), round_up(N, bN), 0)
    ypad = pad_axis(pad_axis(yp, kP, 1), round_up(M, bM), 0)
    xn = jnp.sum(jnp.square(xpad), axis=1)[:, None]
    yn = jnp.sum(jnp.square(ypad), axis=1)[None, :]
    d = pairwise_sqdist(xpad, ypad, xn, yn, block_n=bN, block_m=bM,
                        block_c=bC, interpret=interpret)
    return d[:N, :M]
