"""Pallas TPU kernel: tiled all-pairs squared distances in metric space.

The retrieval/kNN evaluation hot spot (paper §5.4: scoring 200k held-out
pairs, and metric-space retrieval generally): given projected points
``xp = x @ L^T`` (N, k) and ``yp`` (M, k),

    D[i, j] = ||xp_i||^2 + ||yp_j||^2 - 2 xp_i . yp_j

Grid: (N/bN, M/bM, k/bC) — the contraction dim innermost, cross-term
accumulated in VMEM scratch via the MXU. Row norms come in precomputed
(ops.py) as an (N, 1) column and a (1, M) row, the layouts that
broadcast straight against the (bN, bM) tile in the epilogue.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._dispatch import HIGHEST, default_interpret


def _pd_kernel(x_ref, y_ref, xn_ref, yn_ref, o_ref, cross_ref, *, nc: int):
    ci = pl.program_id(2)
    part = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), y_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(ci == 0)
    def _init():
        cross_ref[...] = part

    @pl.when(ci > 0)
    def _acc():
        cross_ref[...] += part

    @pl.when(ci == nc - 1)
    def _epilogue():
        d = xn_ref[...] + yn_ref[...] - 2.0 * cross_ref[...]
        o_ref[...] = jnp.maximum(d, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "block_c",
                                             "interpret"))
def pairwise_sqdist(xp, yp, xn, yn, *, block_n: int = 256,
                    block_m: int = 256, block_c: int = 512, interpret=None):
    """xp (N,k), yp (M,k) with row norms xn (N, 1), yn (1, M) -> (N,M)
    f32 squared distances. Shapes must tile evenly (ops.py pads
    otherwise)."""
    N, k = xp.shape
    M = yp.shape[0]
    bN, bM, bC = min(block_n, N), min(block_m, M), min(block_c, k)
    assert N % bN == 0 and M % bM == 0 and k % bC == 0, (N, M, k, bN, bM, bC)
    nc = k // bC

    kernel = functools.partial(_pd_kernel, nc=nc)
    return pl.pallas_call(
        kernel,
        grid=(N // bN, M // bM, nc),
        in_specs=[
            pl.BlockSpec((bN, bC), lambda i, j, c: (i, c)),
            pl.BlockSpec((bM, bC), lambda i, j, c: (j, c)),
            pl.BlockSpec((bN, 1), lambda i, j, c: (i, 0)),
            pl.BlockSpec((1, bM), lambda i, j, c: (0, j)),
        ],
        out_specs=pl.BlockSpec((bN, bM), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, M), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bN, bM), jnp.float32)],
        interpret=default_interpret(interpret),
        name="pairwise_dist",
    )(xp, yp, xn, yn)
