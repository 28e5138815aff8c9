"""Pallas TPU kernel: fused DML pair loss (paper Eq. 4 inner loop).

Computes, in one pass over VMEM tiles of ``L`` (k x d):

    z      = xs - ys                       (fused subtraction, never stored)
    proj   = z @ L^T                       (MXU, accumulated over d tiles)
    d2     = sum(proj^2, axis=k)           (accumulated over k tiles)
    loss   = sim ? d2 : lam * max(0, margin - d2)

Grid: (pairs/bB, k/bK, d/bD) — ``d`` innermost so each (pair, k) tile's
matmul accumulator lives in a VMEM scratch across d steps; ``k`` next so the
per-pair squared-distance accumulator survives across k tiles; the hinge
epilogue fires on the last (k, d) step. TPU-friendly tile defaults are
multiples of the 128-lane MXU; the d-tile (bD) bounds the VMEM working set
(bK x bD weights + bB x bD pair data).

Per-pair vectors (sim in, loss and d2 out) are (B, 1) columns, so their
(bB, 1) blocks line up with the (bB, bK) projection tile without a
relayout. The projection (B, k) is also written out — the backward pass
(ops.py) is two plain matmuls on it, which XLA already schedules
optimally.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._dispatch import HIGHEST, default_interpret


def _dml_pair_kernel(sim_ref, xs_ref, ys_ref, L_ref,
                     loss_ref, d2_ref, proj_ref,
                     acc_ref, *, lam: float, margin: float,
                     nk: int, nd: int):
    """One (pair-tile, k-tile, d-tile) grid step."""
    ki = pl.program_id(1)
    di = pl.program_id(2)

    # fused z = xs - ys on the current (bB, bD) tile, f32 accumulate
    z = (xs_ref[...] - ys_ref[...]).astype(jnp.float32)
    part = jax.lax.dot_general(
        z, L_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)             # (bB, bK)

    @pl.when(di == 0)
    def _init_acc():
        acc_ref[...] = part

    @pl.when(di > 0)
    def _accum():
        acc_ref[...] += part

    @pl.when(di == nd - 1)
    def _k_epilogue():
        proj = acc_ref[...]
        proj_ref[...] = proj.astype(proj_ref.dtype)
        sq = jnp.sum(jnp.square(proj), axis=1, keepdims=True)  # (bB, 1)

        @pl.when(ki == 0)
        def _init_d2():
            d2_ref[...] = sq

        @pl.when(ki > 0)
        def _acc_d2():
            d2_ref[...] += sq

        @pl.when(ki == nk - 1)
        def _loss_epilogue():
            d2 = d2_ref[...]
            simf = sim_ref[...].astype(jnp.float32)
            hinge = jnp.maximum(0.0, margin - d2)
            loss_ref[...] = simf * d2 + (1.0 - simf) * lam * hinge


@functools.partial(jax.jit, static_argnames=("lam", "margin", "block_b",
                                             "block_k", "block_d",
                                             "interpret"))
def dml_pair_fused(L, xs, ys, sim, *, lam: float = 1.0, margin: float = 1.0,
                   block_b: int = 256, block_k: int = 128, block_d: int = 512,
                   interpret=None):
    """Fused forward. ``sim`` is a (B, 1) column. Returns (losses (B, 1),
    d2 (B, 1), proj (B, k)).

    Shapes must tile evenly (ops.py pads otherwise): B % block_b == 0,
    k % block_k == 0, d % block_d == 0. ``interpret`` None compiles on
    TPU and interprets elsewhere.
    """
    k, d = L.shape
    B = xs.shape[0]
    assert sim.shape == (B, 1), sim.shape
    bB, bK, bD = min(block_b, B), min(block_k, k), min(block_d, d)
    assert B % bB == 0 and k % bK == 0 and d % bD == 0, (B, k, d, bB, bK, bD)
    nb, nk, nd = B // bB, k // bK, d // bD

    kernel = functools.partial(_dml_pair_kernel, lam=lam, margin=margin,
                               nk=nk, nd=nd)
    return pl.pallas_call(
        kernel,
        grid=(nb, nk, nd),
        in_specs=[
            pl.BlockSpec((bB, 1), lambda b, ki, di: (b, 0)),        # sim
            pl.BlockSpec((bB, bD), lambda b, ki, di: (b, di)),      # xs
            pl.BlockSpec((bB, bD), lambda b, ki, di: (b, di)),      # ys
            pl.BlockSpec((bK, bD), lambda b, ki, di: (ki, di)),     # L
        ],
        out_specs=[
            pl.BlockSpec((bB, 1), lambda b, ki, di: (b, 0)),        # loss
            pl.BlockSpec((bB, 1), lambda b, ki, di: (b, 0)),        # d2
            pl.BlockSpec((bB, bK), lambda b, ki, di: (b, ki)),      # proj
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bB, bK), jnp.float32)],
        interpret=default_interpret(interpret),
        name="dml_pair",
    )(sim, xs, ys, L)
