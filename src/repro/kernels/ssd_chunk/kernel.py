"""Pallas TPU kernel: Mamba2 SSD chunk scan with VMEM-resident tiles.

The §Perf A analysis showed the pure-JAX chunked SSD is bound by chunk-tile
materialization: every (Q,Q) decay/attention tile and (Q,p) partial takes
an HBM round trip between XLA fusions. This kernel computes a whole chunk
per grid step entirely in VMEM — HBM traffic becomes inputs + outputs only.

Grid: (B*H panes, T/Q chunks), chunk dim sequential ("arbitrary") so the
(p, n) SSM state is carried in VMEM scratch across chunks. Per chunk step
(all on-chip):

    W      = cumsum(la)                       (Q,)   cumulative log decay
    y_int  = (C h^T) * exp(W)[:,None]         inter-chunk term
    G      = C B^T                            (Q,Q)  MXU
    att    = tril(G * exp(W_t - W_s)) * dt_s  (Q,Q)
    y      = y_int + att @ xs                 (Q,p)  MXU
    h'     = exp(W_last) h + ((dt*exp(W_last-W)) * xs)^T B

Per-head layout (p = head_dim, n = state) keeps tiles small: Q=128, p=64,
n=64 -> ~200 KB VMEM per pane, MXU-aligned contractions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._dispatch import default_interpret


def _ssd_kernel(xs_ref, B_ref, C_ref, dt_ref, la_ref, y_ref, hout_ref,
                h_ref, *, nc: int, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xs = xs_ref[0].astype(jnp.float32)                  # (Q, p)
    Bm = B_ref[0].astype(jnp.float32)                   # (Q, n)
    Cm = C_ref[0].astype(jnp.float32)                   # (Q, n)
    dt = dt_ref[0].astype(jnp.float32)                  # (Q,)
    la = la_ref[0].astype(jnp.float32)                  # (Q,)

    W = jnp.cumsum(la)                                  # (Q,)
    W_last = W[-1]

    # inter-chunk: y_t += exp(W_t) * (h C_t)
    y_int = jax.lax.dot_general(Cm, h_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (Q,p)
    y_int = y_int * jnp.exp(W)[:, None]

    # intra-chunk: att[t,s] = 1{s<=t} (C_t.B_s) exp(W_t - W_s) dt_s
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)      # (Q,Q)
    Wdiff = W[:, None] - W[None, :]
    tmask = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    att = jnp.where(tmask, G * jnp.exp(Wdiff), 0.0) * dt[None, :]
    y = y_int + jax.lax.dot_general(att, xs, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    y_ref[0, ...] = y.astype(y_ref.dtype)

    # state update: h' = exp(W_last) h + (xs * src)^T B, src = dt exp(W_last-W)
    src = dt * jnp.exp(W_last - W)                      # (Q,)
    xsrc = xs * src[:, None]                            # (Q, p)
    h_ref[...] = (jnp.exp(W_last) * h_ref[...]
                  + jax.lax.dot_general(xsrc, Bm, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))

    @pl.when(ci == nc - 1)
    def _emit_state():
        hout_ref[0, ...] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xs, Bm, Cm, dt, la, *, chunk: int = 128, interpret=None):
    """Pane-parallel SSD scan. Shapes per ref.py: xs (G,T,p), Bm/Cm (G,T,n),
    dt/la (G,T). Returns (y (G,T,p), h_final (G,p,n))."""
    G, T, p = xs.shape
    n = Bm.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk

    kernel = functools.partial(_ssd_kernel, nc=nc, chunk=chunk)
    y, hf = pl.pallas_call(
        kernel,
        grid=(G, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, chunk), lambda g, c: (g, c)),
            pl.BlockSpec((1, chunk), lambda g, c: (g, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, p, n), lambda g, c: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, T, p), xs.dtype),
            jax.ShapeDtypeStruct((G, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=default_interpret(interpret),
    )(xs, Bm, Cm, dt, la)
    return y, hf
