"""Pallas TPU flash attention (forward): GQA, causal and sliding-window.

Grid: (B * H, T/bQ, S/bK) with the KV dimension innermost ("arbitrary"
semantics) so the running max / denominator / accumulator for one q tile
live in VMEM scratch across KV steps — the streaming-softmax algorithm with
no (T, S) materialization. GQA is expressed in the k/v BlockSpec index maps
(q head h reads kv head h // group), so no head replication is stored.

The online-softmax update per KV tile:
    m'   = max(m, rowmax(s))
    p    = exp(s - m')
    corr = exp(m - m')
    l'   = corr * l + rowsum(p)
    acc' = corr * acc + p @ v
with the division by l deferred to the last KV step. Tiles masked fully out
(causal/sliding) are skipped via the index bounds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._dispatch import default_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, nk: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # (bQ, Dh)
    k = k_ref[0].astype(jnp.float32)                    # (bK, Dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1)
    acc_ref[...] = (corr[:, None] * acc_ref[...]
                    + jax.lax.dot_general(
                        p, v_ref[0].astype(jnp.float32),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    interpret=None):
    """q (B,T,H,Dh); k,v (B,S,K,Dh), H % K == 0. Returns (B,T,H,Dh)."""
    B, T, H, dh = q.shape
    S, K = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    group = H // K
    bQ, bK = min(block_q, T), min(block_k, S)
    assert T % bQ == 0 and S % bK == 0, (T, S, bQ, bK)
    nq, nk = T // bQ, S // bK
    scale = 1.0 / np.sqrt(dh)

    # layout: fold heads into the leading grid dim; block index maps pick the
    # right (batch, head) pane and the GQA kv head = h // group
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, T, dh)
    kt = k.transpose(0, 2, 1, 3).reshape(B * K, S, dh)
    vt = v.transpose(0, 2, 1, 3).reshape(B * K, S, dh)

    def kv_index(bh, qi, kj):
        b = bh // H
        h = (bh % H) // group
        return (b * K + h, kj, 0)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, block_q=bQ, block_k=bK, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bQ, dh), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, bK, dh), kv_index),
            pl.BlockSpec((1, bK, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bQ, dh), lambda bh, qi, kj: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bQ,), jnp.float32),
            pltpu.VMEM((bQ,), jnp.float32),
            pltpu.VMEM((bQ, dh), jnp.float32),
        ],
        interpret=default_interpret(interpret),
    )(qt, kt, vt)
    return out.reshape(B, H, T, dh).transpose(0, 2, 1, 3)
