"""Mamba2 (SSD — state-space duality) block, chunked for the TPU MXU.

Recurrence per head h (head_dim p, state n):
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t B_t^T        (h: (p, n))
    y_t = h_t C_t + D * x_t

Chunked evaluation (Dao & Gu 2024), scan over chunks of length Q:
  intra-chunk: attention-like lower-triangular term with cumulative decays,
  inter-chunk: carried state h updated once per chunk.
Both terms are dense einsums -> MXU-friendly; the scan carries only the
(heads, p, n) state. Decode is the exact single-step recurrence.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import common
from repro.sharding import constrain


class MambaCache(NamedTuple):
    h: jax.Array        # (B, H, p, n) SSM state
    conv: jax.Array     # (B, W-1, conv_channels) causal-conv history


def _dims(cfg: ArchConfig):
    d_in = cfg.ssm_expand * cfg.d_model
    H = cfg.ssm_heads
    p = d_in // H
    n = cfg.ssm_state
    conv_ch = d_in + 2 * n
    return d_in, H, p, n, conv_ch


def init_mamba2(cfg: ArchConfig, rng) -> dict:
    d = cfg.d_model
    d_in, H, p, n, conv_ch = _dims(cfg)
    ks = jax.random.split(rng, 8)
    dt = jnp.exp(jax.random.uniform(ks[5], (H,), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return {
        "w_z": common.he_init(ks[0], (d, d_in), d),
        "w_xbc": common.he_init(ks[1], (d, conv_ch), d),
        "w_dt": common.he_init(ks[2], (d, H), d),
        "conv_w": 0.1 * jax.random.normal(ks[3], (cfg.conv_width, conv_ch)),
        "conv_b": jnp.zeros((conv_ch,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(dt)),                  # softplus inverse
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "norm_scale": jnp.ones((d_in,), jnp.float32),
        "w_out": common.he_init(ks[4], (d_in, d), d_in),
    }


def logical_axes(cfg: ArchConfig) -> dict:
    return {
        "w_z": ("embed", "ffn"), "w_xbc": ("embed", "ffn"),
        "w_dt": ("embed", None), "conv_w": ("conv", None),
        "conv_b": (None,), "dt_bias": (None,), "A_log": (None,),
        "D": (None,), "norm_scale": (None,), "w_out": ("ffn", "embed"),
    }


def _causal_conv(x, w, b, history=None):
    """Depthwise causal conv. x (B,T,C), w (W,C). history (B,W-1,C) or None."""
    W = w.shape[0]
    if history is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    else:
        pad = history.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)              # (B, T+W-1, C)
    out = sum(xp[:, i:i + x.shape[1]] * w[i].astype(x.dtype) for i in range(W))
    return out + b.astype(x.dtype)


def _proj_split(p, x, cfg: ArchConfig):
    d_in, H, _, n, conv_ch = _dims(cfg)
    dt_ = x.dtype
    z = x @ p["w_z"].astype(dt_)                        # (B,T,d_in)
    xbc = x @ p["w_xbc"].astype(dt_)                    # (B,T,conv_ch)
    dt_raw = x @ p["w_dt"].astype(dt_)                  # (B,T,H)
    return z, xbc, dt_raw


def _post(p, y, z, cfg: ArchConfig):
    """Gated RMSNorm + output projection. y,z (B,T,d_in)."""
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    y = (yf * jax.lax.rsqrt(var + 1e-5) * p["norm_scale"]).astype(y.dtype)
    return y @ p["w_out"].astype(y.dtype)


def apply_mamba2(p, x, cfg: ArchConfig, chunk: int = None):
    """Training/prefill forward. x (B,T,d) -> (B,T,d)."""
    B, T, d = x.shape
    d_in, H, ph, n, conv_ch = _dims(cfg)
    dtype = x.dtype
    tile_dt = jnp.dtype(cfg.ssm_tile_dtype)
    chunk = min(chunk or cfg.ssm_chunk, T)
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk

    z, xbc, dt_raw = _proj_split(p, x, cfg)
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs = xbc[..., :d_in].reshape(B, T, H, ph)
    Bm = xbc[..., d_in:d_in + n]                        # (B,T,n)
    Cm = xbc[..., d_in + n:]                            # (B,T,n)

    dt_v = jax.nn.softplus(dt_raw.astype(jnp.float32)
                           + p["dt_bias"])              # (B,T,H)
    A = -jnp.exp(p["A_log"])                            # (H,) negative
    la = dt_v * A[None, None, :]                        # log decay, (B,T,H)

    # chunked views
    xs_c = xs.reshape(B, nc, chunk, H, ph)
    B_c = Bm.reshape(B, nc, chunk, n)
    C_c = Cm.reshape(B, nc, chunk, n)
    dt_c = dt_v.reshape(B, nc, chunk, H)
    la_c = la.reshape(B, nc, chunk, H)

    def chunk_step(h, inputs):
        xs_k, B_k, C_k, dt_k, la_k = inputs
        # cumulative decays within the chunk (inclusive), always f32
        W = jnp.cumsum(la_k, axis=1)                    # (B,Q,H)
        W_last = W[:, -1]                               # (B,H)
        # All O(Q^2) / O(Q*H*p) tiles are held in cfg.ssm_tile_dtype (bf16
        # for the production configs); every einsum accumulates in f32 via
        # preferred_element_type. Only the scalar-ish decay math is f32.
        C_t = C_k.astype(tile_dt)
        B_t = B_k.astype(tile_dt)
        x_t = xs_k.astype(tile_dt)
        # NOTE: every contraction below is written as explicit two-operand
        # steps — a single 3/4-operand einsum lets XLA pick a contraction
        # order that materializes a (B,Q,S,H,p) 5-D intermediate (measured:
        # 5.4 GB per dot at the full config; §Perf A it6).
        # ---- inter-chunk: y_t += C_t (exp(W_t) h_prev); W_t includes la_t
        # because h_t = exp(la_t) h_{t-1} + ... applies decay at every step
        decay_to_t = jnp.exp(W).astype(tile_dt)         # (B,Q,H)
        ch = jnp.einsum("bqn,bhpn->bqhp", C_t, h.astype(tile_dt),
                        preferred_element_type=jnp.float32)
        y_inter = ch * decay_to_t[..., None]            # (B,Q,H,p) f32
        # ---- intra-chunk: attention-like with decay kernel
        # contribution of s<=t: dt_s * exp(sum_{i=s+1..t} la_i) * (C_t.B_s) x_s
        G = jnp.einsum("bqn,bsn->bqs", C_t, B_t,
                       preferred_element_type=jnp.float32)  # (B,Q,S)
        Wdiff = W[:, :, None, :] - W[:, None, :, :]     # (B,Q,S,H)
        mask = jnp.tril(jnp.ones((chunk, chunk), bool))
        Ldec = jnp.where(mask[None, :, :, None],
                         jnp.exp(Wdiff), 0.0).astype(tile_dt)
        att = (G[..., None].astype(tile_dt) * Ldec
               * dt_k[:, None].astype(tile_dt))         # (B,Q,S,H)
        y_intra = jnp.einsum("bqsh,bshp->bqhp", att, x_t,
                             preferred_element_type=jnp.float32)
        # ---- state update: h_new = exp(W_last) h + sum_s exp(W_last-W_s) dt_s x_s B_s^T
        carry_decay = jnp.exp(W_last)                   # (B,H)
        src = (jnp.exp(W_last[:, None, :] - W) * dt_k).astype(tile_dt)
        xsrc = x_t * src[..., None]                     # (B,Q,H,p)
        h_new = (carry_decay[:, :, None, None] * h
                 + jnp.einsum("bqhp,bqn->bhpn", xsrc, B_t,
                              preferred_element_type=jnp.float32))
        y = (y_inter + y_intra).astype(tile_dt)         # (B,Q,H,p)
        return h_new, y

    h0 = jnp.zeros((B, H, ph, n), jnp.float32)
    inputs = (xs_c.transpose(1, 0, 2, 3, 4), B_c.transpose(1, 0, 2, 3),
              C_c.transpose(1, 0, 2, 3), dt_c.transpose(1, 0, 2, 3),
              la_c.transpose(1, 0, 2, 3))
    # checkpoint: the (B,Q,Q,H) decay/attention tiles are recomputed in the
    # backward pass instead of being stored per chunk
    _, ys = jax.lax.scan(jax.checkpoint(chunk_step), h0, inputs)  # (nc,B,Q,H,p)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, T, H, ph)
    y = y + (p["D"].astype(tile_dt)[None, None, :, None]
             * xs.astype(tile_dt))
    y = y.reshape(B, T, d_in).astype(dtype)
    return _post(p, y, z, cfg)


def init_cache(cfg: ArchConfig, batch: int, dtype=jnp.float32) -> MambaCache:
    d_in, H, p, n, conv_ch = _dims(cfg)
    return MambaCache(
        h=jnp.zeros((batch, H, p, n), jnp.float32),
        conv=jnp.zeros((batch, cfg.conv_width - 1, conv_ch), dtype))


def decode_step(p, x, cache: MambaCache, cfg: ArchConfig):
    """x (B,1,d) -> (y (B,1,d), cache). Exact recurrence."""
    B = x.shape[0]
    d_in, H, ph, n, conv_ch = _dims(cfg)
    dtype = x.dtype

    z, xbc, dt_raw = _proj_split(p, x, cfg)
    conv_hist = jnp.concatenate([cache.conv, xbc.astype(cache.conv.dtype)],
                                axis=1)                 # (B,W,C)
    xbc_t = jnp.einsum("bwc,wc->bc", conv_hist.astype(dtype),
                       p["conv_w"].astype(dtype)) + p["conv_b"].astype(dtype)
    xbc_t = jax.nn.silu(xbc_t)                          # (B,C)
    new_conv = conv_hist[:, 1:]

    xs = xbc_t[:, :d_in].reshape(B, H, ph)
    Bm = xbc_t[:, d_in:d_in + n]                        # (B,n)
    Cm = xbc_t[:, d_in + n:]                            # (B,n)
    dt_v = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt_v * A[None, :])                  # (B,H)

    h = (decay[:, :, None, None] * cache.h
         + jnp.einsum("bh,bhp,bn->bhpn", dt_v, xs.astype(jnp.float32),
                      Bm.astype(jnp.float32)))
    y = jnp.einsum("bhpn,bn->bhp", h, Cm.astype(jnp.float32))
    y = y + p["D"][None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, 1, d_in).astype(dtype)
    out = _post(p, y, z, cfg)
    return out, MambaCache(h=h, conv=new_conv)


def apply_mamba2_kernel(p, x, cfg: ArchConfig, chunk: int = 128,
                        interpret=None):
    """Inference/prefill forward through the Pallas SSD kernel
    (kernels/ssd_chunk): chunk tiles stay in VMEM, HBM traffic is inputs +
    outputs only. Forward-only (training uses apply_mamba2)."""
    from repro.kernels.ssd_chunk import ssd_core
    B, T, d = x.shape
    d_in, H, ph, n, conv_ch = _dims(cfg)
    dtype = x.dtype

    z, xbc, dt_raw = _proj_split(p, x, cfg)
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs = xbc[..., :d_in].reshape(B, T, H, ph)
    Bm = xbc[..., d_in:d_in + n]
    Cm = xbc[..., d_in + n:]
    dt_v = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    la = dt_v * A[None, None, :]

    y, _ = ssd_core(xs, Bm, Cm, dt_v, la, chunk=min(chunk, T),
                    interpret=interpret)
    y = y + p["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, T, d_in).astype(dtype)
    return _post(p, y, z, cfg)


# ---------------------------------------------------------------------------
# Reference (exact sequential scan) — oracle for tests.
# ---------------------------------------------------------------------------

def apply_mamba2_ref(p, x, cfg: ArchConfig):
    """Token-by-token recurrence; numerically exact, O(T) sequential."""
    B, T, d = x.shape
    cache = init_cache(cfg, B, dtype=x.dtype)
    # run the shared pre-compute once to keep conv semantics identical
    z, xbc, dt_raw = _proj_split(p, x, cfg)
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"]))
    d_in, H, ph, n, conv_ch = _dims(cfg)
    xs = xbc[..., :d_in].reshape(B, T, H, ph)
    Bm = xbc[..., d_in:d_in + n]
    Cm = xbc[..., d_in + n:]
    dt_v = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    def step(h, t_in):
        xs_t, B_t, C_t, dt_t = t_in
        decay = jnp.exp(dt_t * A[None, :])
        h = (decay[:, :, None, None] * h
             + jnp.einsum("bh,bhp,bn->bhpn", dt_t, xs_t.astype(jnp.float32),
                          B_t.astype(jnp.float32)))
        y = jnp.einsum("bhpn,bn->bhp", h, C_t.astype(jnp.float32))
        return h, y

    h0 = jnp.zeros((B, H, ph, n), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (xs.transpose(1, 0, 2, 3),
                                    Bm.transpose(1, 0, 2),
                                    Cm.transpose(1, 0, 2),
                                    dt_v.transpose(1, 0, 2)))
    y = ys.transpose(1, 0, 2, 3)                        # (B,T,H,p)
    y = y + p["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, T, d_in).astype(x.dtype)
    return _post(p, y, z, cfg)
