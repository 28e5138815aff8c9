"""Wrapper: run a Mamba2 layer's SSD core through the Pallas kernel.

Used on the inference/prefill path (forward-only; training keeps the
differentiable jnp chunked form in models/mamba2.py — see DESIGN.md §8).
Converts the model's (B, T, H, ...) layout to the kernel's pane layout.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ssd_chunk.kernel import ssd_scan
from repro.kernels.ssd_chunk.ref import ssd_scan_ref


def ssd_core(xs, Bm, Cm, dt, la, *, chunk: int = 128, interpret=None,
             use_kernel: bool = True):
    """xs (B,T,H,p); Bm/Cm (B,T,n) shared across heads (mamba2 ngroups=1);
    dt/la (B,T,H). Returns (y (B,T,H,p), h_final (B,H,p,n))."""
    B, T, H, p = xs.shape
    n = Bm.shape[-1]
    xs_p = xs.transpose(0, 2, 1, 3).reshape(B * H, T, p)
    B_p = jnp.broadcast_to(Bm[:, None], (B, H, T, n)).reshape(B * H, T, n)
    C_p = jnp.broadcast_to(Cm[:, None], (B, H, T, n)).reshape(B * H, T, n)
    dt_p = dt.transpose(0, 2, 1).reshape(B * H, T)
    la_p = la.transpose(0, 2, 1).reshape(B * H, T)
    if use_kernel and T % min(chunk, T) == 0:
        y, hf = ssd_scan(xs_p, B_p, C_p, dt_p, la_p,
                         chunk=chunk, interpret=interpret)
    else:
        y, hf = ssd_scan_ref(xs_p, B_p, C_p, dt_p, la_p)
    y = y.reshape(B, H, T, p).transpose(0, 2, 1, 3)
    return y, hf.reshape(B, H, p, n)
