"""Public wrapper: Pallas flash attention with jnp fallback + ref oracle."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def attend_flash(q, k, v, *, causal: bool = True, window: int = 0,
                 block_q: int = 512, block_k: int = 512,
                 interpret=None):
    """Serving-path attention. Falls back to the oracle when tile shapes
    don't divide (tiny smoke configs)."""
    B, T, H, dh = q.shape
    S = k.shape[1]
    bq = min(block_q, T)
    bk = min(block_k, S)
    if T % bq or S % bk:
        return attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=bq, block_k=bk, interpret=interpret)
