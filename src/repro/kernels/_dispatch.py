"""Shared kernel-dispatch helpers: backend detection, padding, tiling.

Every kernel family (metric_topk, pq_adc, ivf_scan) fronts its Pallas
kernel with the same ops-layer chores: decide compile-vs-interpret from
the runtime backend, round shapes up to tile multiples, pad with zeros
or sentinels, and pick block sizes for inputs smaller than the
configured tile. This module owns those chores — plus the one
tie-breaking contract (``topk_by_distance``) every scan path must agree
on bit-for-bit — so the families stay in lockstep instead of drifting
three private copies.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANE = 128      # TPU lane width: last-dim tiles round up to this
SUBLANE = 8     # f32 sublane width: second-minor tiles round up to this

# Full f32 precision for the exact and IVF scans' cross terms (kernels
# and references alike) and for the fused pair loss: their on-chip
# parity and recall checks fail or lose their margin at the TPU's
# default. Projections, PQ tables and the IVFPQ rerank keep the default.
# docs/kernels.md "Precision on the TPU" gives the policy per path.
HIGHEST = jax.lax.Precision.HIGHEST


def matmul_t(a, b, precision=None):
    """``a @ b.T`` in f32 (the projection and cross-term contraction
    every scan path shares); ``precision`` as for ``lax.dot_general``."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((a.ndim - 1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)


def check_metric_factor(L, d_in=None, *, what: str = "L"):
    """Validate the ``(d_out, d_in)`` metric-factor contract up front.

    Every layer that touches a metric factor — projection, index build,
    kernels — agrees that L is 2-D with raw features on the *second*
    axis, and that rectangular ``d_out < d_in`` (a low-rank factor) is
    as legal as square. Checking here, before any jit boundary, turns a
    transposed / 1-D / wrong-dim factor into one clear ValueError
    instead of an opaque dot-dimension error deep inside a traced
    function. Shapes are static at trace time, so the check is also
    safe to reach from inside jit.

    Args:
      L: candidate metric factor.
      d_in: when given, the raw feature dimensionality the factor must
        contract against (``L.shape[1] == d_in``).
      what: name used in error messages.

    Returns L unchanged.
    """
    shape = tuple(jnp.shape(L))
    if len(shape) != 2:
        raise ValueError(
            f"{what} must be a 2-D (d_out, d_in) metric factor, got "
            f"shape {shape}")
    d_out, d = shape
    if d_out < 1 or d < 1:
        raise ValueError(
            f"{what} must have d_out >= 1 and d_in >= 1, got shape "
            f"{shape}")
    if d_in is not None and d != d_in:
        # rows matching the data dim is the transposed-factor signature
        hint = (" — transposed factor? the contract is rows = d_out, "
                "columns = d_in" if d_out == d_in else "")
        raise ValueError(
            f"{what} has d_in={d} but the data is {d_in}-dimensional; "
            f"expected {what}.shape == (d_out, {d_in}){hint}")
    return L


def default_interpret(interpret=None) -> bool:
    """Resolve the ops-layer ``interpret`` knob: ``None`` (the default)
    compiles the kernel on TPU and interprets everywhere else; a bool
    forces that choice."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def round_up(n: int, mult: int) -> int:
    return n + (-n) % mult


def pad_axis(x, target: int, axis: int, value=0.0):
    """Pad ``x`` along ``axis`` up to length ``target`` with ``value``
    (no-op when already there)."""
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def pick_block(n: int, block: int, mult: int) -> int:
    """Row-tile size: the configured ``block`` when ``n`` fills it,
    else all of ``n`` rounded up to ``mult`` (a single tile)."""
    return block if n >= block else round_up(n, mult)


def segment_block(cap: int, block: int) -> int:
    """Segment-scan row tile: ``block`` when it divides the segment
    capacity evenly, else the whole segment. Probed segments cannot be
    padded per probe (the probe list indexes a fixed layout), so the
    tile must divide ``cap`` exactly."""
    return block if cap % block == 0 else cap


def map_query_chunks(fn, arrays, block: int):
    """Run a per-chunk (dists, ids) scan over query-row chunks.

    The XLA fallback shape both segment-scan families share: pad the
    leading (query) axis of every array in ``arrays`` to a multiple of
    ``block``, lax.map ``fn`` over the (block, ...) chunks so the
    gathered per-chunk intermediates stay cache-sized, and slice the
    concatenated results back to the real query count. ``fn`` receives
    one chunk of each array and returns a (dists (B, kk), ids (B, kk))
    pair. Zero query pads are scored but sliced off.
    """
    n = arrays[0].shape[0]
    B = min(block, n)
    Np = round_up(n, B)
    chunked = tuple(pad_axis(a, Np, 0).reshape(Np // B, B, *a.shape[1:])
                    for a in arrays)
    d, i = jax.lax.map(lambda args: fn(*args), chunked)
    kk = d.shape[-1]
    return d.reshape(Np, kk)[:n], i.reshape(Np, kk)[:n]


def topk_by_distance(d, ids, k_top: int):
    """Top-k candidates by distance with a deterministic presentation.

    The one selection contract every scan path (XLA reference, Pallas
    streaming merge, serve/scan.py) must reproduce exactly: lax.top_k
    does the heavy lifting (ties toward the earlier candidate
    *position*), then the k_top survivors re-sort lexicographically by
    (distance, id) so equal-distance neighbors come back
    smallest-id-first regardless of candidate generation order. Ties
    straddling the k_top boundary still resolve by candidate position —
    see serve/scan.py for the serving-level caveats.
    """
    neg, pos = jax.lax.top_k(-d, k_top)
    cd, ci = -neg, jnp.take_along_axis(ids, pos, axis=-1)
    return jax.lax.sort((cd, ci), dimension=-1, num_keys=2)
