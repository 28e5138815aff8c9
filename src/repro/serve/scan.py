"""Shared gallery-scan machinery: projection, row sharding, top-k merge.

Every index backend (serve/index.py ExactIndex, serve/ivf.py IVFIndex)
answers a query the same way at the bottom: project the query once into the
k-dim metric space, score some set of pre-projected gallery rows with the
factored squared distance, and keep the k_top best with ties broken toward
the smaller global row id. This module owns that shared substrate so the
backends only differ in *which rows they score*:

  * ``project_queries``      — q @ L^T, the once-per-query projection;
  * ``gallery_axes`` / ``put_row_sharded`` / ``put_replicated`` — mapping
    the logical "gallery" axis onto physical mesh axes and placing arrays;
  * ``local_topk`` / ``topk_by_distance`` — candidate selection.
    ``topk_by_distance`` is the deterministic (distance, id) lexicographic
    merge: ties go to the smaller global row id regardless of the order
    candidates were generated in (IVF visits rows cluster-permuted);
  * ``build_sharded_topk``   — the shard_map local-topk/global-merge
    skeleton: each shard turns its local rows into at most ``kk``
    globally-id'd candidates, the per-shard candidates concatenate along
    the neighbor axis, and one final merge makes the result exact.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import _dispatch
from repro.sharding import partition


def project_queries(L, queries):
    """Project raw (Nq, d_in) queries into the d_out-dim metric space (f32).

    ``L`` is the (d_out, d_in) metric factor — square or rectangular.
    Validates the factor contract up front (shapes are static at trace
    time, so this also fires with a clear error from inside jit instead
    of an opaque dot-dimension failure). The kernel and XLA scan paths
    share this projection (_dispatch.matmul_t)."""
    check_metric_factor(L, jnp.shape(queries)[-1])
    return _dispatch.matmul_t(queries, L)


def check_metric_factor(L, d_in=None, *, what: str = "L"):
    """Validate L against the (d_out, d_in) contract — see
    kernels/_dispatch.check_metric_factor (the one copy every layer
    shares); re-exported here because serve-side callers (index builds,
    engine, CLI) reach it through the scan substrate."""
    return _dispatch.check_metric_factor(L, d_in, what=what)


SCAN_IMPLS = ("auto", "xla", "pallas")


def resolve_scan_impl(default: str, override=None, *,
                      sharded: bool = False) -> str:
    """Resolve a segment-scan implementation knob to "xla" or "pallas".

    ``default`` is the index's build-time setting; ``override`` a
    per-call value (None defers to the default — ``is None``, never
    truthiness, so an explicit empty/0 value raises instead of silently
    remapping, the k_top=0 bug class). "auto" picks the fused Pallas
    kernel when the runtime backend is a TPU and the XLA path elsewhere
    (interpret-mode Pallas is a correctness tool, not a serving path).
    On a ``sharded`` index "auto" is the per-shard XLA scan: the fused
    kernel does not compose with shard_map yet, and an explicit
    "pallas" there is the caller's to reject.
    """
    impl = default if override is None else override
    if impl not in SCAN_IMPLS:
        raise ValueError(f"unknown scan_impl {impl!r} "
                         f"({'|'.join(SCAN_IMPLS)})")
    if impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        return "pallas" if on_tpu and not sharded else "xla"
    return impl


def recall_at_k(approx_ids, exact_ids) -> float:
    """Mean per-query overlap |approx ∩ exact| / k between two (Nq, k)
    neighbor-id arrays — the ANN quality metric the IVF frontier sweeps.
    Host-side numpy helper shared by benchmarks, examples, and tests.
    -1 sentinel ids (under-filled probes) never match a real id."""
    a = np.asarray(approx_ids)
    e = np.asarray(exact_ids)
    k = e.shape[1]
    return float(np.mean([len(set(ar[ar >= 0]) & set(er)) / k
                          for ar, er in zip(a, e)]))


def gallery_axes(mesh: Mesh, n_rows: Optional[int] = None,
                 rules=None) -> Tuple[str, ...]:
    """Physical mesh axes the gallery rows shard over (possibly empty).

    ``n_rows=None`` skips the divisibility check — for backends (IVF) that
    pick their padded row count *after* learning the shard count.
    """
    shape = None if n_rows is None else (n_rows, 1)
    spec = partition.logical_to_physical(("gallery", None), mesh, rules,
                                         shape=shape)
    ax = spec[0]
    if ax is None:
        return ()
    return (ax,) if isinstance(ax, str) else tuple(ax)


def row_axis(axes: Tuple[str, ...]):
    """PartitionSpec entry for the row dimension (one axis or a tuple)."""
    return axes if len(axes) > 1 else axes[0]


def n_shards(mesh: Optional[Mesh], axes: Tuple[str, ...]) -> int:
    if not axes:
        return 1
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def put_row_sharded(mesh: Mesh, axes: Tuple[str, ...], arr):
    """device_put with the leading dim split over the gallery mesh axes."""
    spec = P(row_axis(axes), *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


def put_replicated(mesh: Mesh, arr):
    return jax.device_put(jnp.asarray(arr), NamedSharding(mesh, P()))


def shard_index(mesh: Mesh, axes: Tuple[str, ...]):
    """Spec-major linear shard id (traced; only valid inside shard_map)."""
    s = jnp.int32(0)
    for a in axes:
        s = s * mesh.shape[a] + jax.lax.axis_index(a)
    return s


def local_topk(d, ids, kk: int):
    """Cheapest local selection: lax.top_k on -d, ties toward the earlier
    candidate position. Correct merge input whenever candidate position
    order equals global-id order (the contiguous row scan)."""
    neg, pos = jax.lax.top_k(-d, kk)
    return -neg, jnp.take_along_axis(ids, pos, axis=-1)


def topk_by_distance(d, ids, k_top: int):
    """Top-k candidates by distance with a deterministic presentation.

    lax.top_k does the heavy selection (O(n log k); a full lexicographic
    lax.sort is ~50x slower on CPU), then the k_top survivors re-sort
    lexicographically by (distance, id) so equal-distance neighbors always
    come back smallest-id-first regardless of the order candidates were
    generated in (IVF visits rows cluster-permuted). Caveat: ties
    *straddling* the k_top boundary still resolve by candidate position,
    so on galleries with exactly duplicated rows the returned member of a
    tied tail may differ between backends (distances are still correct;
    distinct real-valued distances are unaffected).

    Delegates to kernels/_dispatch.py — the one copy of the contract the
    Pallas segment-scan kernels and their XLA references must reproduce
    bit-for-bit.
    """
    return _dispatch.topk_by_distance(d, ids, k_top)


def build_sharded_topk(mesh: Mesh, axes: Tuple[str, ...],
                       sharded_arrays: Sequence[jax.Array],
                       local_candidates: Callable, k_top: int,
                       n_extras: int = 0):
    """Build the shard_map local-topk/global-merge query skeleton.

    ``local_candidates(shard, qp, extras, locals_) -> (d, ids)`` runs per
    shard: ``shard`` is this shard's spec-major id, ``qp`` the replicated
    projected queries, ``extras`` replicated per-call inputs (e.g. IVF
    probe lists), ``locals_`` this shard's slices of ``sharded_arrays``.
    It must return (Nq, kk) candidates with *global* row ids and
    kk >= min(k_top, candidates available on the shard) — then the final
    (distance, id) merge over the concatenated (Nq, kk * n_shards)
    candidates is exact.

    Returns ``run(qp, *extras, *sharded_arrays) -> (dists, ids)`` (not
    jitted; callers wrap it together with query projection). The arrays
    here fix only the specs: callers pass them again per call, as
    arguments of their jit — a jit that closed over a gallery-sized
    array would bake it into the program as a constant.
    """
    row_ax = row_axis(axes)
    specs = tuple(P(row_ax, *([None] * (a.ndim - 1))) for a in sharded_arrays)
    in_specs = (P(),) * (1 + n_extras) + specs
    out_specs = (P(None, row_ax), P(None, row_ax))

    def body(qp, *rest):
        extras, locals_ = rest[:n_extras], rest[n_extras:]
        return local_candidates(shard_index(mesh, axes), qp, extras, locals_)

    inner = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs)

    def run(qp, *args):
        cand_d, cand_i = inner(qp, *args)
        return topk_by_distance(cand_d, cand_i, k_top)

    return run
