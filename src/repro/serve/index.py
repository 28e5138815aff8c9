"""Index hierarchy: the MetricIndex protocol and the exact scan backend.

``MetricIndex`` is the contract the engine (serve/engine.py) programs
against — build once, answer ``topk`` forever, expose ``size`` /
``n_shards`` for stats and ``version`` for cache invalidation. Two
implementations ship:

  * ``ExactIndex`` (this module) — scans every pre-projected gallery row;
    exact by construction. O(M*k/P) per query.
  * ``IVFIndex`` (serve/ivf.py) — cluster-pruned approximate scan that
    visits only the ``nprobe`` nearest gallery segments. Exact when
    ``nprobe == n_clusters``.

Both compose serve/scan.py for the shared substrate: query projection,
"gallery"-axis row sharding, and the shard_map local-topk/global-merge
skeleton that keeps sharded answers identical to single-device ones.

Index build amortizes the learned metric once (``gp = G @ L^T`` plus row
norms; kernels/metric_topk.project_gallery), after which every query costs
O(d*k + M*k/P) instead of O(M*d*k). Gallery rows shard across the worker
mesh via the logical ``"gallery"`` axis (sharding/partition.py maps it to
the (pod, data) axes); the metric factor L is replicated.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp

from repro.kernels.metric_topk import (metric_sqdist_factored, metric_topk,
                                       metric_topk_xla, project_gallery,
                                       tile_gallery)
from repro.serve import scan


@runtime_checkable
class MetricIndex(Protocol):
    """What the serving engine needs from any retrieval index backend.

    Implementations additionally provide a ``build(L, gallery, ...)``
    classmethod constructor; it is not part of the runtime-checked
    protocol because its signature is backend-specific.
    """

    version: int        # bumped on gallery mutation -> engine cache flush

    @property
    def size(self) -> int: ...          # number of real gallery rows

    @property
    def n_shards(self) -> int: ...      # mesh shards the rows live on

    def topk(self, queries, k_top: int, backend: str = "xla"):
        """(dists (Nq, k_top) ascending, global row ids (Nq, k_top)).

        ``queries`` are raw (Nq, d) vectors; implementations project
        them through L internally. Distances are squared metric
        distances; approximate backends may accept extra keywords
        (``nprobe``, ``rerank``) and mark unservable slots with id -1.
        """
        ...


@dataclasses.dataclass(eq=False)
class ExactIndex:
    """Immutable exact retrieval index over a pre-projected gallery.

    The gallery is held once, in the form its scan reads, made at build:

      * on one device, ``gp`` is the rows tiled to ``(slots, kl)`` and
        ``gn`` their ``(1, slots)`` norms with ``+BIG`` in the pad slots
        (kernels/metric_topk.tile_gallery); the fused kernel and the XLA
        path both read them as they stand;
      * sharded over ``axes``, ``gp`` is the rows ``(M, k)`` and ``gn``
        their ``(M,)`` norms, split over the mesh for the shard_map scan.

    ``rows()`` gives the real rows in row form either way. Invariants:
    the gallery holds ``gallery @ L^T`` and its row norms (never
    recomputed after build); answers are exact for the stored rows,
    deterministic across backends and shardings (equal distances tie
    toward the smaller row id); ``version`` only changes when a wrapper
    (MutableIndex / snapshot load) assigns it — this class never mutates
    itself.
    """

    L: jax.Array                    # (k, d) replicated metric factor
    gp: jax.Array                   # (slots, kl) tiled, or (M, k) sharded
    gn: jax.Array                   # (1, slots) tiled, or (M,) sharded
    n_rows: int                     # real gallery rows M
    mesh: Optional[jax.sharding.Mesh] = None
    axes: Tuple[str, ...] = ()      # mesh axes the rows are sharded over
    version: int = 0
    # per-instance k_top -> jitted sharded query fn (an lru_cache here would
    # pin the whole index in a class-level cache past its lifetime)
    _sharded_fns: dict = dataclasses.field(default_factory=dict, repr=False)

    @classmethod
    def build(cls, L, gallery, mesh=None, rules=None) -> "ExactIndex":
        """Project the gallery through L once and (optionally) shard it.

        Args:
          L: (k, d) metric factor (replicated across the mesh).
          gallery: (M, d) raw gallery rows.
          mesh / rules: optional jax Mesh + partition rules; when given,
            rows shard over the logical "gallery" axis (M must divide by
            the shard count — scan.gallery_axes checks).

        Returns a ready-to-query index (the one-time O(M*d*k) cost).
        """
        gp, gn = project_gallery(L, gallery)
        return cls.from_projected(L, gp, gn, mesh=mesh, rules=rules)

    @classmethod
    def from_projected(cls, L, gp, gn, mesh=None, rules=None) -> "ExactIndex":
        """Construct from already-projected rows (gp (M,k), gn (M,)).

        The mutation/snapshot layer (serve/mutable.py, serve/snapshot.py)
        enters here: compaction folds delta rows and snapshot load restores
        segments without ever re-projecting the gallery through L. On one
        device the rows are tiled here, once (``tile_gallery``); the
        caller's ``gp`` is not kept.
        """
        scan.check_metric_factor(L)
        gp = jnp.asarray(gp, jnp.float32)
        if gp.shape[1] != jnp.shape(L)[0]:
            raise ValueError(
                f"projected rows have dim {gp.shape[1]} but L is "
                f"{tuple(jnp.shape(L))}; gp must be sized d_out")
        gn = jnp.asarray(gn, jnp.float32)
        n_rows = gp.shape[0]
        axes: Tuple[str, ...] = ()
        if mesh is not None:
            axes = scan.gallery_axes(mesh, n_rows, rules)
        if axes:
            gp = scan.put_row_sharded(mesh, axes, gp)
            gn = scan.put_row_sharded(mesh, axes, gn)
            L = scan.put_replicated(mesh, L)
        else:
            gp, gn = tile_gallery(gp, gn)
        return cls(L=jnp.asarray(L), gp=gp, gn=gn, n_rows=n_rows,
                   mesh=mesh, axes=axes)

    @property
    def size(self) -> int:
        """Number of (real) gallery rows."""
        return self.n_rows

    @property
    def n_shards(self) -> int:
        """Mesh shards the rows live on (1 when unsharded)."""
        return scan.n_shards(self.mesh, self.axes)

    def rows(self):
        """The real gallery rows (gp (M, k), gn (M,)), sliced out of the
        stored gallery on demand: for the cold readers (snapshots,
        compaction), never the query path."""
        if self.axes:
            return self.gp, self.gn
        return (self.gp[:self.n_rows, :self.L.shape[0]],
                self.gn[0, :self.n_rows])

    def topk(self, queries, k_top: int, backend: str = "xla"):
        """Exact k nearest gallery rows per query.

        Args:
          queries: (Nq, d) raw queries (projected through L here).
          k_top: neighbors per query (1 <= k_top <= size).
          backend: "xla" (factored fast path; the only sharded option)
            or "pallas" (fused kernel, single-device; interpret
            off-TPU).

        Returns (dists (Nq, k_top) f32 ascending, global row indices
        (Nq, k_top) int32); equal distances tie toward the smaller id.
        """
        if k_top > self.size:
            raise ValueError(f"k_top={k_top} > gallery size {self.size}")
        if self.n_shards > 1:
            if backend != "xla":
                raise NotImplementedError(
                    "sharded index only supports the xla backend")
            return self._topk_sharded(k_top)(queries)
        if backend == "pallas":
            return metric_topk(self.L, queries, self.gp, self.gn,
                               k_top=k_top)
        return metric_topk_xla(self.L, queries, self.gp, self.gn, k_top)

    def _topk_sharded(self, k_top: int):
        fn = self._sharded_fns.get(k_top)
        if fn is None:
            fn = self._sharded_fns[k_top] = self._build_topk_sharded(k_top)
        return fn

    def _build_topk_sharded(self, k_top: int):
        rows_local = self.size // self.n_shards
        kk = min(k_top, rows_local)     # per-shard candidates => exact merge

        def local_candidates(shard, qp, extras, locals_):
            gp_loc, gn_loc = locals_
            d = metric_sqdist_factored(qp, gp_loc, gn_loc)
            ids = shard * gp_loc.shape[0] + jnp.arange(gp_loc.shape[0],
                                                       dtype=jnp.int32)
            # contiguous row scan: candidate position order == global-id
            # order, so the cheap positional tie-break is already exact
            return scan.local_topk(d, jnp.broadcast_to(ids, d.shape), kk)

        inner = scan.build_sharded_topk(self.mesh, self.axes,
                                        (self.gp, self.gn),
                                        local_candidates, k_top)

        # the gallery rides in as arguments, never as jit constants
        @jax.jit
        def run(queries, L, gp, gn):
            return inner(scan.project_queries(L, queries), gp, gn)

        return lambda queries: run(queries, self.L, self.gp, self.gn)


# Back-compat: PR 1 shipped the exact backend under this name.
GalleryIndex = ExactIndex
