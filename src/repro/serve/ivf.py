"""IVF (inverted-file) cluster-pruned ANN index under the learned metric.

The exact scan (serve/index.py) touches all M gallery rows per query; at
paper scale (ImageNet-1M, Xie & Xing 2014 §5) that caps QPS. This backend
trades a bounded recall loss for skipping most of the gallery, the
low-rank-projection-plus-pruning recipe Qian et al. 2015 argue makes
high-d learned-metric retrieval practical:

  build:  k-means in the *projected* k-dim metric space (Lloyd's,
          jit-scanned, with a farthest-point reseed for empty clusters)
          partitions the pre-projected gallery into ``n_clusters``
          contiguous segments, each padded to a common capacity so the
          layout stays static-shaped for jit; a (C, k) centroid table is
          kept replicated.
  query:  score the C centroids (cheap: C << M), keep the ``nprobe``
          nearest clusters, gather only their segments, run the same
          factored distance + (distance, id) merge the exact scan uses.

Per-query row visits drop from M to ``nprobe * capacity``. With
``nprobe == n_clusters`` every row is visited and the result matches
ExactIndex on indices (the correctness oracle the tests pin) whenever
distances are distinct; exactly duplicated gallery rows tied at the k_top
boundary may resolve to a different (equal-distance) copy — see
scan.topk_by_distance.

Padding slots carry ``gn = +BIG`` / ``id = -1`` sentinels; they can reach
the output only when the probed clusters hold fewer than k_top real rows
(raise nprobe if callers see -1 ids). Sharded build places whole clusters
per shard (n_clusters rounds up to a multiple of the shard count) and
composes scan.build_sharded_topk, with non-local probes routed to an
all-sentinel cluster so every shard does identical static-shaped work.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ivf_scan import ivf_scan_topk
from repro.kernels.metric_topk import metric_sqdist_factored, project_gallery
from repro.kernels.metric_topk.kernel import BIG
from repro.kernels.pairwise_dist.ref import pairwise_sqdist_ref
from repro.serve import scan


# -- metric-space k-means ----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_rows",))
def _assign(gp, centroids, block_rows: int):
    """Nearest-centroid assignment, chunked over rows so the (M, C)
    distance matrix never materializes at big M. Returns (assign (M,)
    int32, min_sqdist (M,) f32)."""
    M, k = gp.shape
    B = min(block_rows, M)
    Mp = ((M + B - 1) // B) * B
    gpp = jnp.pad(gp, ((0, Mp - M), (0, 0)))

    def blk(g):
        d = pairwise_sqdist_ref(g, centroids)
        return jnp.argmin(d, axis=1).astype(jnp.int32), jnp.min(d, axis=1)

    a, md = jax.lax.map(blk, gpp.reshape(Mp // B, B, k))
    return a.reshape(-1)[:M], md.reshape(-1)[:M]


@functools.partial(jax.jit, static_argnames=("n_clusters",))
def _farthest_init(gp, n_clusters: int, key):
    """k-center greedy ("maxmin") seeding: start anywhere, then repeatedly
    take the point farthest from every seed so far. One O(M*k) pass per
    seed (same total cost as one Lloyd iteration) and — unlike random row
    draws — never stacks several seeds inside one dense cluster, which is
    what splits a blob's neighbors across segments and caps recall."""
    M = gp.shape[0]
    first = gp[jax.random.randint(key, (), 0, M)]

    def step(carry, _):
        mind, last = carry
        d = jnp.sum(jnp.square(gp - last), axis=1)
        mind = jnp.minimum(mind, d)
        nxt = gp[jnp.argmax(mind)]
        return (mind, nxt), last

    (_, last), seeds = jax.lax.scan(
        step, (jnp.full((M,), jnp.inf, jnp.float32), first), None,
        length=n_clusters)
    return seeds


@functools.partial(jax.jit, static_argnames=("iters", "block_rows"))
def _lloyd(gp, cent0, iters: int, block_rows: int):
    M = gp.shape[0]
    C = cent0.shape[0]

    def step(cent, _):
        a, md = _assign(gp, cent, block_rows)
        counts = jnp.zeros((C,), jnp.float32).at[a].add(1.0)
        sums = jnp.zeros_like(cent).at[a].add(gp)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # balanced-assignment fallback: each empty cluster reseeds at a
        # distinct currently-worst-served point (largest min-distance),
        # which splits overloaded regions instead of leaving dead segments
        empty = counts == 0.0
        far = jnp.argsort(-md)
        rank = jnp.clip(jnp.cumsum(empty) - 1, 0, M - 1)
        new = jnp.where(empty[:, None], gp[far[rank]], new)
        return new, md.mean()

    return jax.lax.scan(step, cent0, None, length=iters)


def kmeans_projected(gp, n_clusters: int, *, iters: int = 10, seed: int = 0,
                     block_rows: int = 16384, init: str = "farthest"):
    """Lloyd's k-means over pre-projected gallery rows (M, k).

    ``init``: "farthest" (k-center greedy; default) or "random" (row
    draws). Returns (centroids (C, k) f32, assign (M,) int32, objective
    (iters,) f32) — objective[t] is the mean squared distance to the
    nearest centroid *entering* iteration t, so it is non-increasing for
    pure Lloyd steps (empty-cluster reseeds may bump it transiently).
    """
    gp = jnp.asarray(gp, jnp.float32)
    M = gp.shape[0]
    if n_clusters > M:
        raise ValueError(f"n_clusters={n_clusters} > gallery size {M}")
    key = jax.random.PRNGKey(seed)
    if init == "farthest":
        cent0 = _farthest_init(gp, n_clusters, key)
    elif init == "random":
        cent0 = gp[jax.random.permutation(key, M)[:n_clusters]]
    else:
        raise ValueError(f"unknown init {init!r}")
    centroids, objective = _lloyd(gp, cent0, iters, block_rows)
    assign, _ = _assign(gp, centroids, block_rows)
    return centroids, assign, objective


def _balance_assign(gp, centroids, assign, cap: int) -> np.ndarray:
    """Capacity-bounded assignment: clusters keep their ``cap`` closest
    rows; overflow rows move to the nearest cluster with free space.

    Host-side one-time build step (numpy). Total capacity C*cap >= M is
    guaranteed by cap >= ceil(M/C), so the greedy pass always places
    every row.
    """
    C = centroids.shape[0]
    counts = np.bincount(assign, minlength=C)
    if counts.max() <= cap:
        return assign
    assign = assign.copy()
    spilled = []
    for c in np.flatnonzero(counts > cap):
        rows = np.flatnonzero(assign == c)
        d = np.sum((gp[rows] - centroids[c]) ** 2, axis=1)
        spilled.extend(rows[np.argsort(d)[cap:]])
        counts[c] = cap
    d_all = (np.sum(gp[spilled] ** 2, axis=1)[:, None]
             + np.sum(centroids ** 2, axis=1)[None, :]
             - 2.0 * gp[spilled] @ centroids.T)             # (S, C)
    for i, row in enumerate(spilled):
        for c in np.argsort(d_all[i]):
            if counts[c] < cap:
                assign[row] = c
                counts[c] += 1
                break
    return assign


# -- the index ---------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class IVFIndex:
    """Cluster-pruned approximate retrieval index (MetricIndex backend).

    Invariants: segments are cluster-major with a common capacity
    (static shapes keep the jitted query paths hot); pad slots carry
    ``gn = +BIG`` / ``id = -1`` sentinels and can only surface when the
    probed clusters hold fewer than k_top real rows; at ``nprobe ==
    n_clusters`` answers match ExactIndex on indices (ties at the k_top
    boundary between exactly duplicated rows excepted — see
    scan.topk_by_distance).
    """

    L: jax.Array                    # (k, d) replicated metric factor
    centroids: jax.Array            # (C, k) cluster centers, replicated
    gp_pad: jax.Array               # (C*cap, k) cluster-major padded rows
    gn_pad: jax.Array               # (C*cap,) row norms; BIG on pad slots
    ids_pad: jax.Array              # (C*cap,) original row ids; -1 on pads
    cap: int                        # per-cluster segment capacity
    n_clusters: int
    nprobe: int                     # default clusters scanned per query
    n_rows: int                     # real (unpadded) gallery size M
    block_q: int = 16               # query chunk for the segment gather
    # segment-scan implementation: "auto" (Pallas kernel on TPU, XLA
    # elsewhere), "xla", or "pallas" (kernels/ivf_scan; single-shard only)
    scan_impl: str = "auto"
    mesh: Optional[jax.sharding.Mesh] = None
    axes: Tuple[str, ...] = ()
    version: int = 0
    _fns: dict = dataclasses.field(default_factory=dict, repr=False)

    @classmethod
    def build(cls, L, gallery, n_clusters: int = 64, nprobe: int = 8,
              *, iters: int = 10, seed: int = 0, cap_factor: float = 1.25,
              scan_impl: str = "auto", mesh=None, rules=None) -> "IVFIndex":
        """Project the gallery, cluster it, lay out padded segments.

        ``cap_factor`` bounds segment capacity at ~cap_factor * M/C rows:
        k-means clusters larger than that spill their farthest rows to the
        nearest cluster with free space (balanced assignment). Query cost
        is nprobe * cap, so capping it keeps skewed galleries from paying
        the worst cluster's size on every probe; spilled rows are only
        found via their adoptive cluster (a bounded recall trade).
        ``scan_impl`` picks the default segment-scan implementation —
        "auto" (kernels/ivf_scan fused Pallas kernel on TPU, XLA
        elsewhere), "xla", or "pallas" (overridable per topk call).
        """
        gp, gn = project_gallery(L, gallery)
        return cls.build_projected(L, gp, gn, n_clusters=n_clusters,
                                   nprobe=nprobe, iters=iters, seed=seed,
                                   cap_factor=cap_factor,
                                   scan_impl=scan_impl, mesh=mesh,
                                   rules=rules)

    @classmethod
    def build_projected(cls, L, gp, gn, n_clusters: int = 64,
                        nprobe: int = 8, *, iters: int = 10, seed: int = 0,
                        cap_factor: float = 1.25, scan_impl: str = "auto",
                        mesh=None, rules=None) -> "IVFIndex":
        """Cluster + lay out already-projected rows (gp (M,k), gn (M,)).

        The compaction-triggered rebuild and metric hot-swap
        (serve/mutable.py) enter here: they already hold projected rows
        and must not pay a second gallery projection.
        """
        if scan_impl not in scan.SCAN_IMPLS:
            raise ValueError(f"unknown scan_impl {scan_impl!r} "
                             f"({'|'.join(scan.SCAN_IMPLS)})")
        scan.check_metric_factor(L)
        gp = jnp.asarray(gp, jnp.float32)
        gn = jnp.asarray(gn, jnp.float32)
        M, k = gp.shape
        if k != jnp.shape(L)[0]:
            raise ValueError(
                f"projected rows have dim {k} but L is "
                f"{tuple(jnp.shape(L))}; gp must be sized d_out")
        axes: Tuple[str, ...] = ()
        if mesh is not None:
            axes = scan.gallery_axes(mesh, None, rules)
        shards = scan.n_shards(mesh, axes)
        C = ((n_clusters + shards - 1) // shards) * shards  # whole clusters
        if C > M:                                           # per shard
            raise ValueError(f"n_clusters={C} (after shard round-up) > "
                             f"gallery size {M}")
        centroids, assign, _ = kmeans_projected(gp, C, iters=iters,
                                                seed=seed)

        gp_np = np.asarray(gp)
        cap = int(-((-max(cap_factor, 1.0) * M) // C))      # ceil
        cap = ((cap + 7) // 8) * 8
        assign = _balance_assign(gp_np, np.asarray(centroids),
                                 np.asarray(assign), cap)
        counts = np.bincount(assign, minlength=C)
        order = np.argsort(assign, kind="stable")           # cluster-major
        offsets = np.cumsum(counts) - counts
        within = np.arange(M) - offsets[assign[order]]
        slots = assign[order] * cap + within

        gp_pad = np.zeros((C * cap, k), np.float32)
        gn_pad = np.full((C * cap,), BIG, np.float32)
        ids_pad = np.full((C * cap,), -1, np.int32)
        gp_pad[slots] = gp_np[order]
        gn_pad[slots] = np.asarray(gn)[order]
        ids_pad[slots] = order.astype(np.int32)

        gp_pad, gn_pad, ids_pad = map(jnp.asarray, (gp_pad, gn_pad, ids_pad))
        if axes:
            gp_pad = scan.put_row_sharded(mesh, axes, gp_pad)
            gn_pad = scan.put_row_sharded(mesh, axes, gn_pad)
            ids_pad = scan.put_row_sharded(mesh, axes, ids_pad)
            L = scan.put_replicated(mesh, L)
            centroids = scan.put_replicated(mesh, centroids)
        return cls(L=jnp.asarray(L), centroids=centroids, gp_pad=gp_pad,
                   gn_pad=gn_pad, ids_pad=ids_pad, cap=cap, n_clusters=C,
                   nprobe=min(nprobe, C), n_rows=M, scan_impl=scan_impl,
                   mesh=mesh, axes=axes)

    @property
    def size(self) -> int:
        """Real (unpadded) gallery rows."""
        return self.n_rows

    @property
    def n_shards(self) -> int:
        """Mesh shards the segments live on (1 when unsharded)."""
        return scan.n_shards(self.mesh, self.axes)

    def topk(self, queries, k_top: int, backend: str = "xla",
             nprobe: Optional[int] = None,
             scan_impl: Optional[str] = None):
        """Approximate k nearest gallery rows per query.

        Args:
          queries: (Nq, d) raw queries (projected through L here).
          k_top: neighbors per query (<= size and <= nprobe * cap — the
            candidate pool actually scanned).
          backend: "xla" only.
          nprobe: clusters scanned per query (defaults to the build-time
            setting; ``n_clusters`` scans everything = exact).
          scan_impl: segment-scan implementation for this call — "auto" /
            "xla" / "pallas" (defaults to the build setting; see
            scan.resolve_scan_impl — "auto" is the XLA scan on a sharded
            index). "pallas" requires a single-shard index; ids match the
            xla path except where two rows' distances lie within f32
            rounding of each other and swap, distances to f32 rounding.

        Returns (dists (Nq, k_top) f32 ascending, global row indices
        (Nq, k_top) int32); -1 ids mark under-filled probes (raise
        nprobe if callers see them).
        """
        if backend != "xla":
            raise NotImplementedError(
                "IVFIndex only supports the xla backend")
        if k_top > self.size:
            raise ValueError(f"k_top={k_top} > gallery size {self.size}")
        # `is None`, not truthiness: `nprobe or default` would silently
        # map an explicit nprobe=0 to the default (the k_top=0 bug class)
        np_ = self.nprobe if nprobe is None else nprobe
        if np_ < 1:
            raise ValueError(f"nprobe must be >= 1, got {np_}")
        np_ = min(np_, self.n_clusters)
        if k_top > np_ * self.cap:
            raise ValueError(
                f"k_top={k_top} > nprobe*cap={np_ * self.cap} scanned "
                f"rows per query; raise nprobe")
        impl = scan.resolve_scan_impl(self.scan_impl, scan_impl,
                                      sharded=self.n_shards > 1)
        if impl == "pallas" and self.n_shards > 1:
            raise NotImplementedError(
                "scan_impl='pallas' is single-shard only (the fused "
                "kernel does not compose with shard_map yet)")
        key = (k_top, np_, impl)
        fn = self._fns.get(key)
        if fn is None:
            build = (self._build_topk_sharded if self.n_shards > 1
                     else self._build_topk)
            fn = self._fns[key] = build(k_top, np_, impl)
        return fn(queries)

    # -- single-device query path -------------------------------------------

    def _build_topk(self, k_top: int, nprobe: int, impl: str):
        C, cap = self.n_clusters, self.cap
        k = self.centroids.shape[1]

        # the segments ride in as arguments: a jit that closed over them
        # would bake the whole gallery into the program as a constant
        @jax.jit
        def run(queries, L, centroids, gp_pad, gn_pad, ids_pad):
            qp = scan.project_queries(L, queries)
            probes = _probe(qp, centroids, nprobe)
            return ivf_scan_topk(qp, probes, gp_pad.reshape(C, cap, k),
                                 gn_pad.reshape(C, cap),
                                 ids_pad.reshape(C, cap), kk=k_top,
                                 block_q=self.block_q,
                                 use_kernel=(impl == "pallas"))

        return lambda queries: run(queries, self.L, self.centroids,
                                   self.gp_pad, self.gn_pad, self.ids_pad)

    # -- sharded query path (whole clusters per shard) -----------------------

    def _build_topk_sharded(self, k_top: int, nprobe: int, impl: str):
        # impl is always "xla" here (topk rejects pallas when sharded);
        # the per-shard body below is the same pure-jnp reference the
        # single-device xla path runs, via kernels/ivf_scan.
        del impl
        C, cap = self.n_clusters, self.cap
        C_loc = C // self.n_shards
        kk = min(k_top, nprobe * cap)

        def local_candidates(shard, qp, extras, locals_):
            (probes,) = extras
            gp_loc, gn_loc, ids_loc = locals_
            k = gp_loc.shape[1]
            # slot C_loc is an appended all-sentinel cluster; probes owned
            # by other shards land there so shapes stay static
            g = jnp.concatenate([gp_loc.reshape(C_loc, cap, k),
                                 jnp.zeros((1, cap, k), jnp.float32)])
            gn = jnp.concatenate([gn_loc.reshape(C_loc, cap),
                                  jnp.full((1, cap), BIG, jnp.float32)])
            ids = jnp.concatenate([ids_loc.reshape(C_loc, cap),
                                   jnp.full((1, cap), -1, jnp.int32)])
            slot = probes - shard * C_loc
            slot = jnp.where((slot >= 0) & (slot < C_loc), slot, C_loc)
            return _probed_topk(qp, slot, g, gn, ids, kk, self.block_q)

        arrays = (self.gp_pad, self.gn_pad, self.ids_pad)
        inner = scan.build_sharded_topk(self.mesh, self.axes, arrays,
                                        local_candidates, k_top, n_extras=1)

        @jax.jit
        def run(queries, L, centroids, *arrays):
            qp = scan.project_queries(L, queries)
            return inner(qp, _probe(qp, centroids, nprobe), *arrays)

        return lambda queries: run(queries, self.L, self.centroids, *arrays)


def _probe(qp, centroids, nprobe: int):
    """Coarse quantizer: ids of the nprobe nearest centroids (Nq, np)."""
    cd = metric_sqdist_factored(qp, centroids)
    _, probes = jax.lax.top_k(-cd, nprobe)
    return probes.astype(jnp.int32)


def _probed_topk(qp, cluster_slots, g, gn, ids, kk: int, block_q: int):
    """Top-kk candidates per query from its probed segments.

    Thin alias for ``kernels.ivf_scan.ivf_scan_topk(use_kernel=False)``
    — the chunked XLA reference scan, which is also the pure-jnp
    per-shard body the sharded path runs inside shard_map (the appended
    all-sentinel cluster at slot C_loc is reached via the reference's
    ``mode="clip"`` gathers)."""
    return ivf_scan_topk(qp, cluster_slots, g, gn, ids, kk=kk,
                         block_q=block_q, use_kernel=False)
