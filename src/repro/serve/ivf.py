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

import contextlib
import dataclasses
import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ivf_scan import ivf_scan_topk
from repro.kernels.metric_topk import metric_sqdist_factored, project_gallery
from repro.kernels.metric_topk.kernel import BIG
from repro.kernels.pairwise_dist.ref import pairwise_sqdist_ref
from repro.obs import annotate
from repro.serve import scan


# -- metric-space k-means ----------------------------------------------------

def _row_blocks(fn, block_rows: int, *arrays):
    """``fn`` over row blocks of ``arrays`` (each with M leading rows), the
    last block zero-padded, so a (rows, C) distance block never
    materializes whole at big M. ``fn`` maps one block of each array to a
    tuple of per-row outputs; returns them for the M real rows."""
    M = arrays[0].shape[0]
    B = min(block_rows, M)
    Mp = ((M + B - 1) // B) * B
    blocks = tuple(jnp.pad(a, [(0, Mp - M)] + [(0, 0)] * (a.ndim - 1))
                   .reshape(Mp // B, B, *a.shape[1:]) for a in arrays)
    outs = jax.lax.map(lambda blk: fn(*blk), blocks)
    return tuple(o.reshape(Mp, *o.shape[2:])[:M] for o in outs)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def _assign(gp, centroids, block_rows: int):
    """Nearest-centroid assignment, chunked over rows so the (M, C)
    distance matrix never materializes at big M. Returns (assign (M,)
    int32, min_sqdist (M,) f32)."""
    def blk(g):
        d = pairwise_sqdist_ref(g, centroids)
        return jnp.argmin(d, axis=1).astype(jnp.int32), jnp.min(d, axis=1)

    return _row_blocks(blk, block_rows, gp)


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


@functools.partial(jax.jit, static_argnames=("block_rows",))
def _own_sqdist(gp, centroids, assign, block_rows: int):
    """Squared distance of each row to its own cluster's centroid (M,),
    summed from the difference."""
    def blk(g, a):
        return (jnp.sum(jnp.square(g - centroids[a]), axis=1),)

    return _row_blocks(blk, block_rows, gp, assign)[0]


@functools.partial(jax.jit, static_argnames=("n_near", "block_rows"))
def _nearest_open(gp, centroids, full, n_near: int, block_rows: int):
    """Each row's ``n_near`` nearest centroids among those not ``full``
    (C,) bool, nearest first: (ids (M, n_near) int32, sqdists (M,
    n_near))."""
    shut = jnp.where(full, jnp.inf, 0.0)

    def blk(g):
        neg, ids = jax.lax.top_k(-(pairwise_sqdist_ref(g, centroids)
                                   + shut), n_near)
        return ids.astype(jnp.int32), -neg

    return _row_blocks(blk, block_rows, gp)


# nearest open clusters listed per spilled row in one device pass; rows
# whose list fills up are listed again
_SPILL_NEAR = 32


def _balance_assign(gp, centroids, assign, cap: int) -> np.ndarray:
    """Capacity-bounded assignment: clusters keep their ``cap`` closest
    rows; overflow rows move to the nearest cluster with free space.

    Invariants: no cluster ends over ``cap``; every row is placed; the
    rows of a cluster within ``cap`` stay; a spilled row lands in the
    nearest cluster that still had room when it was placed, so every
    cluster nearer to it ends full. Total capacity C*cap >= M (cap >=
    ceil(M/C)) guarantees a place for every row.

    Vectorized rounds: every unplaced spilled row proposes to its
    nearest open cluster, and each cluster takes its nearest proposers
    up to its room. A round places every row or fills a cluster, so at
    most C rounds run. Distances run on the device in row blocks (each
    spilled row's ``_SPILL_NEAR`` nearest open clusters, listed again
    only for rows whose list has filled up); the host holds index arrays
    only.
    """
    C = centroids.shape[0]
    assign = np.asarray(assign)
    counts = np.bincount(assign, minlength=C)
    if counts.max() <= cap:
        return assign
    gp = jnp.asarray(gp, jnp.float32)
    centroids = jnp.asarray(centroids, jnp.float32)
    n_near, block_rows = min(_SPILL_NEAR, C), 16384
    # rank each row within its cluster, nearest first; ranks >= cap spill
    own = np.asarray(_own_sqdist(gp, centroids, jnp.asarray(assign),
                                 block_rows))
    order = np.lexsort((own, assign))
    first = np.cumsum(counts) - counts
    rank = np.empty(len(assign), np.int64)
    rank[order] = np.arange(len(assign)) - first[assign[order]]
    rows = np.flatnonzero(rank >= cap)
    counts = np.minimum(counts, cap)
    assign = assign.copy()

    chunk = min(block_rows, len(assign))

    def nearest(r):         # in chunks of one shape: a single compile
        full = jnp.asarray(counts >= cap)
        out = [_nearest_open(gp[jnp.asarray(np.resize(r[lo:lo + chunk],
                                                      chunk))],
                             centroids, full, n_near, block_rows)
               for lo in range(0, len(r), chunk)]
        return tuple(np.concatenate([np.asarray(o[i]) for o in out])[:len(r)]
                     for i in (0, 1))

    near, near_d = nearest(rows)
    while rows.size:
        is_open = counts[near] < cap                        # (S, n_near)
        lost = ~is_open.any(axis=1)
        if lost.any():      # every listed cluster filled: list again
            near[lost], near_d[lost] = nearest(rows[lost])
            continue
        j = np.argmax(is_open, axis=1)
        s = np.arange(len(rows))
        target, dist = near[s, j], near_d[s, j]
        by = np.lexsort((dist, target))                     # nearest first
        t = target[by]
        within = np.arange(len(by)) - np.searchsorted(t, t)
        take = within < cap - counts[t]
        assign[rows[by[take]]] = t[take]
        counts += np.bincount(t[take], minlength=C)
        keep = by[~take]
        rows, near, near_d = rows[keep], near[keep], near_d[keep]
    return assign


def _slot_rows(assign, n_clusters: int, cap: int) -> np.ndarray:
    """The cluster-major padded layout: the gallery row of each of the
    C*cap slots (rows of a cluster in row order), -1 on pad slots."""
    counts = np.bincount(assign, minlength=n_clusters)
    order = np.argsort(assign, kind="stable")
    within = np.arange(len(assign)) - (np.cumsum(counts) - counts)[
        assign[order]]
    src = np.full((n_clusters * cap,), -1, np.int32)
    src[assign[order] * cap + within] = order
    return src


def _lay_out(gp, gn, src):
    """The padded segments on the device: (gp_pad (C*cap, k) with zero
    pad rows, gn_pad with BIG on pads, ids_pad = ``src``). Assembled on
    the host: a gather on the device would hold a row-major copy of the
    gallery beside both layouts (the TPU stores f32[M, d_out] column-
    major), about 14 GB at 1M rows."""
    real = np.flatnonzero(src >= 0)
    gp_pad = np.zeros((len(src), gp.shape[1]), np.float32)
    gp_pad[real] = np.asarray(gp)[src[real]]
    gn_pad = np.full((len(src),), BIG, np.float32)
    gn_pad[real] = np.asarray(gn)[src[real]]
    return tuple(map(jnp.asarray, (gp_pad, gn_pad, src)))


@contextlib.contextmanager
def _stage(stages: dict, name: str):
    """One build stage: a profiler annotation ``ivf.<name>``, and its
    wall seconds kept in ``stages``."""
    t = time.perf_counter()
    with annotate("ivf." + name):
        yield
    stages[name] = time.perf_counter() - t


# -- the index ---------------------------------------------------------------

# (name, help) of each count a probed scan returns (``_scan_counts``), over
# a batch's live queries only
SCAN_COUNTS = (
    ("probes", "segments probed, summed over queries"),
    ("rows_probed", "real rows of every probe of every query"),
    ("rows_read", "real rows of the distinct segments each batch probed"),
    ("pad_slots", "pad slots scanned, summed over probes"),
    ("underfilled", "answers holding a -1 id (fewer real rows than k)"),
)


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
    # wall seconds of the build's stages (kmeans, balance, layout) and of
    # the whole build; empty for an index assembled from saved arrays
    build_seconds: dict = dataclasses.field(default_factory=dict)
    # (C,) int32 real rows of each segment, replicated; counted from
    # ids_pad when not given
    seg_rows: Optional[jax.Array] = None
    _fns: dict = dataclasses.field(default_factory=dict, repr=False)

    # the counters of a probed scan (``topk_counted``), in its order, with
    # their help text
    scan_counts = SCAN_COUNTS

    def __post_init__(self):
        if self.seg_rows is None:
            seg = np.count_nonzero(
                np.asarray(self.ids_pad).reshape(self.n_clusters, self.cap)
                >= 0, axis=1).astype(np.int32)
            self.seg_rows = (scan.put_replicated(self.mesh, seg)
                             if self.axes else jnp.asarray(seg))

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
        cap = int(-((-max(cap_factor, 1.0) * M) // C))      # ceil
        cap = ((cap + 7) // 8) * 8
        stages = {}
        t_build = time.perf_counter()
        with annotate("ivf.build", rows=M, n_clusters=C, cap=cap):
            with _stage(stages, "kmeans"):
                centroids, assign, _ = kmeans_projected(gp, C, iters=iters,
                                                        seed=seed)
                jax.block_until_ready(assign)
            with _stage(stages, "balance"):
                assign = _balance_assign(gp, centroids, assign, cap)
            with _stage(stages, "layout"):
                src = _slot_rows(assign, C, cap)
                gp_pad, gn_pad, ids_pad = _lay_out(gp, gn, src)
                jax.block_until_ready(gp_pad)
        stages["build"] = time.perf_counter() - t_build
        seg_rows = jnp.asarray(np.count_nonzero(
            src.reshape(C, cap) >= 0, axis=1).astype(np.int32))
        if axes:
            gp_pad = scan.put_row_sharded(mesh, axes, gp_pad)
            gn_pad = scan.put_row_sharded(mesh, axes, gn_pad)
            ids_pad = scan.put_row_sharded(mesh, axes, ids_pad)
            L = scan.put_replicated(mesh, L)
            centroids = scan.put_replicated(mesh, centroids)
            seg_rows = scan.put_replicated(mesh, seg_rows)
        return cls(L=jnp.asarray(L), centroids=centroids, gp_pad=gp_pad,
                   gn_pad=gn_pad, ids_pad=ids_pad, cap=cap, n_clusters=C,
                   nprobe=min(nprobe, C), n_rows=M, scan_impl=scan_impl,
                   mesh=mesh, axes=axes, build_seconds=stages,
                   seg_rows=seg_rows)

    @property
    def size(self) -> int:
        """Real (unpadded) gallery rows."""
        return self.n_rows

    @property
    def n_shards(self) -> int:
        """Mesh shards the segments live on (1 when unsharded)."""
        return scan.n_shards(self.mesh, self.axes)

    @property
    def pad_share(self) -> float:
        """Share of the layout's C*cap slots that are pads."""
        return 1.0 - self.n_rows / (self.n_clusters * self.cap)

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
        dists, ids, _ = self._search(queries, k_top, None, backend, nprobe,
                                     scan_impl)
        return dists, ids

    def topk_counted(self, queries, k_top: int, live: Optional[int] = None,
                     backend: str = "xla", nprobe: Optional[int] = None,
                     scan_impl: Optional[str] = None):
        """As ``topk``, plus the scan's work on the first ``live`` queries
        (default all; the rest are a batch's pad rows): an int32 device
        vector with one entry per count of ``scan_counts``, computed inside
        the same program as the answers."""
        return self._search(queries, k_top, live, backend, nprobe, scan_impl)

    def _search(self, queries, k_top, live, backend, nprobe, scan_impl):
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
        return fn(queries, jnp.shape(queries)[0] if live is None else live)

    # -- single-device query path -------------------------------------------

    def _build_topk(self, k_top: int, nprobe: int, impl: str):
        C, cap = self.n_clusters, self.cap
        k = self.centroids.shape[1]

        # the segments ride in as arguments: a jit that closed over them
        # would bake the whole gallery into the program as a constant
        @jax.jit
        def run(queries, live, L, centroids, seg_rows, gp_pad, gn_pad,
                ids_pad):
            qp = scan.project_queries(L, queries)
            probes = _probe(qp, centroids, nprobe)
            d, i = ivf_scan_topk(qp, probes, gp_pad.reshape(C, cap, k),
                                 gn_pad.reshape(C, cap),
                                 ids_pad.reshape(C, cap), kk=k_top,
                                 block_q=self.block_q,
                                 use_kernel=(impl == "pallas"))
            return d, i, _scan_counts(probes, seg_rows, i, live, cap)

        return lambda queries, live: run(
            queries, live, self.L, self.centroids, self.seg_rows,
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
        def run(queries, live, L, centroids, seg_rows, *arrays):
            qp = scan.project_queries(L, queries)
            probes = _probe(qp, centroids, nprobe)
            d, i = inner(qp, probes, *arrays)
            return d, i, _scan_counts(probes, seg_rows, i, live, cap)

        return lambda queries, live: run(queries, live, self.L,
                                         self.centroids, self.seg_rows,
                                         *arrays)


def _scan_counts(probes, seg_rows, ids_out, live, cap: int):
    """The work of a probed scan on its first ``live`` queries, in the
    order of ``SCAN_COUNTS``, from the real rows of each segment
    ``seg_rows`` (C,)."""
    is_live = jnp.arange(probes.shape[0]) < live
    n_probes = jnp.sum(is_live, dtype=jnp.int32) * probes.shape[1]
    rows_probed = jnp.sum(jnp.where(is_live[:, None], seg_rows[probes], 0))
    probed = jnp.zeros(seg_rows.shape, jnp.int32).at[probes].max(
        jnp.broadcast_to(is_live[:, None], probes.shape).astype(jnp.int32))
    return jnp.stack([
        n_probes, rows_probed, jnp.sum(probed * seg_rows),
        n_probes * cap - rows_probed,
        jnp.sum(is_live & jnp.any(ids_out < 0, axis=1), dtype=jnp.int32),
    ]).astype(jnp.int32)


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
