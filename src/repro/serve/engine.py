"""Retrieval engine: bucketed, jitted, cached query execution over an index.

The engine owns the serving concerns the index should not know about:

  * **batch bucketing** — incoming batches pad up to a small set of
    power-of-two bucket sizes so jit compiles once per bucket instead of
    once per distinct batch size (pad queries are sliced off the result);
  * **backend choice** — factored XLA path (default, sharded-capable) or
    the fused Pallas kernel (kernels/metric_topk; ExactIndex only);
  * **hot-query cache** — a bounded LRU keyed by (query bytes, k). Repeat
    queries (think: trending items, retried requests) skip the device
    entirely when every row of a batch hits. ``index.version`` is the
    invalidation hook: any bump (gallery mutation, index swap-in) flushes
    the cache before the next lookup;
  * **observability** — the engine owns the stack-wide
    ``obs.MetricsRegistry`` and ``obs.Tracer``: request/query/cache
    counters, the device-path latency histogram, and per-index memory
    gauges all live on the registry, and every layer that attaches to
    the engine (scheduler, batcher, mutable index, miner, closed loop)
    records into the same instance. ``stats()`` is a backward-compatible
    *view* over the registry — same keys, same values as the old private
    counters. Counter updates are atomic under the registry lock: the
    old bare-attribute read-modify-writes lost increments when batcher
    and scheduler threads raced.

Works against any MetricIndex backend (serve/index.py exact scan,
serve/ivf.py cluster-pruned, serve/pq.py product-quantized, and
serve/mutable.py wrapping any of them).
"""

from __future__ import annotations

import collections
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import MetricsRegistry, Tracer, index_memory
from repro.obs.trace import NULL_SPAN
from repro.serve.clock import Clock, SystemClock
from repro.serve.index import MetricIndex

DEFAULT_BUCKETS = (8, 32, 128, 512)
DEFAULT_CACHE = 1024

# every component index_memory can report, so a collector can zero the
# ones the current index lacks (an index swap must not leave stale bytes)
_MEMORY_COMPONENTS = ("gallery", "codes", "centroids", "delta",
                      "host_store")


class RetrievalEngine:
    """Query executor over a MetricIndex: bucketing + caching + counters.

    One engine serves one index (swap ``engine.index`` to repoint it; the
    cache notices the identity change and flushes). Thread-safety: calls
    are expected from a single worker thread — the MicroBatcher front
    door provides exactly that — but the registry-backed counters are
    additionally safe under concurrent callers (each increment is atomic
    under the registry lock).
    """

    def __init__(self, index: MetricIndex, k_top: int = 10,
                 backend: str = "xla",
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 cache_size: int = DEFAULT_CACHE,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 clock: Optional[Clock] = None):
        """Args:
          index: any MetricIndex backend (Exact / IVF / IVFPQ / Mutable).
          k_top: default neighbors per query (>= 1; per-call override in
            ``search``).
          backend: "xla" (default; the only option for IVF/IVFPQ/sharded)
            or "pallas" (fused kernel, single-device ExactIndex).
          buckets: ascending jit batch sizes; batches pad up to the next
            bucket (an oversized batch is served as-is, one extra
            compile).
          cache_size: hot-query LRU entries (0 disables caching).
          registry: the stack's MetricsRegistry (default: a fresh one —
            pass an existing registry to merge several engines' metrics).
          tracer: the stack's Tracer (default: a fresh one with
            sample_rate 0 — tracing off until a front end raises it).
          clock: time source for busy-time/latency measurement (default
            SystemClock; FakeClock makes histogram tests exact).
        """
        if backend not in ("xla", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        if k_top < 1:
            raise ValueError(f"k_top must be >= 1, got {k_top}")
        self.index = index
        self.k_top = k_top
        self.backend = backend
        self.buckets = tuple(sorted(buckets))
        self.cache_size = cache_size
        self.clock = clock if clock is not None else SystemClock()
        # attached traffic front end (serve/scheduler.py RequestScheduler
        # sets this); stats() merges its observability block when present
        self.frontend = None
        self.registry = (registry if registry is not None
                         else MetricsRegistry(clock=self.clock))
        self.tracer = (tracer if tracer is not None
                       else Tracer(clock=self.clock, sample_rate=0.0))
        r = self.registry
        self._c_requests = r.counter(
            "engine_requests_total", "search() calls")
        self._c_queries = r.counter(
            "engine_queries_total", "query rows received")
        self._c_device_queries = r.counter(
            "engine_device_queries_total",
            "query rows that reached the device (cache misses, incl. "
            "bucket pad overhead excluded)")
        self._c_cache_hits = r.counter(
            "engine_cache_hits_total",
            "query rows served from the hot-query LRU")
        self._c_cache_misses = r.counter(
            "engine_cache_misses_total",
            "query rows that missed the LRU")
        self._h_search = r.histogram(
            "engine_search_seconds",
            "device-path latency per searched batch")
        self._g_cache_entries = r.gauge(
            "engine_cache_entries", "hot-query LRU entries resident")
        self._g_gallery_rows = r.gauge(
            "index_gallery_rows", "rows the served index holds")
        self._g_memory = r.gauge(
            "index_memory_bytes",
            "resident bytes of the served index, by component",
            labelnames=("component",))
        self._g_build = r.gauge(
            "index_build_seconds",
            "wall seconds of the served index's build, by stage",
            labelnames=("stage",))
        r.register_collector(self._collect_gauges)
        # (query f32 bytes, k) -> (dists (k,), idxs (k,)) numpy rows
        self._cache: "collections.OrderedDict" = collections.OrderedDict()
        # identity + version: a freshly built replacement index also has
        # version 0, so version alone cannot detect an index swap-in
        self._cache_index = index
        self._cache_version = index.version
        self._adopt_index()

    def _adopt_index(self):
        """Point the index's lifecycle events (mutable compaction/swap,
        snapshot save) at this engine's registry. Re-run by the gauge
        collector so a swapped-in index is adopted too."""
        if (hasattr(self.index, "registry")
                and getattr(self.index, "registry", None) is None):
            self.index.registry = self.registry

    def _collect_gauges(self):
        """Snapshot-time gauges: LRU residency, gallery rows, and the
        per-component memory budget (ROADMAP's paper-scale accounting).
        Components the current index lacks are zeroed — an index swap
        must not leave another backend's bytes dangling."""
        self._adopt_index()
        self._g_cache_entries.set(len(self._cache))
        self._g_gallery_rows.set(self.index.size)
        mem = index_memory(self.index)
        for comp in _MEMORY_COMPONENTS:
            self._g_memory.set(mem.get(comp, 0), component=comp)
        for stage, secs in getattr(self.index, "build_seconds",
                                   {}).items():
            self._g_build.set(secs, stage=stage)

    # -- backward-compatible counter attributes ------------------------------
    # (tests and the miner read these; writes go through the registry)

    @property
    def n_requests(self) -> int:
        return int(self._c_requests.value())

    @property
    def n_queries(self) -> int:
        return int(self._c_queries.value())

    @property
    def n_device_queries(self) -> int:
        return int(self._c_device_queries.value())

    @property
    def busy_s(self) -> float:
        """Device-path wall time: the sum of ``engine_search_seconds``."""
        return self._h_search.sum()

    @property
    def cache_hits(self) -> int:
        return int(self._c_cache_hits.value())

    @property
    def cache_misses(self) -> int:
        return int(self._c_cache_misses.value())

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return n    # oversized batch: serve as-is (one extra compile)

    # -- hot-query LRU -------------------------------------------------------

    def _cache_lookup(self, keys):
        """Per-row lookup, refreshing LRU recency. Hit/miss counters are
        settled by the caller: hits count only rows actually served from
        cache (i.e. the whole batch hit and the device was skipped) — a
        row that was present but recomputed anyway saved nothing."""
        if (self.index is not self._cache_index
                or self.index.version != self._cache_version):
            self.invalidate_cache()                      # invalidation hook
        rows = []
        for key in keys:
            hit = self._cache.get(key)
            if hit is not None:
                self._cache.move_to_end(key)
            rows.append(hit)
        return rows

    def _cache_store(self, keys, dists, idxs):
        if self.cache_size <= 0:
            return
        for row, key in enumerate(keys):
            # copies: the returned arrays are the caller's to mutate
            self._cache[key] = (dists[row].copy(), idxs[row].copy())
            self._cache.move_to_end(key)
        while len(self._cache) > self.cache_size:
            self._cache.popitem(last=False)

    def invalidate_cache(self):
        """Manual flush (version bumps and index swaps do this lazily on
        the next search)."""
        self._cache.clear()
        self._cache_index = self.index
        self._cache_version = self.index.version

    # -- search --------------------------------------------------------------

    def search(self, queries, k_top: Optional[int] = None, *,
               span=None, **topk_kw):
        """queries (Nq, d) or a single (d,) vector. Returns
        (dists (Nq, k_top), indices (Nq, k_top)) as numpy arrays.

        Extra keyword args forward to ``index.topk`` — the degradation
        hook: the scheduler passes per-request quality knobs (``nprobe``,
        ``rerank``) here without the engine knowing their meaning. Knobs
        join the cache key, so answers computed at degraded quality are
        never served to full-quality lookups (or vice versa).

        ``span`` (keyword-only, never forwarded to the index) is an
        obs.Span under which the engine records its internal stages —
        cache_lookup / pad / device_topk — with scan_impl, nprobe,
        rerank_depth, and batch size as attributes; front ends pass the
        sampled request's span here."""
        sp = span if span is not None else NULL_SPAN
        # `is None`, not truthiness: `k_top or default` silently mapped an
        # explicit k_top=0 to the default instead of rejecting it
        k = self.k_top if k_top is None else k_top
        if k < 1:
            raise ValueError(f"k_top must be >= 1, got {k}")
        knobs = tuple(sorted(topk_kw.items()))
        caching = self.cache_size > 0
        # keys come from host bytes, so with the cache on, stay in numpy
        # until the hit check fails — a full hit never touches the device
        q = (np.asarray(queries, np.float32) if caching
             else jnp.asarray(queries, jnp.float32))
        single = q.ndim == 1
        if single:
            q = q[None, :]
        n = q.shape[0]
        self._c_requests.inc()
        self._c_queries.inc(n)
        if n == 0:
            return (np.zeros((0, k), np.float32),
                    np.zeros((0, k), np.int32))

        keys = None
        if caching:                 # disabled cache pays no hashing
            c_sp = sp.child("cache_lookup")
            keys = [(row.tobytes(), k, knobs) for row in q]
            cached = self._cache_lookup(keys)
            if all(c is not None for c in cached):  # full hit: skip device
                self._c_cache_hits.inc(n)
                c_sp.set_attrs(hit=True, rows=n).end()
                dists = np.stack([c[0] for c in cached])
                idxs = np.stack([c[1] for c in cached])
                return (dists[0], idxs[0]) if single else (dists, idxs)
            self._c_cache_misses.inc(n)
            c_sp.set_attrs(hit=False, rows=n).end()
            q = jnp.asarray(q)

        self._c_device_queries.inc(n)
        b = self._bucket(n)
        if b != n:      # pad rows are real compute but sliced from results
            with sp.child("pad").set_attrs(rows=n, bucket=b):
                q = jnp.concatenate(
                    [q, jnp.zeros((b - n, q.shape[1]), q.dtype)])

        d_sp = sp.child("device_topk").set_attrs(
            batch=b, k=k,
            scan_impl=getattr(self.index, "scan_impl", None),
            nprobe=topk_kw.get("nprobe",
                               getattr(self.index, "nprobe", None)),
            rerank_depth=topk_kw.get("rerank",
                                     getattr(self.index, "rerank_depth",
                                             None)))
        counted = getattr(self.index, "topk_counted", None)
        t0 = self.clock.now()
        if counted is None:
            dists, idxs = self.index.topk(q, k, backend=self.backend,
                                          **topk_kw)
            work = None
        else:           # a probed index also says what its scan touched
            dists, idxs, work = counted(q, k, n, backend=self.backend,
                                        **topk_kw)
        dists, idxs, work = jax.block_until_ready((dists, idxs, work))
        dt = self.clock.now() - t0
        d_sp.end()
        self._h_search.observe(dt)
        if work is not None:
            for (name, help_), v in zip(self.index.scan_counts,
                                        np.asarray(work).tolist()):
                self.registry.counter(f"ivf_{name}_total", help_).inc(v)

        dists = np.asarray(dists[:n])
        idxs = np.asarray(idxs[:n])
        if keys is not None:
            self._cache_store(keys, dists, idxs)
        if single:
            return dists[0], idxs[0]
        return dists, idxs

    def warmup(self, ks: Optional[Sequence[int]] = None):
        """Compile every (bucket, k) combination up front so first
        requests don't pay jit. ``ks`` defaults to just the engine's
        ``k_top``; pass the non-default k values clients will request
        (each distinct k is its own compile)."""
        ks = (self.k_top,) if ks is None else tuple(ks)
        for k in ks:
            if k < 1:
                raise ValueError(f"k_top must be >= 1, got {k}")
        d = self.index.L.shape[1]
        for k in ks:
            for b in self.buckets:
                self.index.topk(jnp.zeros((b, d), jnp.float32), k,
                                backend=self.backend)

    def stats(self) -> dict:
        """Serving counters as a plain dict (safe to log/serialize) — a
        backward-compatible view over the MetricsRegistry (the registry
        snapshot is the superset; this keeps every pre-registry consumer
        working unmodified).

        Always present: n_requests / n_queries / n_device_queries,
        busy_s, qps (device-side), gallery_size, n_shards, backend,
        index (class name), cache_hits / cache_misses / cache_entries.
        Backend extras appear when the index exposes them: delta_rows /
        tombstones / compactions (MutableIndex), code_bytes_per_row /
        compression_ratio (IVFPQIndex), scan_impl (IVF/IVFPQ segment-scan
        implementation knob), n_clusters / nprobe / cap (IVF/IVFPQ
        layout) and pad_share (IVFIndex: pad slots over all C*cap).
        With a traffic front end attached
        (serve/scheduler.py), a ``frontend`` sub-dict adds per-class
        latency percentiles, queue depths, admission/rejection/expiry
        counters, and the current degradation level.
        """
        # device qps over device-served queries only: cache hits add no
        # busy time and would inflate the ratio under repeat traffic
        busy = self.busy_s
        qps = self.n_device_queries / busy if busy > 0 else 0.0
        out = {
            "n_requests": self.n_requests,
            "n_queries": self.n_queries,
            "n_device_queries": self.n_device_queries,
            "busy_s": busy,
            "qps": qps,
            "gallery_size": self.index.size,
            "n_shards": self.index.n_shards,
            "backend": self.backend,
            "index": type(self.index).__name__,
            # the (d_out, d_in) metric-factor contract: d_out sizes every
            # projected/coded artifact, d_in is the raw feature dim
            "l_shape": list(np.shape(self.index.L)),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "cache_entries": len(self._cache),
        }
        # backend-specific extras, surfaced when the index has them:
        # mutation lifecycle counters (serve/mutable.py MutableIndex) and
        # compression figures (serve/pq.py IVFPQIndex)
        for key, attr in (("delta_rows", "delta_rows"),
                          ("tombstones", "tombstones"),
                          ("compactions", "n_compactions"),
                          ("code_bytes_per_row", "code_bytes_per_row"),
                          ("compression_ratio", "compression_ratio"),
                          ("scan_impl", "scan_impl"),
                          ("n_clusters", "n_clusters"),
                          ("nprobe", "nprobe"), ("cap", "cap"),
                          ("pad_share", "pad_share")):
            value = getattr(self.index, attr, None)
            if value is not None:
                out[key] = value
        if self.frontend is not None:
            out["frontend"] = self.frontend.observability()
        return out
