"""Mutable gallery index: streaming upserts/deletes over a frozen base.

``ExactIndex`` and ``IVFIndex`` are build-once: their device layouts are
immutable by design (static shapes keep the jitted query paths hot). A
production gallery is not — rows arrive and expire continuously, and the
async PS trainer keeps producing fresh L factors. ``MutableIndex`` closes
that gap with the classic LSM split:

  base      any frozen MetricIndex (Exact, IVF, or IVFPQ), untouched by
            mutations;
  delta     an append-only buffer of *pre-projected* new rows, scanned
            exactly (it stays small between compactions);
  tombstones  dead slots — deleted rows, and rows superseded by an upsert
            of the same external id. Masked at merge time, never eagerly
            rewritten into device arrays.

External ids are stable across every mutation and compaction: the id->slot
map tracks where each id currently lives ("base" slot or "delta" slot),
and ``topk`` returns external ids, not layout positions. Every mutation
*batch* bumps ``version``, so the engine's hot-query LRU invalidates for
free (serve/engine.py keys its flush on ``index.version``).

Query path: oversample the base past its dead slots (k_top + #dead base
slots, clamped to the base's candidate pool), scan the delta buffer with
the same factored distance the exact path uses (scan.py's deterministic
(dist, id) select), then lexicographically merge (distance, external id)
on the host while masking tombstones. No rebuild ever happens on the
query path.

Compaction folds the delta into the base and drops tombstones:

  exact base  live base rows + live delta rows concatenate (already
              projected) in ascending-external-id order and a fresh
              ExactIndex wraps them — no re-projection, no re-clustering.
  IVF base    delta rows land in their nearest centroid's capacity
              headroom (the ``cap_factor`` slack from the build, plus
              slots freed by tombstones); if the live delta outgrows the
              total free capacity, the fold *spills* and triggers a full
              rebuild (fresh k-means over all live projected rows).
  IVFPQ base  same headroom fold, with each folded row *encoded* against
              the existing residual codebooks (delta rows are served
              full-precision until then); a spill-triggered rebuild
              re-trains k-means and the codebooks together.

``compact()`` can be called explicitly; ``auto_compact_delta`` /
``auto_compact_dead`` thresholds (fractions of the base size) trigger it
from the mutation path.

Metric hot-swap (``swap_metric``): with ``retain_raw=True`` the index
keeps the raw d-dim rows, so a fresh L from the trainer re-projects the
whole live gallery in blocks, rebuilds the base off to the side, and
swaps it in atomically — queries in flight keep hitting the old base
until the new one is fully built. This closes the trainer -> server loop.

Single-host only for now: wrapping a sharded base raises (the multi-host
gallery item on the ROADMAP covers that axis). Mutation calls (upsert /
delete / compact / swap_metric) must be serialized with in-flight topk
calls by the caller — the engine/batcher stack already issues queries
from one worker thread, and mutations belong on the control plane.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.metric_topk import metric_sqdist_factored, project_gallery
from repro.kernels.metric_topk.kernel import BIG
from repro.serve import scan
from repro.serve.index import ExactIndex
from repro.serve.ivf import IVFIndex
from repro.serve.pq import IVFPQIndex, _t_term

_DELTA_MIN_CAP = 256    # device delta buffer floor; grows by doubling so
                        # the jitted delta scan retraces O(log growth) times


class MutableIndex:
    """MetricIndex wrapper adding upsert/delete/compact/snapshot/hot-swap."""

    def __init__(self, base, L, *, ids=None, raw=None, base_kwargs=None,
                 auto_compact_delta: float = 0.5,
                 auto_compact_dead: float = 0.25):
        if base.n_shards > 1:
            raise NotImplementedError(
                "MutableIndex wraps single-shard bases only (multi-host "
                "gallery mutation is a ROADMAP item)")
        if not isinstance(base, (ExactIndex, IVFIndex, IVFPQIndex)):
            raise TypeError(f"unsupported base index {type(base).__name__}")
        if isinstance(base, IVFPQIndex) and base.rerank_depth < 1:
            # the (distance, id) merge against the exact delta scan is
            # only sound when the base returns exact distances too —
            # raw ADC scores would mis-order against delta candidates
            raise ValueError(
                "MutableIndex over an IVFPQ base requires rerank_depth "
                ">= 1 (exact base distances for the delta merge)")
        M = base.size
        self.base = base
        self.L = jnp.asarray(scan.check_metric_factor(L), jnp.float32)
        self.base_ids = (np.arange(M, dtype=np.int64) if ids is None
                         else np.asarray(ids, np.int64).copy())
        if self.base_ids.shape != (M,):
            raise ValueError(f"ids shape {self.base_ids.shape} != ({M},)")
        if len(np.unique(self.base_ids)) != M:
            raise ValueError("external ids must be unique")
        self.dead_base = np.zeros(M, bool)
        k = self.L.shape[0]
        self.delta_gp = np.zeros((0, k), np.float32)
        self.delta_gn = np.zeros((0,), np.float32)
        self.delta_ids = np.zeros((0,), np.int64)
        self.dead_delta = np.zeros((0,), bool)
        self.raw_base: Optional[np.ndarray] = None
        self.raw_delta: Optional[np.ndarray] = None
        if raw is not None:
            raw = np.asarray(raw, np.float32)
            if raw.shape[0] != M:
                raise ValueError(f"raw rows {raw.shape[0]} != base size {M}")
            self.raw_base = raw.copy()
            self.raw_delta = np.zeros((0, raw.shape[1]), np.float32)
        self._loc = {int(e): ("base", i)
                     for i, e in enumerate(self.base_ids)}
        self._next_id = int(self.base_ids.max()) + 1 if M else 0
        self.auto_compact_delta = auto_compact_delta
        self.auto_compact_dead = auto_compact_dead
        self._base_kwargs = dict(base_kwargs or {})
        self.version = base.version
        self.n_upserts = 0
        self.n_deletes = 0
        self.n_compactions = 0
        self.n_rebuilds = 0          # compactions that fell back to k-means
        self.n_swaps = 0
        # obs hook: the engine points this at its MetricsRegistry on
        # adoption; lifecycle transitions (compaction, spill rebuild,
        # metric swap) then land as structured events + counters
        self.registry = None
        self._delta_dev = None       # (version, cap, gp, gn, slot ids)
        self._delta_fns: dict = {}   # (cap, kk) -> jitted delta scan

    # -- construction --------------------------------------------------------

    @classmethod
    def build(cls, L, gallery, *, base: str = "exact", ids=None,
              retain_raw: bool = False, auto_compact_delta: float = 0.5,
              auto_compact_dead: float = 0.25, **base_kwargs):
        """Build the base index and wrap it.

        ``base``: "exact", "ivf", or "ivfpq" (``base_kwargs`` forward to
        the base build — n_clusters, nprobe, cap_factor, n_subspaces,
        ...). ``ids`` assigns external ids to the initial rows (default
        0..M-1, which keeps the deterministic smallest-id tie-break
        aligned with the base's positional one). ``retain_raw=True``
        keeps the raw feature rows so ``swap_metric`` can re-project
        under a fresh L.

        An IVFPQ base serves its frozen rows from uint8 codes while the
        delta buffer stays full-precision and exact; compaction encodes
        folded rows with the existing codebooks (see ``compact``).
        """
        gallery = np.asarray(gallery, np.float32)
        if base == "exact":
            b = ExactIndex.build(L, jnp.asarray(gallery), **base_kwargs)
        elif base == "ivf":
            b = IVFIndex.build(L, jnp.asarray(gallery), **base_kwargs)
        elif base == "ivfpq":
            b = IVFPQIndex.build(L, jnp.asarray(gallery), **base_kwargs)
        else:
            raise ValueError(f"unknown base {base!r} (exact|ivf|ivfpq)")
        return cls(b, L, ids=ids, raw=gallery if retain_raw else None,
                   base_kwargs=base_kwargs,
                   auto_compact_delta=auto_compact_delta,
                   auto_compact_dead=auto_compact_dead)

    # -- MetricIndex surface -------------------------------------------------

    @property
    def size(self) -> int:
        """Live rows (upserts minus deletes); what k_top is bounded by."""
        return len(self._loc)

    @property
    def n_shards(self) -> int:
        return 1

    @property
    def delta_rows(self) -> int:
        """Live rows currently served from the delta buffer."""
        return int((~self.dead_delta).sum())

    @property
    def code_bytes_per_row(self):
        """Forwarded from an IVFPQ base (None otherwise) so engine
        stats() surfaces compression figures through the wrapper."""
        return getattr(self.base, "code_bytes_per_row", None)

    @property
    def compression_ratio(self):
        """Forwarded from an IVFPQ base (None otherwise)."""
        return getattr(self.base, "compression_ratio", None)

    @property
    def scan_impl(self):
        """Forwarded from an IVF/IVFPQ base (None for exact) so engine
        stats() reports which segment-scan implementation serves."""
        return getattr(self.base, "scan_impl", None)

    @property
    def tombstones(self) -> int:
        """Dead slots awaiting compaction (base + delta)."""
        return int(self.dead_base.sum() + self.dead_delta.sum())

    def live_ids(self) -> np.ndarray:
        """Ascending external ids of every live row ((size,) int64)."""
        return np.sort(np.fromiter(self._loc, np.int64, len(self._loc)))

    def contains(self, ext_id: int) -> bool:
        return int(ext_id) in self._loc

    def topk(self, queries, k_top: int, backend: str = "xla", **kw):
        """(dists (Nq, k_top) ascending, external ids (Nq, k_top) int64).

        Extra kwargs (e.g. ``nprobe``) forward to the base. Returns host
        numpy arrays — the merge over (base ∪ delta) \\ tombstones runs on
        the host, where int64 external ids are cheap.
        """
        if k_top < 1:
            raise ValueError(f"k_top must be >= 1, got {k_top}")
        if k_top > self.size:
            raise ValueError(f"k_top={k_top} > live gallery size "
                             f"{self.size}")
        if isinstance(self.base, IVFPQIndex) and kw.get("rerank") == 0:
            # same soundness rule the ctor enforces for rerank_depth:
            # raw ADC base distances cannot merge against the exact
            # delta scan
            raise ValueError(
                "rerank=0 is unsupported through MutableIndex (the "
                "(distance, id) delta merge needs exact base distances)")
        queries = jnp.asarray(queries, jnp.float32)
        if queries.ndim != 2:
            raise ValueError(f"queries must be (Nq, d), got "
                             f"{queries.shape}")
        parts_d, parts_i = [], []

        n_dead_base = int(self.dead_base.sum())
        k_base = min(self.base.size, k_top + n_dead_base)
        pool = self._base_pool(kw)
        if pool is not None:
            k_base = min(k_base, pool)
        if k_base > 0:
            d_b, i_b = self.base.topk(queries, k_base, backend=backend,
                                      **kw)
            d_b = np.asarray(d_b, np.float32)
            i_b = np.asarray(i_b)
            valid = i_b >= 0                 # IVF under-filled probes: -1
            safe = np.where(valid, i_b, 0)
            dead = self.dead_base[safe] | ~valid
            parts_d.append(np.where(dead, np.inf, d_b))
            parts_i.append(np.where(dead, np.int64(-1),
                                    self.base_ids[safe]))

        if len(self.delta_ids):
            kk = min(k_top, self._delta_cap())
            d_d, s_d = self._delta_topk(queries, kk)
            d_d = np.asarray(d_d, np.float32)
            s_d = np.asarray(s_d)
            valid = s_d >= 0                 # pad / tombstoned slots
            safe = np.where(valid, s_d, 0)
            parts_d.append(np.where(valid, d_d, np.inf))
            parts_i.append(np.where(valid, self.delta_ids[safe],
                                    np.int64(-1)))

        dists = np.concatenate(parts_d, axis=1)
        ids = np.concatenate(parts_i, axis=1)
        order = np.lexsort((ids, dists), axis=-1)[:, :k_top]
        return (np.take_along_axis(dists, order, 1),
                np.take_along_axis(ids, order, 1))

    def _base_pool(self, kw) -> Optional[int]:
        """Candidate pool the base can actually return (IVF/IVFPQ:
        nprobe*cap). Oversampling past it would make the base raise;
        clamping instead costs only the (already approximate) recall of
        dead-slot oversamples."""
        if isinstance(self.base, (IVFIndex, IVFPQIndex)):
            np_ = kw.get("nprobe")
            if np_ is not None and np_ < 1:
                # reject here: a 0 pool would silently skip the base
                # scan before the base's own nprobe validation can fire
                raise ValueError(f"nprobe must be >= 1, got {np_}")
            np_ = self.base.nprobe if np_ is None else np_
            return min(np_, self.base.n_clusters) * self.base.cap
        return None

    # -- delta scan ----------------------------------------------------------

    def _delta_cap(self) -> int:
        n = len(self.delta_ids)
        if n <= _DELTA_MIN_CAP:
            return _DELTA_MIN_CAP
        return 1 << (n - 1).bit_length()

    def _delta_device(self):
        """Padded device mirror of the delta buffer, rebuilt per version.

        Tombstoned and pad slots carry gn = +BIG / slot id = -1 sentinels
        (same convention as the IVF segments), so they can only surface
        when fewer than kk live delta rows exist — and are masked then.
        """
        if self._delta_dev is not None and self._delta_dev[0] == self.version:
            return self._delta_dev
        cap = self._delta_cap()
        n = len(self.delta_ids)
        k = self.delta_gp.shape[1]
        gp = np.zeros((cap, k), np.float32)
        gn = np.full((cap,), BIG, np.float32)
        slots = np.full((cap,), -1, np.int32)
        gp[:n] = self.delta_gp
        gn[:n] = np.where(self.dead_delta, BIG, self.delta_gn)
        slots[:n] = np.where(self.dead_delta, -1,
                             np.arange(n, dtype=np.int32))
        self._delta_dev = (self.version, cap, jnp.asarray(gp),
                           jnp.asarray(gn), jnp.asarray(slots))
        return self._delta_dev

    def _delta_topk(self, queries, kk: int):
        _, cap, gp, gn, slots = self._delta_device()
        fn = self._delta_fns.get((cap, kk))
        if fn is None:
            @jax.jit
            def fn(q, L, gp, gn, slots):
                qp = scan.project_queries(L, q)
                d = metric_sqdist_factored(qp, gp, gn)
                return scan.topk_by_distance(
                    d, jnp.broadcast_to(slots, d.shape), kk)
            self._delta_fns[(cap, kk)] = fn
        return fn(queries, self.L, gp, gn, slots)

    # -- mutation ------------------------------------------------------------

    def upsert(self, rows, ids=None) -> np.ndarray:
        """Insert or replace rows; returns the external ids (n,) int64.

        ``rows`` (n, d) raw feature rows (projected through L here, once).
        ``ids=None`` auto-assigns fresh ids; an existing id tombstones its
        old slot and re-lands in the delta (last write wins, also within a
        batch). One call = one version bump = one engine cache flush.
        """
        rows = np.asarray(rows, np.float32)
        if rows.ndim == 1:
            rows = rows[None, :]
        n = rows.shape[0]
        if ids is None:
            ids = np.arange(self._next_id, self._next_id + n,
                            dtype=np.int64)
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        if ids.shape != (n,):
            raise ValueError(f"ids shape {ids.shape} != ({n},)")
        if (ids < 0).any():
            raise ValueError("external ids must be >= 0 (negative ids are "
                             "sentinels)")
        if n == 0:
            return ids
        gp, gn = project_gallery(self.L, jnp.asarray(rows))
        start = len(self.delta_ids)
        self.delta_gp = np.concatenate([self.delta_gp, np.asarray(gp)])
        self.delta_gn = np.concatenate([self.delta_gn, np.asarray(gn)])
        self.delta_ids = np.concatenate([self.delta_ids, ids])
        self.dead_delta = np.concatenate([self.dead_delta,
                                          np.zeros(n, bool)])
        if self.raw_base is not None:
            self.raw_delta = np.concatenate([self.raw_delta, rows])
        for j, e in enumerate(ids.tolist()):
            old = self._loc.get(e)
            if old is not None:
                self._kill(old)
            self._loc[e] = ("delta", start + j)
        self._next_id = max(self._next_id, int(ids.max()) + 1)
        self.n_upserts += n
        self._bump()
        self._maybe_compact()
        return ids

    def delete(self, ids) -> None:
        """Tombstone rows by external id. Unknown ids raise KeyError (and
        the batch is rejected whole); one call = one version bump."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        if len(np.unique(ids)) != len(ids):
            raise ValueError("duplicate ids in delete batch")
        missing = [int(e) for e in ids.tolist() if e not in self._loc]
        if missing:
            raise KeyError(f"ids not in index: {missing[:5]}"
                           f"{'...' if len(missing) > 5 else ''}")
        for e in ids.tolist():
            self._kill(self._loc.pop(int(e)))
        self.n_deletes += len(ids)
        self._bump()
        self._maybe_compact()

    def _kill(self, loc):
        kind, i = loc
        if kind == "base":
            self.dead_base[i] = True
        else:
            self.dead_delta[i] = True

    def _bump(self):
        self.version += 1           # engine LRU flushes on the next search

    def _maybe_compact(self):
        ref = max(self.base.size, 1)
        if ((self.auto_compact_delta
             and self.delta_rows > self.auto_compact_delta * ref)
                or (self.auto_compact_dead
                    and self.tombstones > self.auto_compact_dead * ref)):
            self.compact()

    # -- compaction ----------------------------------------------------------

    def _live_state(self):
        """Live (gp, gn, ids[, raw]) in ascending-external-id order — the
        canonical layout a from-scratch rebuild over live rows would use,
        so positional tie-breaks keep matching external-id tie-breaks."""
        lb = ~self.dead_base
        ld = ~self.dead_delta
        if isinstance(self.base, ExactIndex):
            gp_b, gn_b = (np.asarray(a)[lb] for a in self.base.rows())
        elif isinstance(self.base, IVFPQIndex):
            # the PQ base keeps exact rows in its (host) rerank store,
            # already in base-position order — codes are never decoded
            gp_b = self.base.gp_full[lb]
            gn_b = self.base.gn_full[lb]
        else:
            gp_b, gn_b = self._ivf_live_gp(lb)
        ids = np.concatenate([self.base_ids[lb], self.delta_ids[ld]])
        gp = np.concatenate([gp_b, self.delta_gp[ld]])
        gn = np.concatenate([gn_b, self.delta_gn[ld]])
        order = np.argsort(ids)
        raw = None
        if self.raw_base is not None:
            raw = np.concatenate([self.raw_base[lb], self.raw_delta[ld]])
            raw = raw[order]
        return gp[order], gn[order], ids[order], raw

    def _ivf_live_gp(self, live_mask):
        """Base rows of an IVF index, gathered out of the cluster-major
        padded segments back into base-position order, then masked live."""
        occ = np.asarray(self.base.ids_pad) >= 0
        pos = np.asarray(self.base.ids_pad)[occ]            # base positions
        k = np.asarray(self.base.gp_pad).shape[1]
        gp = np.empty((self.base.size, k), np.float32)
        gn = np.empty((self.base.size,), np.float32)
        gp[pos] = np.asarray(self.base.gp_pad)[occ]
        gn[pos] = np.asarray(self.base.gn_pad)[occ]
        return gp[live_mask], gn[live_mask]

    def compact(self) -> bool:
        """Fold the delta into the base and drop tombstones.

        Exact base: concatenate + re-wrap (no re-projection). IVF base:
        delta rows land in nearest-centroid capacity headroom; if the live
        delta exceeds the total free capacity the fold spills and triggers
        a full rebuild (fresh k-means). IVFPQ base: same headroom fold,
        but each folded row is *encoded* with the existing residual
        codebooks (no PQ retrain — quantization quality can drift if the
        live distribution shifts far from the build-time residuals; a
        spill-triggered rebuild re-trains both k-means and the
        codebooks). Returns True if anything changed.
        """
        if self.delta_rows == 0 and self.tombstones == 0:
            return False
        folded, dropped = self.delta_rows, self.tombstones
        rebuilds_before = self.n_rebuilds
        if isinstance(self.base, IVFPQIndex):
            self._compact_ivfpq()
        elif isinstance(self.base, IVFIndex):
            self._compact_ivf()
        else:
            self._compact_exact()
        self.n_compactions += 1
        self._event("compaction", base=type(self.base).__name__,
                    delta_rows=folded, tombstones=dropped,
                    spill_rebuild=self.n_rebuilds > rebuilds_before,
                    size=self.base.size)
        self._reset_delta()
        self._bump()
        return True

    def _event(self, name: str, **attrs) -> None:
        """Structured lifecycle event onto the adopting engine's registry
        (no-op while unadopted — a bare index carries no obs plumbing)."""
        if self.registry is not None:
            self.registry.event(f"index_{name}", **attrs)
            self.registry.counter(
                "index_lifecycle_total",
                "mutable-index lifecycle transitions by kind",
                labelnames=("event",)).inc(event=name)

    def _reset_delta(self):
        # fresh buffers size off the *current* L, not the old delta_gp:
        # after a rank-changing swap_metric the old buffer's d_out is
        # stale and new upserts (projected at the new rank) must fit
        k = self.L.shape[0]
        self.delta_gp = np.zeros((0, k), np.float32)
        self.delta_gn = np.zeros((0,), np.float32)
        self.delta_ids = np.zeros((0,), np.int64)
        self.dead_delta = np.zeros((0,), bool)
        self.dead_base = np.zeros(self.base.size, bool)
        if self.raw_delta is not None:
            self.raw_delta = np.zeros((0, self.raw_delta.shape[1]),
                                      np.float32)
        self._loc = {int(e): ("base", i)
                     for i, e in enumerate(self.base_ids)}
        self._delta_dev = None
        # _delta_fns survives: the jitted scans are shape-keyed and take
        # the delta arrays as arguments, so steady-state churn re-uses
        # them instead of re-paying a compile after every compaction

    def _compact_exact(self):
        gp, gn, ids, raw = self._live_state()
        self.base = ExactIndex.from_projected(self.L, gp, gn)
        self.base_ids = ids
        if raw is not None:
            self.raw_base = raw

    def _fold_segments(self, clear_dead, place_delta, rebuild, remake):
        """Shared IVF/IVFPQ compaction skeleton (one copy of the
        invariant-bearing bookkeeping; the payload differs per backend).

        Steps: free dead slots, remap kept slots' ids to the new
        ascending-external-id order, spill-check the headroom (falling
        back to a full rebuild), then greedily place each live delta row
        in its nearest centroid with a free slot — the same rule as the
        build's balanced assignment. The callbacks own the payload
        arrays:

          clear_dead(dead_slots)                wipe freed slots
          place_delta(slots, clusters, rows)    write placed delta rows
          rebuild(gp, gn)                       spill path: rebuild
                                                self.base from live rows
          remake(ids_pad, new_ids, lb, live_d, order)
                                                construct the folded base
        """
        base = self.base
        C, cap = base.n_clusters, base.cap
        live_d = np.flatnonzero(~self.dead_delta)
        lb = ~self.dead_base
        ext_live = np.concatenate([self.base_ids[lb],
                                   self.delta_ids[live_d]])
        new_ids = np.sort(ext_live)

        ids_pad = np.asarray(base.ids_pad).copy()
        occ_slots = np.flatnonzero(ids_pad >= 0)
        old_pos = ids_pad[occ_slots]
        keep = lb[old_pos]
        dead_slots = occ_slots[~keep]
        clear_dead(dead_slots)
        ids_pad[dead_slots] = -1
        kept_slots = occ_slots[keep]
        ids_pad[kept_slots] = np.searchsorted(
            new_ids, self.base_ids[old_pos[keep]]).astype(np.int32)

        n_free = C * cap - len(kept_slots)
        if n_free < len(live_d):            # headroom spill -> full rebuild
            gp, gn, ids, raw = self._live_state()
            rebuild(gp, gn)
            self.base_ids = ids
            if raw is not None:
                self.raw_base = raw
            self.n_rebuilds += 1
            self._event("spill_rebuild", free_slots=int(n_free),
                        live_delta=int(len(live_d)))
            return

        # in-place fold: each delta row takes a free slot in its nearest
        # centroid (spilling to the next-nearest with space)
        free = [list(np.flatnonzero(ids_pad[c * cap:(c + 1) * cap] == -1))
                for c in range(C)]
        cent = np.asarray(base.centroids)
        d_dc = (np.sum(self.delta_gp[live_d] ** 2, axis=1)[:, None]
                + np.sum(cent ** 2, axis=1)[None, :]
                - 2.0 * self.delta_gp[live_d] @ cent.T)     # (live, C)
        slots = np.empty(len(live_d), np.int64)
        clusters = np.empty(len(live_d), np.int64)
        for i in range(len(live_d)):
            for c in np.argsort(d_dc[i]):
                if free[c]:
                    slots[i] = c * cap + free[c].pop(0)
                    clusters[i] = c
                    break
        place_delta(slots, clusters, live_d)
        ids_pad[slots] = np.searchsorted(
            new_ids, self.delta_ids[live_d]).astype(np.int32)

        order = np.argsort(ext_live)
        if self.raw_base is not None:
            self.raw_base = np.concatenate(
                [self.raw_base[lb], self.raw_delta[live_d]])[order]
        # remake returns a fresh base instance: the old one's jitted fns
        # close over the old segment arrays and must not be reused
        remake(ids_pad, new_ids, lb, live_d, order)
        self.base_ids = new_ids

    def _rebuild_kwargs(self):
        return {k: v for k, v in self._base_kwargs.items()
                if k in ("iters", "seed", "cap_factor")}

    def _compact_ivf(self):
        """IVF fold: delta rows land full-precision in nearest-centroid
        capacity headroom (see ``_fold_segments``)."""
        base = self.base
        gp_pad = np.asarray(base.gp_pad).copy()
        gn_pad = np.asarray(base.gn_pad).copy()

        def clear_dead(dead_slots):
            gp_pad[dead_slots] = 0.0
            gn_pad[dead_slots] = BIG

        def place_delta(slots, clusters, rows):
            gp_pad[slots] = self.delta_gp[rows]
            gn_pad[slots] = self.delta_gn[rows]

        def rebuild(gp, gn):
            self.base = IVFIndex.build_projected(
                self.L, gp, gn, n_clusters=base.n_clusters,
                nprobe=base.nprobe, scan_impl=base.scan_impl,
                **self._rebuild_kwargs())

        def remake(ids_pad, new_ids, lb, live_d, order):
            self.base = IVFIndex(
                L=base.L, centroids=base.centroids,
                gp_pad=jnp.asarray(gp_pad), gn_pad=jnp.asarray(gn_pad),
                ids_pad=jnp.asarray(ids_pad), cap=base.cap,
                n_clusters=base.n_clusters, nprobe=base.nprobe,
                n_rows=len(new_ids), block_q=base.block_q,
                scan_impl=base.scan_impl)

        self._fold_segments(clear_dead, place_delta, rebuild, remake)

    def _compact_ivfpq(self):
        """IVFPQ fold: each placed delta row is encoded against the
        *existing* codebooks (one batched encode per compaction) and the
        host full-precision store rebuilds in external-id order; a
        headroom spill rebuilds k-means *and* codebooks (see
        ``_fold_segments``)."""
        base = self.base
        codes_pad = np.asarray(base.codes_pad).copy()
        t_pad = np.asarray(base.t_pad).copy()

        def clear_dead(dead_slots):
            codes_pad[dead_slots] = 0
            t_pad[dead_slots] = BIG

        def place_delta(slots, clusters, rows):
            if not len(rows):
                return
            cent = np.asarray(base.centroids)[clusters]
            res = self.delta_gp[rows] - cent
            codes = np.asarray(base.pq.encode(jnp.asarray(res)))
            codes_pad[slots] = codes
            t_pad[slots] = _t_term(base.pq, codes, cent)

        def rebuild(gp, gn):
            self.base = IVFPQIndex.build_projected(
                self.L, gp, gn, n_clusters=base.n_clusters,
                nprobe=base.nprobe, n_subspaces=base.pq.n_subspaces,
                bits=base.pq.bits, rerank_depth=base.rerank_depth,
                store=base.store, scan_impl=base.scan_impl,
                **self._rebuild_kwargs())

        def remake(ids_pad, new_ids, lb, live_d, order):
            gp_full = np.concatenate([base.gp_full[lb],
                                      self.delta_gp[live_d]])[order]
            gn_full = np.concatenate([base.gn_full[lb],
                                      self.delta_gn[live_d]])[order]
            self.base = IVFPQIndex(
                L=base.L, centroids=base.centroids, pq=base.pq,
                codes_pad=jnp.asarray(codes_pad),
                t_pad=jnp.asarray(t_pad), ids_pad=jnp.asarray(ids_pad),
                gp_full=gp_full, gn_full=gn_full, cap=base.cap,
                n_clusters=base.n_clusters, nprobe=base.nprobe,
                n_rows=len(new_ids), rerank_depth=base.rerank_depth,
                store=base.store, scan_impl=base.scan_impl,
                block_q=base.block_q)

        self._fold_segments(clear_dead, place_delta, rebuild, remake)

    # -- metric hot-swap -----------------------------------------------------

    def swap_metric(self, L_new, block_rows: int = 65536) -> None:
        """Re-project the live gallery under a fresh metric factor and swap.

        Requires ``retain_raw=True`` at build. The live raw rows (base +
        delta, tombstones dropped, ascending-external-id order)
        re-project in ``block_rows`` chunks and a replacement base builds
        entirely off to the side — served state is first touched by the
        final flip, so no query ever pays the re-projection or sees a
        half-projected gallery. One version bump at the end flushes the
        engine cache. Closes the trainer -> server loop.

        ``L_new`` may have a *different rank* than the serving factor —
        the retained raw rows make swapping square -> rectangular (or
        back) legal; only ``d_in`` must keep matching the raw feature
        dim. All projected state (base segments, delta buffer) comes
        back sized at the new ``d_out``.

        (The flip itself is a few attribute writes, not one atomic store:
        like ``upsert``/``delete``/``compact``, calls must be serialized
        with in-flight ``topk`` calls by the caller — the engine/batcher
        stack already issues queries from a single worker thread.)
        """
        if self.raw_base is None:
            raise ValueError("swap_metric requires retain_raw=True at "
                             "build (raw features were not kept)")
        scan.check_metric_factor(L_new, self.raw_base.shape[1],
                                 what="L_new")
        L_new = jnp.asarray(L_new, jnp.float32)
        ids = np.concatenate([self.base_ids[~self.dead_base],
                              self.delta_ids[~self.dead_delta]])
        raw = np.concatenate([self.raw_base[~self.dead_base],
                              self.raw_delta[~self.dead_delta]])
        order = np.argsort(ids)
        ids, raw = ids[order], raw[order]
        gps, gns = [], []
        for s in range(0, raw.shape[0], block_rows):
            gp_b, gn_b = project_gallery(L_new,
                                         jnp.asarray(raw[s:s + block_rows]))
            gps.append(np.asarray(gp_b))
            gns.append(np.asarray(gn_b))
        gp = np.concatenate(gps)
        gn = np.concatenate(gns)
        if isinstance(self.base, IVFPQIndex):
            new_base = IVFPQIndex.build_projected(
                L_new, gp, gn, n_clusters=self.base.n_clusters,
                nprobe=self.base.nprobe,
                # a lower-rank L may have fewer projected dims than the
                # old code layout split over; PQ needs n_subspaces <= k
                n_subspaces=min(self.base.pq.n_subspaces,
                                int(L_new.shape[0])),
                bits=self.base.pq.bits,
                rerank_depth=self.base.rerank_depth,
                store=self.base.store, scan_impl=self.base.scan_impl,
                **self._rebuild_kwargs())
        elif isinstance(self.base, IVFIndex):
            new_base = IVFIndex.build_projected(
                L_new, gp, gn, n_clusters=self.base.n_clusters,
                nprobe=self.base.nprobe, scan_impl=self.base.scan_impl,
                **self._rebuild_kwargs())
        else:
            new_base = ExactIndex.from_projected(L_new, gp, gn)
        # the flip: nothing above mutated served state
        self.base = new_base
        self.base_ids = ids
        self.raw_base = raw
        self.L = L_new
        self.n_swaps += 1
        self._event("swap_metric", base=type(new_base).__name__,
                    rows=int(raw.shape[0]), block_rows=block_rows)
        self._reset_delta()
        self._bump()
