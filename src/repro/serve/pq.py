"""Residual product quantization: uint8 codes + ADC scoring for IVF retrieval.

The IVF scan (serve/ivf.py) is gather-bound: every probe pulls ``cap``
full-precision projected rows (k * 4 bytes each) out of the segment
arrays, which is the LLC/HBM bandwidth cliff the block_q chunking only
softens. This module compresses those rows ~16x so the same byte budget
scans a proportionally larger slice of the gallery — the
exactness-for-bandwidth trade Qian et al. 2015 argue makes high-d
learned-metric retrieval practical at scale:

  * ``ProductQuantizer`` — splits the k-dim *residual* space (row minus
    its IVF centroid) into ``n_subspaces`` contiguous subspaces and
    k-means-quantizes each independently (``2**bits`` codewords, so a row
    encodes to ``n_subspaces`` uint8 codes). Residuals, not raw rows:
    after subtracting the coarse centroid the remaining variance is small
    and near-isotropic, so the same code budget buys far less distortion.
  * ``IVFPQIndex`` — the IVF layout (cluster-major capacity-padded
    segments) with codes instead of rows, scored by **asymmetric distance
    computation** (ADC): the query stays full-precision, and

        ||qp - (c + r̂)||² = ||qp - c||² - 2⟨qp, r̂⟩ + (||r̂||² + 2⟨c, r̂⟩)

    where r̂ is the decoded residual. The first term is the centroid scan
    (already computed to pick probes), the last is a per-row f32 baked at
    encode time (``t_pad``, the 4-byte analogue of ``gn_pad``), and the
    middle splits per subspace into ⟨qp_s, codebook[s, code]⟩ — one
    (n_subspaces, 2**bits) lookup table per query, built once, *independent
    of which clusters are probed* (inner products are linear, so the
    centroid never enters the table). Scanning a segment is then a uint8
    gather plus table lookups: no decode, no k-dim arithmetic per row.
  * optional **exact re-rank** — ADC distances are approximate, so the top
    ``rerank_depth`` ADC candidates re-score against a full-precision row
    store and the top k_top of that exact ordering is returned. The store
    placement is a knob: ``store="device"`` fuses the re-rank into the
    same jit (it gathers only ``rerank_depth`` rows per query, so it never
    re-enters the bandwidth cliff the codes avoid — but the f32 rows stay
    in HBM); ``store="host"`` keeps them in numpy/RAM, trading a
    host-gather round trip per batch for an HBM footprint of just codes —
    the paper-scale-M configuration. With ``nprobe == n_clusters`` and a
    deep enough ``rerank_depth``, the result matches ExactIndex (the
    correctness oracle tests pin); rerank recall is capped by the probed
    clusters' candidate recall, not by quantization error.

Single-shard only: the sharded IVF path re-places arrays at build and the
host-resident rerank store has no mesh story yet (the multi-host gallery
ROADMAP item covers this axis). ``MutableIndex`` can wrap an IVFPQIndex:
delta rows stay full-precision and exact, compaction encodes them into
segment headroom with the *existing* codebooks (serve/mutable.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.metric_topk import metric_sqdist_factored, project_gallery
from repro.kernels.metric_topk.kernel import BIG
from repro.kernels.pq_adc import pq_adc_topk
from repro.serve import scan
from repro.serve.ivf import _balance_assign, _slot_rows, kmeans_projected


@dataclasses.dataclass(eq=False)
class ProductQuantizer:
    """Per-subspace k-means codebooks over a k-dim vector space.

    Attributes:
      codebooks: (n_subspaces, 2**bits, sub_dim) f32 codeword table.
      dim: the un-padded input dimensionality k (``encode``/``decode``
        operate on (N, dim); internally dim zero-pads up to
        ``n_subspaces * sub_dim``, and zero pad columns are
        distance-neutral, the same rule the kernels use).
      bits: code width; codes are uint8, so 1 <= bits <= 8.

    Invariant: ``decode(encode(x))`` is the per-subspace nearest-codeword
    reconstruction — squared error is bounded by the per-subspace k-means
    quantization error, and ADC scoring against the tables from
    ``sqdist_tables``/``ip_tables`` equals decode-then-score exactly
    (up to f32 rounding), which tests/test_serve_pq.py pins.
    """

    codebooks: jax.Array
    dim: int

    @property
    def n_subspaces(self) -> int:
        return self.codebooks.shape[0]

    @property
    def n_codes(self) -> int:
        return self.codebooks.shape[1]

    @property
    def sub_dim(self) -> int:
        return self.codebooks.shape[2]

    @property
    def bits(self) -> int:
        return int(self.n_codes - 1).bit_length() if self.n_codes > 1 else 1

    @property
    def code_bytes(self) -> int:
        """Stored bytes per encoded vector (one uint8 per subspace)."""
        return self.n_subspaces

    @classmethod
    def train(cls, vecs, n_subspaces: int = 8, bits: int = 8, *,
              iters: int = 10, seed: int = 0) -> "ProductQuantizer":
        """Fit per-subspace codebooks on training vectors.

        Args:
          vecs: (N, dim) f32 training set — for the IVF use case, the
            *residuals* of projected gallery rows to their centroids.
          n_subspaces: how many contiguous subspaces dim splits into
            (dim zero-pads up to a multiple; more subspaces = finer
            reconstruction and more code bytes per row).
          bits: log2 codewords per subspace (uint8 codes: 1..8). When N
            < 2**bits the codebook pads by repeating real codewords
            (harmless: encode picks the nearest, duplicates never win
            uniquely).
          iters / seed: Lloyd iterations / PRNG seed per subspace
            (each subspace reuses serve/ivf.py's jit-scanned k-means).

        Returns: the fitted ProductQuantizer.
        """
        if not 1 <= bits <= 8:
            raise ValueError(f"bits must be in 1..8 (uint8 codes), "
                             f"got {bits}")
        vecs = np.asarray(vecs, np.float32)
        if vecs.ndim != 2:
            raise ValueError(f"vecs must be (N, dim), got {vecs.shape}")
        N, dim = vecs.shape
        if N < 1:
            raise ValueError("cannot train on an empty set")
        if n_subspaces < 1 or n_subspaces > dim:
            raise ValueError(f"n_subspaces={n_subspaces} outside 1..{dim}")
        sub = -(-dim // n_subspaces)                       # ceil
        padded = sub * n_subspaces
        if padded != dim:
            vecs = np.pad(vecs, ((0, 0), (0, padded - dim)))
        n_codes = 1 << bits
        books = np.empty((n_subspaces, n_codes, sub), np.float32)
        for s in range(n_subspaces):
            part = jnp.asarray(vecs[:, s * sub:(s + 1) * sub])
            c = min(n_codes, N)
            cent, _, _ = kmeans_projected(part, c, iters=iters,
                                          seed=seed + s)
            cent = np.asarray(cent)
            if c < n_codes:                   # pad by repeating real rows
                cent = cent[np.arange(n_codes) % c]
            books[s] = cent
        return cls(codebooks=jnp.asarray(books), dim=dim)

    def _split(self, vecs):
        """(N, dim) -> (N, n_subspaces, sub_dim), zero-padding dim."""
        vecs = jnp.asarray(vecs, jnp.float32)
        padded = self.n_subspaces * self.sub_dim
        if vecs.shape[1] != self.dim:
            raise ValueError(f"expected dim {self.dim}, got "
                             f"{vecs.shape[1]}")
        if padded != self.dim:
            vecs = jnp.pad(vecs, ((0, 0), (0, padded - self.dim)))
        return vecs.reshape(vecs.shape[0], self.n_subspaces, self.sub_dim)

    def encode(self, vecs, block_rows: int = 16384) -> jax.Array:
        """Quantize (N, dim) vectors to (N, n_subspaces) uint8 codes
        (independent nearest codeword per subspace, ties to the smaller
        code — argmin semantics). Chunked over ``block_rows`` so the
        (block, n_subspaces, 2**bits) distance tensor stays bounded at
        paper-scale N (a build/compaction-time host loop, not a jit
        path)."""
        parts = self._split(vecs)                     # (N, S, sub)
        cn = jnp.sum(jnp.square(self.codebooks), axis=2)    # (S, K)
        out = []
        for s in range(0, parts.shape[0], block_rows):
            blk = parts[s:s + block_rows]
            # ||p-c||^2 = ||p||^2 - 2<p,c> + ||c||^2; ||p||^2 const in c
            cross = jnp.einsum("nsd,skd->nsk", blk, self.codebooks)
            out.append(jnp.argmin(cn[None] - 2.0 * cross,
                                  axis=2).astype(jnp.uint8))
        return jnp.concatenate(out) if len(out) != 1 else out[0]

    def decode(self, codes) -> jax.Array:
        """Reconstruct (N, dim) f32 vectors from (N, n_subspaces) codes
        (the per-subspace codeword concatenation; pad columns sliced
        off)."""
        codes = jnp.asarray(codes)
        gathered = jnp.take_along_axis(
            self.codebooks[None], codes.astype(jnp.int32)[:, :, None, None],
            axis=2)                                   # (N, S, 1, sub)
        out = gathered.reshape(codes.shape[0], -1)
        return out[:, :self.dim]

    def ip_tables(self, q) -> jax.Array:
        """Per-query inner-product lookup tables (Nq, n_subspaces,
        2**bits): entry [i, s, b] = <q_i restricted to subspace s,
        codebook[s, b]>. Linear in q, so for residual ADC the *projected
        query* works directly — the probed centroid never enters the
        table (see the module docstring identity)."""
        return jnp.einsum("nsd,skd->nsk", self._split(q), self.codebooks)

    def sqdist_tables(self, q) -> jax.Array:
        """Per-query squared-distance tables (Nq, n_subspaces, 2**bits):
        entry [i, s, b] = ||q_i|_s - codebook[s, b]||². Summing entries
        at a row's codes gives the symmetric-free ADC distance
        ||q - decode(codes)||² exactly (subspaces are orthogonal
        coordinate blocks)."""
        split = self._split(q)                        # (Nq, S, sub)
        qn = jnp.sum(jnp.square(split), axis=2)       # (Nq, S)
        cn = jnp.sum(jnp.square(self.codebooks), axis=2)
        cross = jnp.einsum("nsd,skd->nsk", split, self.codebooks)
        return qn[:, :, None] + cn[None] - 2.0 * cross

    def adc(self, tables, codes) -> jax.Array:
        """Sum per-subspace table entries at each row's codes.

        Args:
          tables: (Nq, n_subspaces, 2**bits) from ``ip_tables`` or
            ``sqdist_tables``.
          codes: (N, n_subspaces) uint8.

        Returns (Nq, N) f32: tables[i].sum over s at codes[j]. One fused
        gather over a flattened (s, code) index — the scan hot path.
        """
        S, K = self.n_subspaces, self.n_codes
        flat = (jnp.arange(S, dtype=jnp.int32) * K
                + jnp.asarray(codes).astype(jnp.int32))      # (N, S)
        t = tables.reshape(tables.shape[0], S * K)
        picked = jnp.take(t, flat.reshape(-1), axis=1)       # (Nq, N*S)
        return picked.reshape(tables.shape[0], -1, S).sum(axis=2)


# -- the index ---------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class IVFPQIndex:
    """IVF segments over uint8 PQ codes + ADC scan + optional exact rerank.

    MetricIndex backend (serve/index.py protocol). Same cluster-major
    capacity-padded layout as IVFIndex, but segments hold ``code_bytes``
    per row instead of ``4k`` — the gather the scan pays shrinks
    accordingly. ``gp_full``/``gn_full`` keep the full-precision projected
    rows **host-resident** (numpy) for the rerank pass, mutable-gallery
    compaction, and snapshotting; they are never gathered on the ADC path.
    """

    L: jax.Array                    # (k, d) replicated metric factor
    centroids: jax.Array            # (C, k) cluster centers
    pq: ProductQuantizer            # residual codebooks
    codes_pad: jax.Array            # (C*cap, S) uint8; 0 on pad slots
    t_pad: jax.Array                # (C*cap,) ||r̂||²+2⟨c,r̂⟩; BIG on pads
    ids_pad: jax.Array              # (C*cap,) original row ids; -1 on pads
    gp_full: np.ndarray             # (M, k) host copy of the exact rows
    gn_full: np.ndarray             # (M,) their norms
    cap: int                        # per-cluster segment capacity
    n_clusters: int
    nprobe: int                     # default clusters scanned per query
    n_rows: int                     # real (unpadded) gallery size M
    rerank_depth: int = 50          # default exact-rerank pool (0 = off)
    store: str = "device"           # rerank row store: "device" | "host"
    # ADC segment-scan implementation: "auto" (Pallas kernel on TPU, XLA
    # elsewhere), "xla", or "pallas" (kernels/pq_adc; interpret mode off
    # TPU — a correctness tool, not a serving path)
    scan_impl: str = "auto"
    # query chunk for the segment gather; 4x the IVF default because the
    # gathered code blocks are ~16x smaller than full-precision rows, so
    # bigger chunks stay cache-sized and amortize per-block overhead
    block_q: int = 64
    version: int = 0
    # device mirror of (gp_full, gn_full) when store == "device"
    _dev_store: Optional[tuple] = dataclasses.field(default=None,
                                                    repr=False)
    _fns: dict = dataclasses.field(default_factory=dict, repr=False)

    @classmethod
    def build(cls, L, gallery, n_clusters: int = 64, nprobe: int = 8, *,
              n_subspaces: int = 8, bits: int = 8, rerank_depth: int = 50,
              store: str = "device", scan_impl: str = "auto",
              iters: int = 10, seed: int = 0,
              cap_factor: float = 1.25, mesh=None,
              rules=None) -> "IVFPQIndex":
        """Project the gallery, cluster, train PQ on residuals, encode.

        Args:
          L: (k, d) metric factor; gallery: (M, d) raw rows.
          n_clusters / nprobe / iters / seed / cap_factor: the IVF coarse
            quantizer knobs (see IVFIndex.build).
          n_subspaces / bits: PQ shape — ``n_subspaces`` uint8 codes per
            row, ``2**bits`` codewords per subspace. Code bytes per row =
            n_subspaces (vs 4k full precision).
          rerank_depth: default exact-rerank pool per query (0 disables;
            overridable per topk call).
          store: where the full-precision rerank rows live — "device"
            (fused in-jit rerank, f32 rows stay in HBM) or "host" (RAM
            only; a host gather round trip per reranked batch).
          scan_impl: default ADC segment-scan implementation — "auto"
            (kernels/pq_adc fused Pallas kernel on TPU, XLA elsewhere),
            "xla", or "pallas" (overridable per topk call).
          mesh/rules: accepted for API symmetry; a multi-device mesh
            raises (single-shard backend, see module docstring).

        Returns the built index.
        """
        gp, gn = project_gallery(L, gallery)
        return cls.build_projected(
            L, gp, gn, n_clusters=n_clusters, nprobe=nprobe,
            n_subspaces=n_subspaces, bits=bits, rerank_depth=rerank_depth,
            store=store, scan_impl=scan_impl, iters=iters, seed=seed,
            cap_factor=cap_factor, mesh=mesh, rules=rules)

    @classmethod
    def build_projected(cls, L, gp, gn, n_clusters: int = 64,
                        nprobe: int = 8, *, n_subspaces: int = 8,
                        bits: int = 8, rerank_depth: int = 50,
                        store: str = "device", scan_impl: str = "auto",
                        iters: int = 10,
                        seed: int = 0, cap_factor: float = 1.25,
                        pq_train_rows: int = 20_000, mesh=None,
                        rules=None) -> "IVFPQIndex":
        """Cluster + encode already-projected rows (gp (M,k), gn (M,)).

        Mutable-gallery compaction rebuilds and metric hot-swap
        (serve/mutable.py) enter here — they hold projected rows already.
        Same layout contract as IVFIndex.build_projected; additionally
        trains the residual ProductQuantizer and encodes every row.
        ``pq_train_rows`` bounds the codebook training set (a seeded
        subsample of the residuals — with <= 2**bits codewords per small
        subspace, tens of thousands of rows saturate the fit and training
        on all of paper-scale M would only slow the build).
        """
        if store not in ("device", "host"):
            raise ValueError(f"unknown store {store!r} (device|host)")
        if scan_impl not in scan.SCAN_IMPLS:
            raise ValueError(f"unknown scan_impl {scan_impl!r} "
                             f"({'|'.join(scan.SCAN_IMPLS)})")
        if mesh is not None and scan.n_shards(
                mesh, scan.gallery_axes(mesh, None, rules)) > 1:
            raise NotImplementedError(
                "IVFPQIndex is single-shard (the rerank row store has no "
                "mesh story; multi-host gallery is a ROADMAP item)")
        scan.check_metric_factor(L)
        gp = jnp.asarray(gp, jnp.float32)
        gn = jnp.asarray(gn, jnp.float32)
        M, k = gp.shape
        if k != jnp.shape(L)[0]:
            raise ValueError(
                f"projected rows have dim {k} but L is "
                f"{tuple(jnp.shape(L))}; gp must be sized d_out")
        C = n_clusters
        if C > M:
            raise ValueError(f"n_clusters={C} > gallery size {M}")
        centroids, assign, _ = kmeans_projected(gp, C, iters=iters,
                                                seed=seed)
        gp_np = np.asarray(gp)
        cap = int(-((-max(cap_factor, 1.0) * M) // C))      # ceil
        cap = ((cap + 7) // 8) * 8
        assign = _balance_assign(gp_np, np.asarray(centroids),
                                 np.asarray(assign), cap)

        cent_np = np.asarray(centroids)
        residuals = gp_np - cent_np[assign]
        train = residuals
        if 0 < pq_train_rows < M:
            sel = np.random.RandomState(seed).choice(M, pq_train_rows,
                                                     replace=False)
            train = residuals[sel]
        pq = ProductQuantizer.train(train, n_subspaces=n_subspaces,
                                    bits=bits, iters=iters, seed=seed)
        codes = np.asarray(pq.encode(jnp.asarray(residuals)))
        t = _t_term(pq, codes, cent_np[assign])

        ids_pad = _slot_rows(assign, C, cap)                # cluster-major
        real = np.flatnonzero(ids_pad >= 0)
        codes_pad = np.zeros((C * cap, pq.n_subspaces), np.uint8)
        t_pad = np.full((C * cap,), BIG, np.float32)
        codes_pad[real] = codes[ids_pad[real]]
        t_pad[real] = t[ids_pad[real]]

        return cls(L=jnp.asarray(L, jnp.float32), centroids=centroids,
                   pq=pq, codes_pad=jnp.asarray(codes_pad),
                   t_pad=jnp.asarray(t_pad), ids_pad=jnp.asarray(ids_pad),
                   gp_full=gp_np, gn_full=np.asarray(gn), cap=cap,
                   n_clusters=C, nprobe=min(nprobe, C), n_rows=M,
                   rerank_depth=rerank_depth, store=store,
                   scan_impl=scan_impl)

    # -- MetricIndex surface -------------------------------------------------

    @property
    def size(self) -> int:
        """Real (unpadded) gallery rows."""
        return self.n_rows

    @property
    def n_shards(self) -> int:
        return 1

    @property
    def code_bytes_per_row(self) -> int:
        """Device bytes gathered per scanned row: uint8 codes + the f32
        ``t`` term (vs ``4k + 4`` for the full-precision IVF segment)."""
        return self.pq.code_bytes + 4

    @property
    def compression_ratio(self) -> float:
        """Full-precision segment bytes / PQ segment bytes per row."""
        k = self.gp_full.shape[1]
        return (4 * k + 4) / self.code_bytes_per_row

    def topk(self, queries, k_top: int, backend: str = "xla",
             nprobe: Optional[int] = None,
             rerank: Optional[int] = None,
             scan_impl: Optional[str] = None
             ) -> Tuple[jax.Array, jax.Array]:
        """(dists (Nq, k_top) ascending, global row ids (Nq, k_top)).

        Args:
          queries: (Nq, d) raw queries (projected through L here).
          k_top: neighbors per query (<= size).
          backend: "xla" only (no sharded path; the fused ADC kernel is
            the ``scan_impl`` knob, not an engine backend).
          nprobe: clusters scanned (defaults to the build setting;
            ``n_clusters`` scans everything).
          rerank: exact-rerank pool (defaults to build ``rerank_depth``;
            0 returns raw ADC distances, > 0 re-scores that many ADC
            candidates against the full-precision row store — device or
            host per ``store`` — and returns exact distances for the
            survivors).
          scan_impl: ADC segment-scan implementation for this call —
            "auto" / "xla" / "pallas" (defaults to the build setting;
            see scan.resolve_scan_impl). The pallas path returns
            bit-identical results to the xla path.

        Invariants: with rerank on, returned distances are exact squared
        metric distances for the returned ids. Ids match ExactIndex when
        ``nprobe == n_clusters`` *and* the rerank pool is deep enough
        that the true top-k survives ADC preselection — quantization can
        mis-rank a true neighbor below the ADC top-``rerank``, so only
        ``rerank == size`` guarantees equality (the tests' oracle);
        shallower pools trade that tail recall for speed. -1 ids can
        appear only when the probed clusters hold fewer than k_top real
        rows.
        """
        if backend != "xla":
            raise NotImplementedError(
                "IVFPQIndex only supports the xla backend")
        if k_top > self.size:
            raise ValueError(f"k_top={k_top} > gallery size {self.size}")
        # `is None`, not truthiness: `nprobe or default` would silently
        # map an explicit nprobe=0 to the default (the k_top=0 bug class)
        np_ = self.nprobe if nprobe is None else nprobe
        if np_ < 1:
            raise ValueError(f"nprobe must be >= 1, got {np_}")
        np_ = min(np_, self.n_clusters)
        rr = self.rerank_depth if rerank is None else rerank
        rr = min(rr, np_ * self.cap)
        if rr:
            rr = max(rr, k_top)
        if max(k_top, rr) > np_ * self.cap:
            raise ValueError(
                f"k_top={k_top} > nprobe*cap={np_ * self.cap} scanned "
                f"rows per query; raise nprobe")
        impl = scan.resolve_scan_impl(self.scan_impl, scan_impl)
        queries = jnp.asarray(queries, jnp.float32)
        fused = rr > 0 and self.store == "device"
        key = (k_top, np_, rr, fused, impl)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = self._build_topk(k_top, np_, rr, fused,
                                                   impl)
        if fused or rr == 0:
            return fn(queries)
        # host store: two-phase rerank (the scan fn hands back the
        # projected queries so the rerank pass doesn't re-project)
        adc_d, adc_i, qp = fn(queries)
        return self._rerank_host(qp, adc_i, k_top)

    # -- ADC scan (+ fused device rerank) ------------------------------------

    def _device_store(self):
        """Lazy device mirror of the full-precision rows (store="device")."""
        if self._dev_store is None:
            self._dev_store = (jnp.asarray(self.gp_full),
                               jnp.asarray(self.gn_full))
        return self._dev_store

    def _build_topk(self, k_top: int, nprobe: int, rr: int, fused: bool,
                    impl: str):
        """Jitted query fn for one (k_top, nprobe, rerank, store, impl)
        combo.

        ``fused`` appends the device-store exact rerank inside the same
        jit; otherwise the fn returns the top max(k_top, rr) ADC
        candidates — plus the projected queries when rr > 0, for the
        host-store rerank phase that follows. ``impl`` is the resolved
        segment-scan implementation ("xla" | "pallas"); both route
        through kernels/pq_adc and return bit-identical results.
        """
        C, cap = self.n_clusters, self.cap
        S, K = self.pq.n_subspaces, self.pq.n_codes
        block_q = self.block_q
        kk = max(k_top, rr)
        store = self._device_store() if fused else (None, None)

        # index arrays ride in as arguments: a jit that closed over them
        # would bake the codes and the device store into the program as
        # constants
        @jax.jit
        def run(queries, L, centroids, codes, t, ids, gp_dev, gn_dev):
            qp = scan.project_queries(L, queries)
            cd = metric_sqdist_factored(qp, centroids)
            neg, probes = jax.lax.top_k(-cd, nprobe)
            tables = self.pq.ip_tables(qp).reshape(qp.shape[0], S * K)
            d, i = pq_adc_topk(tables, -neg, probes,
                               codes.reshape(C, cap, S),
                               t.reshape(C, cap), ids.reshape(C, cap),
                               kk=kk, block_q=block_q,
                               use_kernel=(impl == "pallas"))
            if not fused:
                return (d, i, qp) if rr > 0 else (d, i)
            # fused exact rerank: gather only kk full-precision rows per
            # query from the device store (never re-entering the segment
            # gather the codes avoided) and re-sort by exact distance
            safe = jnp.maximum(i, 0)
            rows = jnp.take(gp_dev, safe, axis=0)        # (Nq, kk, k)
            norms = jnp.where(i >= 0, jnp.take(gn_dev, safe, axis=0), BIG)
            return _exact_rerank(qp, rows, norms, i, k_top)

        return lambda queries: run(queries, self.L, self.centroids,
                                   self.codes_pad, self.t_pad, self.ids_pad,
                                   *store)

    # -- host-store exact re-rank --------------------------------------------

    def _rerank_host(self, qp, cand_ids, k_top: int):
        """Re-score ADC candidates against the host full-precision rows.

        ``qp`` is the already-projected query batch (computed once by the
        scan jit). The candidate gather runs in numpy (host RAM — the
        point of ``store="host"`` is keeping the f32 rows out of device
        memory), then one jitted exact-distance + merge pass runs on
        device with static shapes. Costs a device->host->device round
        trip per batch; ``store="device"`` fuses the same math into the
        scan jit instead.

        Sentinel candidates (-1 ids from under-filled probes) keep their
        id and a BIG distance, so they sort last and surface only when
        fewer than k_top real candidates exist — the same convention as
        IVFIndex.
        """
        ci = np.asarray(cand_ids)
        safe = np.where(ci >= 0, ci, 0)
        rows = jnp.asarray(self.gp_full[safe])        # (Nq, rr, k)
        norms = jnp.asarray(
            np.where(ci >= 0, self.gn_full[safe], BIG).astype(np.float32))
        key = ("rerank_host", ci.shape[1], k_top)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = jax.jit(
                lambda qp, rows, norms, ids:
                _exact_rerank(qp, rows, norms, ids, k_top))
        return fn(qp, rows, norms, jnp.asarray(ci))

    def probe_stats(self, queries, nprobe: Optional[int] = None):
        """Diagnostic: (probes (Nq, nprobe), centroid dists) for a batch —
        which segments a query would scan. Host helper for docs/tests."""
        qp = scan.project_queries(self.L, jnp.asarray(queries, jnp.float32))
        cd = metric_sqdist_factored(qp, self.centroids)
        np_ = self.nprobe if nprobe is None else nprobe
        np_ = min(np_, self.n_clusters)
        neg, probes = jax.lax.top_k(-cd, np_)
        return np.asarray(probes), np.asarray(-neg)


def _exact_rerank(qp, rows, norms, ids, k_top: int):
    """Exact (projected-space) rescore of gathered candidate rows.

    qp (Nq, k) projected queries; rows (Nq, R, k) candidate rows; norms
    (Nq, R) their norms with BIG on -1 sentinels; ids (Nq, R). Returns
    the (distance, id)-merged exact top k_top — the same deterministic
    select (scan.topk_by_distance) every other backend ends on.
    """
    cross = jnp.einsum("qrk,qk->qr", rows, qp)
    qn = jnp.sum(jnp.square(qp), axis=1)
    d = jnp.maximum(qn[:, None] + norms - 2.0 * cross, 0.0)
    d = jnp.where(ids < 0, BIG, d)
    return scan.topk_by_distance(d, ids, k_top)


def _t_term(pq: ProductQuantizer, codes: np.ndarray,
            cents: np.ndarray) -> np.ndarray:
    """Per-row additive ADC term ||r̂||² + 2⟨c, r̂⟩ (f32 (N,)).

    ``codes`` (N, S) uint8, ``cents`` (N, k) the row's own centroid. Baked
    at encode time so the scan never touches the decoded residual.
    """
    dec = np.asarray(pq.decode(jnp.asarray(codes)))
    return (np.sum(dec * dec, axis=1)
            + 2.0 * np.sum(cents * dec, axis=1)).astype(np.float32)
