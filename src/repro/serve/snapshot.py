"""Index snapshots: npz segments + a manifest, restart without re-projecting.

A serving process that restarts loses its index; rebuilding means
re-projecting the whole gallery through L (and, for IVF, re-running
k-means) before the first query can be answered. Snapshots persist the
*built* device layout instead:

  base.npz      the frozen base index arrays — ExactIndex: L and its
                real rows gp, gn (tiled again on load);
                IVFIndex: L, centroids, gp_pad, gn_pad, ids_pad;
                IVFPQIndex: L, centroids, codebooks, codes_pad, t_pad,
                ids_pad plus the full-precision rerank store
                (gp_full/gn_full);
  mutable.npz   (MutableIndex only) the mutation state: base_ids,
                tombstone masks, the pre-projected delta buffer;
  raw.npz       (MutableIndex with retain_raw) the raw feature rows that
                power ``swap_metric``;
  manifest.json written **last** — a partial snapshot has no manifest and
                ``load_index`` refuses it. Carries the format number, the
                index type, the ``version`` counter, array shapes, scalar
                build parameters, and an L fingerprint (sha256 prefix of
                the f32 factor bytes).

Because the stored arrays are the exact f32 device contents, a loaded
index answers top-k **bit-for-bit** identically to the index that was
saved — the property tests/test_serve_mutable.py pins. The fingerprint
lets a caller holding an L (say, fresh from the trainer) check whether
the snapshot was built under the same metric before serving from it:
``load_index(dir, expect_L=L)`` raises on mismatch (recover by loading
without ``expect_L`` and calling ``swap_metric(L)``).
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.serve.index import ExactIndex
from repro.serve.ivf import IVFIndex
from repro.serve.mutable import MutableIndex
from repro.serve.pq import IVFPQIndex, ProductQuantizer

FORMAT = 1
MANIFEST = "manifest.json"


def l_fingerprint(L) -> str:
    """Stable short id of a metric factor: sha256 of its f32 bytes."""
    a = np.ascontiguousarray(np.asarray(L, np.float32))
    return hashlib.sha256(a.tobytes()).hexdigest()[:16]


def has_snapshot(snapshot_dir: str) -> bool:
    """True iff ``snapshot_dir`` holds a *complete* snapshot — i.e. its
    manifest exists (the manifest is written last, so segments without
    one are an interrupted save and load_index refuses them)."""
    return os.path.isfile(os.path.join(snapshot_dir, MANIFEST))


def _require_unsharded(index):
    if index.n_shards > 1:
        raise NotImplementedError(
            "snapshots cover single-shard indexes only (a sharded index "
            "re-places arrays at build; snapshot the per-host state "
            "instead)")


def _base_payload(index):
    """(arrays dict, meta dict) for a frozen base index."""
    if isinstance(index, ExactIndex):
        gp, gn = index.rows()
        return ({"L": np.asarray(index.L), "gp": np.asarray(gp),
                 "gn": np.asarray(gn)},
                {"base_type": "exact"})
    if isinstance(index, IVFIndex):
        return ({"L": np.asarray(index.L),
                 "centroids": np.asarray(index.centroids),
                 "gp_pad": np.asarray(index.gp_pad),
                 "gn_pad": np.asarray(index.gn_pad),
                 "ids_pad": np.asarray(index.ids_pad)},
                {"base_type": "ivf", "cap": index.cap,
                 "n_clusters": index.n_clusters, "nprobe": index.nprobe,
                 "n_rows": index.n_rows, "block_q": index.block_q,
                 "scan_impl": index.scan_impl})
    if isinstance(index, IVFPQIndex):
        return ({"L": np.asarray(index.L),
                 "centroids": np.asarray(index.centroids),
                 "codebooks": np.asarray(index.pq.codebooks),
                 "codes_pad": np.asarray(index.codes_pad),
                 "t_pad": np.asarray(index.t_pad),
                 "ids_pad": np.asarray(index.ids_pad),
                 "gp_full": np.asarray(index.gp_full),
                 "gn_full": np.asarray(index.gn_full)},
                {"base_type": "ivfpq", "cap": index.cap,
                 "n_clusters": index.n_clusters, "nprobe": index.nprobe,
                 "n_rows": index.n_rows, "block_q": index.block_q,
                 "pq_dim": index.pq.dim,
                 "rerank_depth": index.rerank_depth,
                 "store": index.store, "scan_impl": index.scan_impl})
    raise TypeError(f"cannot snapshot {type(index).__name__}")


def _load_base(path: str, meta: dict):
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    L = jnp.asarray(arrays["L"])
    if meta["base_type"] == "exact":
        return ExactIndex.from_projected(L, arrays["gp"], arrays["gn"])
    if meta["base_type"] == "ivfpq":
        pq = ProductQuantizer(codebooks=jnp.asarray(arrays["codebooks"]),
                              dim=int(meta["pq_dim"]))
        return IVFPQIndex(
            L=L, centroids=jnp.asarray(arrays["centroids"]), pq=pq,
            codes_pad=jnp.asarray(arrays["codes_pad"]),
            t_pad=jnp.asarray(arrays["t_pad"]),
            ids_pad=jnp.asarray(arrays["ids_pad"]),
            gp_full=arrays["gp_full"].astype(np.float32),
            gn_full=arrays["gn_full"].astype(np.float32),
            cap=int(meta["cap"]), n_clusters=int(meta["n_clusters"]),
            nprobe=int(meta["nprobe"]), n_rows=int(meta["n_rows"]),
            rerank_depth=int(meta["rerank_depth"]),
            store=str(meta["store"]), block_q=int(meta["block_q"]),
            scan_impl=str(meta.get("scan_impl", "auto")))
    return IVFIndex(
        L=L, centroids=jnp.asarray(arrays["centroids"]),
        gp_pad=jnp.asarray(arrays["gp_pad"]),
        gn_pad=jnp.asarray(arrays["gn_pad"]),
        ids_pad=jnp.asarray(arrays["ids_pad"]), cap=int(meta["cap"]),
        n_clusters=int(meta["n_clusters"]), nprobe=int(meta["nprobe"]),
        n_rows=int(meta["n_rows"]), block_q=int(meta["block_q"]),
        scan_impl=str(meta.get("scan_impl", "auto")))


def save_index(index, snapshot_dir: str, *, registry=None) -> dict:
    """Persist an ExactIndex / IVFIndex / IVFPQIndex / MutableIndex
    (over any of those bases) to ``snapshot_dir``.

    Writes the npz segments first and the manifest last (its presence
    marks the snapshot complete; re-saving retracts the old manifest
    before touching segments). Returns the manifest dict. ``registry``
    (or the index's own adopting registry) gets an ``index_snapshot_save``
    event.
    """
    _require_unsharded(index)
    os.makedirs(snapshot_dir, exist_ok=True)
    # re-saving over an existing snapshot: retract the old manifest first,
    # so a crash mid-save leaves an (unloadable) incomplete snapshot
    # rather than the old manifest over new partial segments
    stale = os.path.join(snapshot_dir, MANIFEST)
    if os.path.isfile(stale):
        os.remove(stale)
    mutable = isinstance(index, MutableIndex)
    base = index.base if mutable else index
    arrays, base_meta = _base_payload(base)
    np.savez(os.path.join(snapshot_dir, "base.npz"), **arrays)
    segments = {"base": "base.npz"}

    manifest = {
        "format": FORMAT,
        "type": type(index).__name__,
        "version": index.version,
        "l_fingerprint": l_fingerprint(index.L),
        "l_shape": list(np.asarray(index.L).shape),
        "size": index.size,
        "base": base_meta,
        "segments": segments,
    }
    if mutable:
        np.savez(os.path.join(snapshot_dir, "mutable.npz"),
                 base_ids=index.base_ids, dead_base=index.dead_base,
                 delta_gp=index.delta_gp, delta_gn=index.delta_gn,
                 delta_ids=index.delta_ids, dead_delta=index.dead_delta)
        segments["mutable"] = "mutable.npz"
        if index.raw_base is not None:
            np.savez(os.path.join(snapshot_dir, "raw.npz"),
                     raw_base=index.raw_base, raw_delta=index.raw_delta)
            segments["raw"] = "raw.npz"
        manifest["mutable"] = {
            "next_id": index._next_id,
            "n_upserts": index.n_upserts, "n_deletes": index.n_deletes,
            "n_compactions": index.n_compactions,
            "n_rebuilds": index.n_rebuilds, "n_swaps": index.n_swaps,
            "auto_compact_delta": index.auto_compact_delta,
            "auto_compact_dead": index.auto_compact_dead,
            "base_kwargs": index._base_kwargs,
        }

    # manifest last: its presence marks the snapshot complete
    path = os.path.join(snapshot_dir, MANIFEST)
    with open(path + ".tmp", "w") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
    os.replace(path + ".tmp", path)
    _emit(index, registry, "snapshot_save", type=manifest["type"],
          size=manifest["size"], version=manifest["version"],
          dir=snapshot_dir)
    return manifest


def _emit(index, registry, name: str, **attrs) -> None:
    """Structured obs event: the explicit registry wins, else the index's
    adopting registry (the engine attaches one to MutableIndex; frozen
    bases have none — no-op)."""
    registry = (registry if registry is not None
                else getattr(index, "registry", None))
    if registry is not None:
        registry.event(f"index_{name}", **attrs)
        registry.counter(
            "index_lifecycle_total", "index lifecycle transitions",
            labelnames=("event",)).inc(event=name)


def load_index(snapshot_dir: str, *, expect_L=None, registry=None):
    """Reconstruct a saved index; no gallery projection, no k-means.

    Args:
      snapshot_dir: directory written by ``save_index``.
      expect_L: optional metric factor to assert the snapshot was built
        under — a fingerprint mismatch raises ValueError before any
        array loads (callers can then load plain and ``swap_metric``).
      registry: optional obs MetricsRegistry to receive the
        ``index_snapshot_load`` event (a freshly loaded index has no
        adopting engine yet).

    Returns the restored index (same concrete type that was saved, same
    ``version``); its top-k answers are bit-for-bit identical to the
    saved index's. Raises FileNotFoundError on a missing/incomplete
    snapshot and ValueError on a format or fingerprint mismatch.
    """
    path = os.path.join(snapshot_dir, MANIFEST)
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no snapshot manifest at {path} (incomplete or missing "
            f"snapshot)")
    with open(path) as f:
        manifest = json.load(f)
    if manifest["format"] != FORMAT:
        raise ValueError(f"snapshot format {manifest['format']} != "
                         f"supported {FORMAT}")
    if expect_L is not None:
        # shape first: a rank-mismatched factor can never fingerprint-
        # match, and the caller deserves the structural diagnosis (the
        # snapshot was built at a different d_out/d_in), not a generic
        # fingerprint complaint. Older manifests lack l_shape; only the
        # fingerprint gate applies then.
        saved_shape = manifest.get("l_shape")
        expect_shape = list(np.asarray(expect_L).shape)
        if saved_shape is not None and saved_shape != expect_shape:
            raise ValueError(
                f"snapshot metric factor has shape "
                f"{tuple(saved_shape)} but expect_L is "
                f"{tuple(expect_shape)}: rank-mismatched L (the gallery "
                f"was projected at a different (d_out, d_in); load "
                f"without expect_L and swap_metric, or rebuild)")
        got, want = manifest["l_fingerprint"], l_fingerprint(expect_L)
        if got != want:
            raise ValueError(
                f"snapshot metric fingerprint {got} != expected {want}: "
                f"the gallery was projected under a different L (load "
                f"without expect_L and swap_metric, or rebuild)")

    base = _load_base(os.path.join(snapshot_dir, "base.npz"),
                      manifest["base"])
    if manifest["type"] != "MutableIndex":
        base.version = manifest["version"]
        _emit(base, registry, "snapshot_load", type=manifest["type"],
              size=manifest["size"], version=manifest["version"],
              dir=snapshot_dir)
        return base

    with np.load(os.path.join(snapshot_dir, "mutable.npz")) as z:
        mz = {k: z[k] for k in z.files}
    raw_base = raw_delta = None
    if "raw" in manifest["segments"]:
        with np.load(os.path.join(snapshot_dir, "raw.npz")) as z:
            raw_base, raw_delta = z["raw_base"], z["raw_delta"]
    meta = manifest["mutable"]
    mut = MutableIndex(base, base.L, ids=mz["base_ids"], raw=raw_base,
                       base_kwargs=meta["base_kwargs"],
                       auto_compact_delta=meta["auto_compact_delta"],
                       auto_compact_dead=meta["auto_compact_dead"])
    mut.dead_base = mz["dead_base"].astype(bool)
    mut.delta_gp = mz["delta_gp"].astype(np.float32)
    mut.delta_gn = mz["delta_gn"].astype(np.float32)
    mut.delta_ids = mz["delta_ids"].astype(np.int64)
    mut.dead_delta = mz["dead_delta"].astype(bool)
    if raw_delta is not None:
        mut.raw_delta = raw_delta.astype(np.float32)
    mut._loc = {}
    for i, e in enumerate(mut.base_ids.tolist()):
        if not mut.dead_base[i]:
            mut._loc[int(e)] = ("base", i)
    for j, e in enumerate(mut.delta_ids.tolist()):
        if not mut.dead_delta[j]:
            mut._loc[int(e)] = ("delta", j)
    mut._next_id = int(meta["next_id"])
    mut.n_upserts = int(meta["n_upserts"])
    mut.n_deletes = int(meta["n_deletes"])
    mut.n_compactions = int(meta["n_compactions"])
    mut.n_rebuilds = int(meta["n_rebuilds"])
    mut.n_swaps = int(meta["n_swaps"])
    mut.version = manifest["version"]
    _emit(mut, registry, "snapshot_load", type=manifest["type"],
          size=manifest["size"], version=manifest["version"],
          dir=snapshot_dir)
    return mut
