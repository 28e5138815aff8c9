"""Synthetic pair-constraint datasets mirroring the paper's setup (§5.1).

The paper samples similar pairs (same class) and dissimilar pairs (different
class) from labeled image features (MNIST pixels / ImageNet LLC). Offline we
generate class-structured feature clouds of matching dimensionality:

  * ``class_blobs``     — Gaussian blobs around random class centers (fast,
                          used by unit/integration tests).
  * ``mnist_like``      — 780-dim, 10-class cloud with pixel-like sparsity and
                          [0,1] range so the MNIST-scale experiments are
                          shape/scale faithful.
  * ``llc_like``        — high-dim sparse nonnegative features mimicking LLC
                          codes (ImageNet-63K / ImageNet-1M configs).

Pair sampling matches the paper: uniform over same-class pairs for S, over
different-class pairs for D.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.loader import partition_pairs
from repro.obs import annotate


@dataclasses.dataclass(frozen=True)
class PairDatasetConfig:
    n_samples: int
    feat_dim: int
    n_classes: int
    kind: str = "class_blobs"       # class_blobs | mnist_like | llc_like
    noise: float = 0.3
    sparsity: float = 0.9           # fraction of zero dims (llc_like)
    seed: int = 0


def make_features(cfg: PairDatasetConfig) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (features (n, d) float32, labels (n,) int32)."""
    rng = np.random.RandomState(cfg.seed)
    labels = rng.randint(0, cfg.n_classes, size=cfg.n_samples).astype(np.int32)
    centers = rng.randn(cfg.n_classes, cfg.feat_dim).astype(np.float32)
    if cfg.kind == "class_blobs":
        x = centers[labels] + cfg.noise * rng.randn(
            cfg.n_samples, cfg.feat_dim).astype(np.float32)
    elif cfg.kind == "mnist_like":
        # pixel-ish: nonnegative, bounded, with class-dependent active masks
        masks = (rng.rand(cfg.n_classes, cfg.feat_dim) < 0.25)
        base = np.abs(centers)
        x = (base[labels] * masks[labels]).astype(np.float32)
        x += 0.1 * np.abs(rng.randn(cfg.n_samples, cfg.feat_dim)).astype(np.float32)
        x = np.clip(x / (x.max() + 1e-6), 0.0, 1.0)
    elif cfg.kind == "noisy_subspace":
        # class signal lives in a small subspace; the remaining dims carry
        # high-variance noise that dominates Euclidean distance — the
        # canonical case where a learned Mahalanobis metric matters
        s = max(4, cfg.feat_dim // 8)
        sig_centers = rng.randn(cfg.n_classes, s).astype(np.float32)
        x = np.empty((cfg.n_samples, cfg.feat_dim), np.float32)
        x[:, :s] = sig_centers[labels] + cfg.noise * rng.randn(
            cfg.n_samples, s).astype(np.float32)
        x[:, s:] = 3.0 * rng.randn(
            cfg.n_samples, cfg.feat_dim - s).astype(np.float32)
    elif cfg.kind == "llc_like":
        # sparse nonnegative codes: class-specific support + magnitude noise
        masks = (rng.rand(cfg.n_classes, cfg.feat_dim) < (1.0 - cfg.sparsity))
        mags = np.abs(centers)
        x = (mags[labels] * masks[labels]).astype(np.float32)
        x += cfg.noise * np.abs(
            rng.randn(cfg.n_samples, cfg.feat_dim)).astype(np.float32) * masks[labels]
    else:
        raise ValueError(f"unknown kind {cfg.kind}")
    return x, labels


def llc_like_chunk(cfg: PairDatasetConfig, chunk: int, rows: int):
    """One chunk of an ``llc_like`` feature stream, made on the device.

    Same class structure as ``make_features``' llc_like — per-class
    supports of density ``1 - sparsity``, |N(0,1)| class magnitudes,
    ``noise * |N(0,1)|`` row noise inside the support — drawn with
    jax.random from ``cfg.seed``. A chunk depends only on (seed, chunk,
    rows), so a stream of any length (the paper's 1M x 21,504 is 86 GB)
    is made chunk by chunk without ever being held whole, and disjoint
    chunk ids give disjoint rows of the same distribution. Returns
    (x (rows, feat_dim) f32, labels (rows,) int32) on the default device.
    """
    if cfg.kind != "llc_like":
        raise ValueError(f"llc_like_chunk makes llc_like rows, not "
                         f"{cfg.kind!r}")
    return _llc_chunk(cfg.seed, chunk, cfg.sparsity, cfg.noise, rows=rows,
                      n_classes=cfg.n_classes, feat_dim=cfg.feat_dim)


@functools.partial(jax.jit, static_argnames=("rows", "n_classes",
                                             "feat_dim"))
def _llc_chunk(seed, chunk, sparsity, noise, *, rows: int, n_classes: int,
               feat_dim: int):
    k_mag, k_sup, k_rows = jax.random.split(jax.random.PRNGKey(seed), 3)
    mags = jnp.abs(jax.random.normal(k_mag, (n_classes, feat_dim)))
    support = jax.random.uniform(k_sup, (n_classes, feat_dim)) < 1 - sparsity
    k_lab, k_noise = jax.random.split(jax.random.fold_in(k_rows, chunk))
    labels = jax.random.randint(k_lab, (rows,), 0, n_classes)
    row_noise = noise * jnp.abs(jax.random.normal(k_noise, (rows, feat_dim)))
    x = jnp.where(support[labels], mags[labels] + row_noise, 0.0)
    return x, labels.astype(jnp.int32)


def _draw_pair_indices(rng, labels: np.ndarray, n_pairs: int,
                       want_same: bool, dedup: bool = True):
    """Rejection-sample (a, b) index pairs of the requested kind.

    Self-pairs (a == b) are always masked — they carry zero gradient for
    similar constraints and are label-inconsistent for dissimilar ones.
    With ``dedup`` (default), duplicate constraints within the draw are
    dropped too, treating (a, b) and (b, a) as the same unordered
    constraint, so every returned pair is distinct.
    """
    n = labels.shape[0]
    a = np.empty(n_pairs, np.int64)
    b = np.empty(n_pairs, np.int64)
    # canonical min*n+max keys taken so far, kept SORTED: membership is
    # then a searchsorted per round instead of np.isin's full re-sort of
    # the accumulated set (which goes quadratic-ish at the paper's
    # 200M-pair scale), and the merge below is a linear memcpy
    seen = np.empty(0, np.int64)
    filled = 0
    stalled = 0
    grow = 1        # oversample factor; doubles when a round finds nothing
                    # fresh (coupon-collector tail near pool exhaustion)
    while filled < n_pairs:
        m = min(max(2 * (n_pairs - filled) * grow, 64), 1 << 22)
        ca = rng.randint(0, n, size=m)
        cb = rng.randint(0, n, size=m)
        same = labels[ca] == labels[cb]
        keep = (same if want_same else ~same) & (ca != cb)
        ca, cb = ca[keep], cb[keep]
        if dedup and len(ca):
            key = np.minimum(ca, cb) * n + np.maximum(ca, cb)
            _, first = np.unique(key, return_index=True)
            first.sort()               # keep draw order (determinism)
            ca, cb, key = ca[first], cb[first], key[first]
            pos = np.searchsorted(seen, key)
            found = np.zeros(len(key), bool)
            inside = pos < len(seen)
            found[inside] = seen[pos[inside]] == key[inside]
            ca, cb, key = ca[~found], cb[~found], key[~found]
            take = min(len(ca), n_pairs - filled)
            new = np.sort(key[:take])
            seen = np.insert(seen, np.searchsorted(seen, new), new)
        k = min(len(ca), n_pairs - filled)
        a[filled:filled + k] = ca[:k]
        b[filled:filled + k] = cb[:k]
        filled += k
        if k == 0:
            stalled += 1
            grow = min(grow * 2, 1 << 16)
        else:
            stalled = 0
        if stalled >= 64:
            raise ValueError(
                f"could not draw {n_pairs} distinct "
                f"{'similar' if want_same else 'dissimilar'} pairs from "
                f"{n} rows (only {filled} exist under the labeling)")
    return a, b


def sample_pairs(features: np.ndarray, labels: np.ndarray, n_similar: int,
                 n_dissimilar: int, seed: int = 0, dedup: bool = True):
    """Sample S and D as in the paper: same class -> similar, else dissimilar.

    Returns dict(xs, ys, sim) with xs/ys (n_s+n_d, d), sim in {1, 0}.
    Self-pairs are masked and (with ``dedup``) each unordered constraint
    appears at most once per set.
    """
    rng = np.random.RandomState(seed)
    sa, sb = _draw_pair_indices(rng, labels, n_similar, True, dedup)
    da, db = _draw_pair_indices(rng, labels, n_dissimilar, False, dedup)
    xs = np.concatenate([features[sa], features[da]], axis=0)
    ys = np.concatenate([features[sb], features[db]], axis=0)
    sim = np.concatenate([np.ones(n_similar, np.int32),
                          np.zeros(n_dissimilar, np.int32)])
    perm = rng.permutation(xs.shape[0])
    return {"xs": xs[perm], "ys": ys[perm], "sim": sim[perm]}


def sample_pair_indices(labels: np.ndarray, n_similar: int,
                        n_dissimilar: int, seed: int = 0,
                        dedup: bool = True):
    """Index-only pair sampling: returns dict(a, b, sim) of int arrays.

    O(n_pairs) memory instead of O(n_pairs * d) — at web scale (the paper's
    200M pairs) pairs are always stored as indices into the feature store.
    Self-pairs are masked and (with ``dedup``) each unordered constraint
    appears at most once per set.
    """
    rng = np.random.RandomState(seed)
    sa, sb = _draw_pair_indices(rng, labels, n_similar, True, dedup)
    da, db = _draw_pair_indices(rng, labels, n_dissimilar, False, dedup)
    a = np.concatenate([sa, da])
    b = np.concatenate([sb, db])
    sim = np.concatenate([np.ones(n_similar, np.int32),
                          np.zeros(n_dissimilar, np.int32)])
    perm = rng.permutation(a.shape[0])
    return {"a": a[perm], "b": b[perm], "sim": sim[perm]}


def distinct_draws(rng, n_pool: int, size: int) -> np.ndarray:
    """``size`` distinct uniform draws from range(n_pool), O(size) expected
    when size << n_pool (rng.choice(replace=False) permutes the whole pool,
    which at the paper's 200M-pair scale is O(pool) per batch). Falls back
    to replacement draws only when the pool is smaller than the batch."""
    if size > n_pool:
        return rng.randint(0, n_pool, size)
    if 4 * size >= n_pool:              # dense: permutation is cheapest
        return rng.permutation(n_pool)[:size]
    out = np.unique(rng.randint(0, n_pool, size))
    while len(out) < size:
        out = np.union1d(out, rng.randint(0, n_pool, 2 * (size - len(out))))
    return out[rng.permutation(len(out))[:size]]


def _batch_draws(sim: np.ndarray, batch_size: int, seed: int,
                 balanced: bool) -> Iterator[np.ndarray]:
    """Endless per-batch pair selections over labels ``sim``: half S and
    half D when ``balanced`` and both exist, else uniform; distinct within
    a batch. Each draw is the trainer's ``train.draw`` profiler span."""
    rng = np.random.RandomState(seed)
    sim_idx = np.nonzero(sim == 1)[0]
    dis_idx = np.nonzero(sim == 0)[0]
    n = sim.shape[0]
    while True:
        with annotate("train.draw"):
            if balanced and len(sim_idx) and len(dis_idx):
                h = batch_size // 2
                sel = np.concatenate([
                    sim_idx[distinct_draws(rng, len(sim_idx), h)],
                    dis_idx[distinct_draws(rng, len(dis_idx),
                                            batch_size - h)]])
            else:
                sel = distinct_draws(rng, n, batch_size)
        yield sel


def pair_batches_from_indices(features, idx_pairs: dict, batch_size: int,
                              seed: int = 0,
                              balanced: bool = True) -> Iterator[dict]:
    """Minibatch stream gathering features on the fly (memory-bounded).
    Constraints within a batch are distinct (no duplicated pair rows).

    On a device store (a ``jax.Array``) each batch is one call of the
    compiled ``pair_rows``, which indexes in int32; a host store is
    gathered in numpy and the batch's rows are sent to the device."""
    if isinstance(features, jax.Array):
        if features.shape[0] > np.iinfo(np.int32).max:
            raise ValueError(
                f"a device store of {features.shape[0]} rows cannot be "
                f"indexed in int32; keep it under 2**31 rows")
        gather = _gather_on_device
    else:
        gather = _gather_indexed
    return (gather(features, idx_pairs, sel)
            for sel in _batch_draws(idx_pairs["sim"], batch_size, seed,
                                    balanced))


@jax.jit
def pair_rows(features, idx):
    """One batch from a device store: ``idx`` is int32 (3, B), the pairs'
    ``a`` and ``b`` rows and their ``sim`` labels. Returns ``xs``, ``ys``
    (B, d) and ``sim`` (B,) int32."""
    return {"xs": features[idx[0]], "ys": features[idx[1]], "sim": idx[2]}


def _gather_on_device(features, idx_pairs: dict, sel: np.ndarray) -> dict:
    """As ``_gather_indexed``, on a device store: the drawn pairs go to the
    device as one small int32 array and ``pair_rows`` gathers the batch."""
    with annotate("train.gather"):
        return pair_rows(features, np.stack(
            [idx_pairs[k][sel] for k in ("a", "b", "sim")]).astype(np.int32))


def _gather_indexed(features, idx_pairs: dict, sel: np.ndarray) -> dict:
    """One batch's feature gathers from a host store, the ``train.gather``
    profiler span. The batch goes straight to the stream's ``yield``: a
    generator local would keep it alive while the next batch is
    gathered."""
    with annotate("train.gather"):
        return {"xs": jnp.asarray(features[idx_pairs["a"][sel]]),
                "ys": jnp.asarray(features[idx_pairs["b"][sel]]),
                "sim": jnp.asarray(idx_pairs["sim"][sel])}


class IndexPairSource:
    """A trainer pair source over a feature store and index pairs.

    At the paper's scale pairs are kept as indices into the features
    (``sample_pair_indices``) and gathered per batch. ``worker_streams``
    is the pluggable-source contract of ``core/ps/trainer``: it
    partitions the index pairs over workers (paper §4.1) and streams each
    shard through ``pair_batches_from_indices``. ``features`` may be a
    device array; each batch is then one gather program on the device.
    """

    def __init__(self, features, idx_pairs: dict):
        self.features = features
        self.idx_pairs = idx_pairs

    def worker_streams(self, n_workers: int, batch_size: int, seed: int):
        return [pair_batches_from_indices(self.features, shard, batch_size,
                                          seed=seed + i)
                for i, shard in enumerate(
                    partition_pairs(self.idx_pairs, n_workers))]


def pair_batches(pairs: dict, batch_size: int, seed: int = 0,
                 balanced: bool = True) -> Iterator[dict]:
    """Infinite minibatch stream. ``balanced`` draws half S / half D per batch
    as in the paper's experimental setup (§5.2). Constraints within a batch
    are distinct (no duplicated pair rows)."""
    for idx in _batch_draws(pairs["sim"], batch_size, seed, balanced):
        yield _gather_rows(pairs, idx)


def _gather_rows(pairs: dict, idx: np.ndarray) -> dict:
    """As ``_gather_indexed``, from a pair dict that holds the rows."""
    with annotate("train.gather"):
        return {k: jnp.asarray(v[idx]) for k, v in pairs.items()}


def train_eval_split(cfg: PairDatasetConfig, n_train_sim: int, n_train_dis: int,
                     n_eval_sim: int, n_eval_dis: int):
    """Features + disjoint train/eval pair sets (paper's held-out pair eval)."""
    x, y = make_features(cfg)
    n_hold = max(cfg.n_samples // 5, 2 * cfg.n_classes)
    train_x, train_y = x[:-n_hold], y[:-n_hold]
    hold_x, hold_y = x[-n_hold:], y[-n_hold:]
    train_pairs = sample_pairs(train_x, train_y, n_train_sim, n_train_dis,
                               seed=cfg.seed + 1)
    eval_pairs = sample_pairs(hold_x, hold_y, n_eval_sim, n_eval_dis,
                              seed=cfg.seed + 2)
    return train_pairs, eval_pairs


def sample_triplet_indices(labels: np.ndarray, n_triplets: int,
                           seed: int = 0):
    """(anchor, positive, negative) index triples — the paper's §4
    triple-wise constraint extension ("i is more similar to j than to k")."""
    rng = np.random.RandomState(seed)
    n = labels.shape[0]
    a = np.empty(n_triplets, np.int64)
    p = np.empty(n_triplets, np.int64)
    ng = np.empty(n_triplets, np.int64)
    filled = 0
    while filled < n_triplets:
        ca = rng.randint(0, n, size=2 * (n_triplets - filled))
        cp = rng.randint(0, n, size=2 * (n_triplets - filled))
        cn = rng.randint(0, n, size=2 * (n_triplets - filled))
        keep = ((labels[ca] == labels[cp]) & (labels[ca] != labels[cn])
                & (ca != cp))
        k = min(keep.sum(), n_triplets - filled)
        a[filled:filled + k] = ca[keep][:k]
        p[filled:filled + k] = cp[keep][:k]
        ng[filled:filled + k] = cn[keep][:k]
        filled += k
    return {"a": a, "p": p, "n": ng}


def triplet_batches_from_indices(features: np.ndarray, idx: dict,
                                 batch_size: int, seed: int = 0):
    """Minibatch stream of {anchor, pos, neg} gathered on the fly."""
    rng = np.random.RandomState(seed)
    n = idx["a"].shape[0]
    while True:
        sel = rng.randint(0, n, batch_size)
        yield {
            "anchor": jnp.asarray(features[idx["a"][sel]]),
            "pos": jnp.asarray(features[idx["p"][sel]]),
            "neg": jnp.asarray(features[idx["n"][sel]]),
        }
