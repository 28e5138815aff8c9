"""The benchmark's own inputs, made from ``--seed``.

Everything a cell feeds the system is made here, so that no change to the
program can change what it is measured on:

* ``llc_like`` rows: sparse nonnegative codes with the class structure of
  LLC features (paper §5.1), made on the device in one jitted call per
  array, a chunk of rows at a time. Each class has a support of density
  ``1 - sparsity`` and |N(0,1)| magnitudes; a row adds
  ``noise * |N(0,1)|`` inside its class's support. A row depends only on
  (seed, stream, row index), so the training store, the gallery and the
  query pool are disjoint draws of one distribution, and the reference
  makes the same rows again without keeping them.
* pair pools: similar pairs uniform over same-class pairs, dissimilar
  pairs uniform over different-class pairs (paper §5.2), as indices into
  the training store.
* arrival schedules: one multiset of exponential gaps shared by every
  seed, put in the seed's own order, so every seed offers the same load.

The arithmetic of the rows follows ``chip_smoke.make_rows`` and
``repro.data.pairs.llc_like_chunk``; it is copied so that it stays fixed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# streams of rows: disjoint row-key spaces of one distribution
TRAIN, GALLERY, QUERIES, WARM = 0, 1, 2, 3
CHUNK = 4096        # rows made per step of the loop that fills an array


def base_key(seed: int):
    """A PRNG key from a seed of any size (seeds may pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _class_tables(key, n_classes: int, feat_dim: int, sparsity: float):
    k_mag, k_sup = jax.random.split(jax.random.fold_in(key, 0))
    mags = jnp.abs(jax.random.normal(k_mag, (n_classes, feat_dim)))
    support = jax.random.uniform(k_sup, (n_classes, feat_dim)) < 1 - sparsity
    return mags, support


def _rows_at(key, stream: int, idx, tables, *, feat_dim: int, noise: float):
    """The rows ``idx`` (n,) of one stream: (x (n, d) f32, labels (n,))."""
    mags, support = tables
    k_stream = jax.random.fold_in(jax.random.fold_in(key, 1), stream)

    def one(i):
        k_lab, k_noise = jax.random.split(jax.random.fold_in(k_stream, i))
        label = jax.random.randint(k_lab, (), 0, mags.shape[0])
        row_noise = noise * jnp.abs(jax.random.normal(k_noise, (feat_dim,)))
        return jnp.where(support[label], mags[label] + row_noise, 0.0), label

    x, labels = jax.vmap(one)(idx)
    return x, labels.astype(jnp.int32)


def _fill(rows: int, chunk: int, make, init):
    """Fill ``init`` (arrays with ``rows`` leading rows) chunk by chunk:
    ``make(idx)`` gives the arrays' rows ``idx``. The last chunk is moved
    back to end at ``rows``, which rewrites a few rows with the same
    values, so that any ``rows >= chunk`` works."""
    chunk = min(chunk, rows)

    def body(i, acc):
        start = jnp.minimum(i * chunk, rows - chunk)
        new = make(start + jnp.arange(chunk))
        return tuple(jax.lax.dynamic_update_slice(
            a, n.astype(a.dtype), (start,) + (0,) * (a.ndim - 1))
            for a, n in zip(acc, new))

    return jax.lax.fori_loop(0, -(-rows // chunk), body, init)


@functools.partial(jax.jit, static_argnames=("stream", "rows", "n_classes",
                                             "feat_dim"))
def make_rows(key, *, stream: int, rows: int, n_classes: int, feat_dim: int,
              sparsity: float = 0.9, noise: float = 0.3):
    """One on-device array of ``rows`` llc_like rows and their labels."""
    tables = _class_tables(key, n_classes, feat_dim, sparsity)
    make = functools.partial(_rows_at, key, stream, tables=tables,
                             feat_dim=feat_dim, noise=noise)
    init = (jnp.zeros((rows, feat_dim), jnp.float32),
            jnp.zeros((rows,), jnp.int32))
    return _fill(rows, CHUNK, make, init)


def make_projected(key, L, project, *, stream: int, rows: int, chunk: int,
                   n_classes: int, feat_dim: int, out_dim: int,
                   sparsity: float = 0.9, noise: float = 0.3):
    """``rows`` rows of a stream, made chunk by chunk and each chunk mapped
    by ``project(L, x) -> (p (chunk, out_dim), n (chunk,))`` inside one
    jitted call, so that the raw (rows, feat_dim) matrix never exists
    whole. ``L`` is an argument of that call, not a constant baked into
    it, so that the program stays small enough for the compile cache.
    Returns (p (rows, out_dim) f32, n (rows,) f32)."""

    @jax.jit
    def run(key, L):
        tables = _class_tables(key, n_classes, feat_dim, sparsity)

        def make(idx):
            x, _ = _rows_at(key, stream, idx, tables, feat_dim=feat_dim,
                            noise=noise)
            return project(L, x)

        init = (jnp.zeros((rows, out_dim), jnp.float32),
                jnp.zeros((rows,), jnp.float32))
        return _fill(rows, chunk, make, init)

    return run(key, L)


def pair_pool(labels: np.ndarray, n_similar: int, n_dissimilar: int,
              seed: int) -> dict:
    """Index pairs into a labelled store: ``{"a", "b", "sim"}`` int arrays,
    similar pairs uniform over ordered same-class pairs (a != b),
    dissimilar pairs uniform over different-class pairs, in a shuffled
    order. Vectorised; a pool of millions is drawn in about a second."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 1])
    labels = np.asarray(labels)
    n = labels.shape[0]
    order = np.argsort(labels, kind="stable")
    counts = np.bincount(labels)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    # anchor weight (n_c - 1): every ordered same-class pair equally likely
    w = (counts[labels] - 1).astype(np.float64)
    sa = rng.choice(n, size=n_similar, p=w / w.sum())
    cls = labels[sa]
    pos = rng.integers(0, counts[cls] - 1)          # among the other rows
    own = rank[sa] - starts[cls]
    pos = pos + (pos >= own)
    sb = order[starts[cls] + pos]
    da = rng.integers(0, n, size=n_dissimilar)
    db = rng.integers(0, n, size=n_dissimilar)
    same = labels[da] == labels[db]
    while same.any():
        db[same] = rng.integers(0, n, size=int(same.sum()))
        same = labels[da] == labels[db]
    a = np.concatenate([sa, da])
    b = np.concatenate([sb, db])
    sim = np.concatenate([np.ones(n_similar, np.int32),
                          np.zeros(n_dissimilar, np.int32)])
    perm = rng.permutation(a.shape[0])
    return {"a": a[perm], "b": b[perm], "sim": sim[perm]}


def arrivals(rate: float, seconds: float, lead_s: float, seed: int,
             pool: int):
    """An open-loop Poisson schedule: (due offsets in s from the window's
    start, from ``-lead_s`` on, (n,) float64; query ids into a pool of
    ``pool``, (n,) int64). The gaps are one fixed multiset (base seed 0)
    that ``seed`` only reorders, so every seed offers the same number of
    requests in the window; the query ids are uniform draws of ``seed``."""
    total = seconds + lead_s
    n = max(1, int(round(rate * total)))
    gaps = np.random.default_rng(0).exponential(1.0 / rate, size=n)
    gaps *= total / gaps.sum()            # the last one is due at `seconds`
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 2])
    due = np.cumsum(rng.permutation(gaps))[:-1] - lead_s
    return due, rng.integers(0, pool, size=due.shape[0])
