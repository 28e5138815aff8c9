"""Plain reference of an inverted-file (IVF) k-NN search.

Written from the definition of an IVF search, in ``jax.numpy``: project
the query, probe the ``nprobe`` lists whose centroids are nearest, and
take the exact k nearest among the members of those lists. The index's
centroids are given like weights; everything else (the gallery rows, the
projection, every distance) the reference makes itself from the seed, as
``reference.py`` does for the exact scan. Which list holds each row is
read from the index, and held to the documented rule (``assignment``).
One pass over the gallery gives both the top k within the probed lists
and the exact top k over the whole gallery, for recall.

The rule of a list's members, from the given centroids (``cap``,
``assignment``): each row belongs to its nearest centroid's list; a list
keeps its ``cap`` nearest rows, and each row over the cap goes to the
nearest list that still has room. So a row's list is no farther than any
list that ends with room, and a row that left its nearest list is no
nearer it than the rows that list kept. ``assign_excess`` measures the
worst break of either.

``PROBE_TIE`` is the rule for a tie at the ``nprobe``-th centroid. A
program computes its probe distances within its stated precision of the
reference's, so which of two lists whose centroid distances lie that
close comes in ``nprobe``-th is no error. The reference therefore
compares served answers against the lists every correct program probes:
those whose centroid distance lies below the (``nprobe`` + 1)-th smallest
by more than ``PROBE_TIE`` x (|qp|^2 + the largest |centroid|^2). Where
the ``nprobe``-th and the next lie farther apart than that, all
``nprobe`` lists are required. A program that probes those lists and
more can only do as well or better there; one that leaves one of them
out shows as an excess.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.reference import _dot_t

# three times the largest gap between a distance the program serves and
# the reference's true one over every serving reading (dist_err at most
# 9.3e-5 over |qp|^2 + |gp|^2, PERF.md section 2): a tie wider than
# both distances' errors together
PROBE_TIE = 3e-4


def cap(rows: int, n_clusters: int, cap_factor: float) -> int:
    """The rule's list capacity: ceil(cap_factor x rows / n_clusters),
    rounded up to a multiple of 8."""
    c = -(-max(cap_factor, 1.0) * rows // n_clusters)
    return int(-(-c // 8) * 8)


def membership(ids_pad: np.ndarray, seg: int, rows: int, cap_: int):
    """(the list of each gallery row from an index's padded segments of
    ``seg`` slots, -1 where no slot holds it; the real rows of each list;
    the number of gallery ids missing from the segments, held twice, out
    of range, or beyond ``cap_`` in their list)."""
    ids_pad = np.asarray(ids_pad)
    slots = np.flatnonzero(ids_pad != -1)
    ids = ids_pad[slots]
    bad = (ids < 0) | (ids >= rows)
    held = np.bincount(ids[~bad], minlength=rows)
    assign = np.full(rows, -1, np.int32)
    once = ~bad & (held[np.clip(ids, 0, rows - 1)] == 1)
    assign[ids[once]] = slots[once] // seg
    counts = np.bincount(slots[~bad] // seg, minlength=len(ids_pad) // seg)
    wrong = int(bad.sum() + (held == 0).sum() + (held > 1).sum()
                + np.maximum(counts - cap_, 0).sum())
    return assign, counts, wrong


@functools.partial(jax.jit, static_argnames=("nprobe",))
def probed(qp, centroids, *, nprobe: int, tie):
    """(Nq, C) bool: the lists each query must probe: those whose
    centroid distance lies below the (``nprobe`` + 1)-th smallest by more
    than ``tie`` x (|qp|^2 + max |c|^2) (``tie`` 0: exactly the nearest
    ``nprobe``; all C where ``nprobe`` is C)."""
    dtype = qp.dtype
    qn = jnp.sum(qp * qp, axis=1, keepdims=True)
    cn = jnp.sum(centroids * centroids, axis=1)
    cd = qn + cn[None, :] - 2 * _dot_t(qp, centroids, dtype)
    if nprobe >= centroids.shape[0]:
        return jnp.ones(cd.shape, bool)
    nxt = -jax.lax.top_k(-cd, nprobe + 1)[0][:, -1:]
    band = jnp.asarray(tie, dtype) * (qn + jnp.max(cn))
    return cd < nxt - band


@functools.partial(jax.jit, static_argnames=("blocks",))
def assignment(gp, gn, centroids, own, has_room, *, blocks: int):
    """One pass over the gallery in ``blocks`` row blocks: per row (M,),
    its squared distance to the centroid of its list ``own`` (+inf where
    -1), to its nearest centroid, which that is, and to the nearest
    centroid whose list ``has_room`` (C,) bool."""
    M, d = gp.shape
    rows = M // blocks
    cn = jnp.sum(centroids * centroids, axis=1)
    room = jnp.where(has_room, 0.0, jnp.inf)

    def body(_, blk):
        g, n, a = blk
        dist = jnp.maximum(n[:, None] + cn[None, :]
                           - 2 * _dot_t(g, centroids, jnp.float32), 0)
        d_own = jnp.where(a >= 0, jnp.take_along_axis(
            dist, jnp.maximum(a, 0)[:, None], axis=1)[:, 0], jnp.inf)
        return None, (d_own, jnp.min(dist, axis=1),
                      jnp.argmin(dist, axis=1).astype(jnp.int32),
                      jnp.min(dist + room[None, :], axis=1))

    _, out = jax.lax.scan(body, None, (gp.reshape(blocks, rows, d),
                                       gn.reshape(blocks, rows),
                                       own.reshape(blocks, rows)))
    return tuple(o.reshape(M) for o in out)


def assign_excess(own, d_own, d_near, near, d_room, scale) -> float:
    """The worst break of the membership rule over the gallery's rows, in
    units of ``scale`` (M,): a row's list farther than the nearest list
    with room, or a row that left its nearest list though nearer it than
    a row that list kept (by at most how much farther its own list is).
    Rows no list holds (``own`` -1) are left to ``membership``."""
    held = own >= 0
    kept = held & (own == near)
    far = np.full(int(near.max(initial=0)) + 1, -np.inf)
    np.maximum.at(far, near[kept], d_own[kept])
    far = far[near]
    far = np.where(np.isneginf(far), np.inf, far)   # a list that kept none
    left = held & ~kept
    excess = np.zeros(len(own))
    excess[held] = d_own[held] - d_room[held]
    excess[left] = np.maximum(excess[left], np.minimum(
        d_own[left] - d_near[left], far[left] - d_near[left]))
    return float(max(np.max(excess / scale, initial=0.0), 0.0))


@functools.partial(jax.jit, static_argnames=("k", "blocks"))
def knn(qp, gp, gn, assign, allowed, *, k: int, blocks: int):
    """One pass over the gallery in ``blocks`` row blocks: ((dists, ids)
    of the k nearest rows among the lists ``allowed`` (Nq, C) holds,
    (dists, ids) of the k nearest of the whole gallery), each (Nq, k)
    ascending, ties to the smaller id; +inf and -1 where the lists hold
    fewer than k rows."""
    dtype = gp.dtype
    M, d = gp.shape
    rows = M // blocks
    qn = jnp.sum(qp * qp, axis=1, keepdims=True)
    Nq = qp.shape[0]

    def merge(best, dist, off):
        bd, bi = best
        cd = jnp.concatenate([bd, dist], axis=1)
        ci = jnp.concatenate(
            [bi, jnp.broadcast_to(off + jnp.arange(rows), dist.shape)],
            axis=1)
        neg, pos = jax.lax.top_k(-cd, k)   # running best first: ties to the
        return -neg, jnp.take_along_axis(ci, pos, axis=1)   # smaller id

    def body(carry, blk):
        inl, full = carry
        g, n, a, off = blk
        dist = jnp.maximum(qn + n[None, :] - 2 * _dot_t(qp, g, dtype), 0)
        member = jnp.take(allowed, jnp.maximum(a, 0), axis=1) & (a >= 0)
        inside = jnp.where(member, dist, jnp.inf)
        return (merge(inl, inside, off), merge(full, dist, off)), None

    init = (jnp.full((Nq, k), jnp.inf, dtype), jnp.full((Nq, k), -1,
                                                        jnp.int32))
    (inl, full), _ = jax.lax.scan(
        body, (init, init),
        (gp.reshape(blocks, rows, d), gn.reshape(blocks, rows),
         assign.reshape(blocks, rows),
         jnp.arange(blocks, dtype=jnp.int32) * rows))
    d_in, i_in = inl
    return (d_in, jnp.where(jnp.isinf(d_in), -1, i_in)), full
