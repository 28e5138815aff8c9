"""The benchmark's inputs are made from the seed alone."""

import numpy as np
import pytest

from harness import data

BIG = 2 ** 31 + 12345          # run seeds may pass 32 signed bits


def test_arrivals_repeat_for_a_seed_and_offer_the_same_load():
    a = data.arrivals(500.0, 2.0, 0.5, BIG, 1024)
    b = data.arrivals(500.0, 2.0, 0.5, BIG, 1024)
    c = data.arrivals(500.0, 2.0, 0.5, BIG + 1, 1024)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    # one multiset of gaps in another order: as many requests, and
    # nearly as many of them due inside the window
    inw = lambda due: int(np.sum((due >= 0) & (due < 2.0)))
    assert len(a[0]) == len(c[0])
    assert abs(inw(a[0]) - inw(c[0])) <= 0.05 * inw(a[0])
    assert a[0][0] >= -0.5 and a[0][-1] < 2.0
    assert np.all(np.diff(a[0]) >= 0)
    assert a[1].min() >= 0 and a[1].max() < 1024


def test_rows_repeat_for_a_seed_and_streams_differ():
    kw = dict(rows=64, n_classes=4, feat_dim=128)
    x1, y1 = data.make_rows(data.base_key(BIG), stream=data.QUERIES, **kw)
    x2, y2 = data.make_rows(data.base_key(BIG), stream=data.QUERIES, **kw)
    x3, _ = data.make_rows(data.base_key(BIG + 2 ** 32),
                           stream=data.QUERIES, **kw)
    x4, _ = data.make_rows(data.base_key(BIG), stream=data.GALLERY, **kw)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert not np.array_equal(np.asarray(x1), np.asarray(x3))
    assert not np.array_equal(np.asarray(x1), np.asarray(x4))
    x = np.asarray(x1)
    assert (x >= 0).all() and 0.02 < (x > 0).mean() < 0.3   # sparse codes


def test_projected_rows_match_the_rows_they_come_from():
    import jax.numpy as jnp
    key = data.base_key(BIG)
    L = jnp.asarray(np.random.default_rng(0).normal(size=(8, 128)),
                    jnp.float32)
    proj = lambda L, x: (x @ L.T, jnp.sum((x @ L.T) ** 2, axis=1))
    gp, gn = data.make_projected(key, L, proj, stream=data.GALLERY, rows=96,
                                 chunk=32, n_classes=4, feat_dim=128,
                                 out_dim=8)
    x, _ = data.make_rows(key, stream=data.GALLERY, rows=32, n_classes=4,
                          feat_dim=128)
    np.testing.assert_allclose(np.asarray(gp[:32]), np.asarray(x @ L.T),
                               rtol=1e-5, atol=1e-4)
    # a row count that is no multiple of the chunk gives the same rows
    gp2, gn2 = data.make_projected(key, L, proj, stream=data.GALLERY, rows=80,
                                   chunk=32, n_classes=4, feat_dim=128,
                                   out_dim=8)
    np.testing.assert_array_equal(np.asarray(gp2), np.asarray(gp[:80]))
    np.testing.assert_array_equal(np.asarray(gn2), np.asarray(gn[:80]))


def test_pair_pool_is_valid_and_repeats_for_a_seed():
    labels = np.random.default_rng(1).integers(0, 20, size=3000)
    p = data.pair_pool(labels, 5000, 4000, BIG)
    q = data.pair_pool(labels, 5000, 4000, BIG)
    for k in ("a", "b", "sim"):
        np.testing.assert_array_equal(p[k], q[k])
    sim = p["sim"] == 1
    assert sim.sum() == 5000 and (~sim).sum() == 4000
    assert (labels[p["a"][sim]] == labels[p["b"][sim]]).all()
    assert (p["a"][sim] != p["b"][sim]).all()
    assert (labels[p["a"][~sim]] != labels[p["b"][~sim]]).all()
    # uniform over ordered same-class pairs: a class's share of similar
    # pairs follows n_c (n_c - 1)
    counts = np.bincount(labels, minlength=20)
    want = counts * (counts - 1) / np.sum(counts * (counts - 1))
    got = np.bincount(labels[p["a"][sim]], minlength=20) / 5000
    np.testing.assert_allclose(got, want, atol=0.02)
