"""The IVF search served end to end against its plain reference.

``kernels.ivf_scan.ivf_search_ref`` projects raw queries and raw gallery
rows itself, probes the ``nprobe`` nearest of the index's centroids, and
takes the exact top-k over the members of those lists; it is given the
index's centroids and membership as its learned structure. Here the
served path (``RequestScheduler -> RetrievalEngine -> IVFIndex``) must
return its answers, with the segment scan in XLA and in the Pallas
kernel (interpreted on the CPU), on a gallery skewed so that the build's
balanced assignment spills rows. Also: the engine's counts of what a
probed scan touched, on a layout small enough to count by hand.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.ivf_scan import ivf_search_ref
from repro.serve import (ExactIndex, IVFIndex, RequestScheduler,
                         RetrievalEngine, kmeans_projected)

D_IN, D_OUT, C, K = 24, 16, 8, 21

# The factored distance |q|^2 + |g|^2 - 2 q.g the index scans with
# cancels: its f32 error scales with |q|^2 + |g|^2, not with the
# distance. The reference sums the difference. Served distances must
# match within a few f32 ulps of that scale (1e-5 of it leaves two
# decades above f32's 6e-8 and is below what a bf16 pass, 4e-3, gives).
DIST_TOL = 1e-5


def _skewed(seed=0, M=640):
    """Raw rows: 70% in one tight blob, the rest over a few others, so
    that k-means leaves clusters over the balanced capacity."""
    rng = np.random.RandomState(seed)
    centers = 4.0 * rng.randn(6, D_IN).astype(np.float32)
    blob = np.where(rng.rand(M) < 0.7, 0, rng.randint(1, 6, M))
    g = centers[blob] + 0.4 * rng.randn(M, D_IN).astype(np.float32)
    q = centers[rng.randint(0, 6, 40)] + 0.4 * rng.randn(
        40, D_IN).astype(np.float32)
    L = (rng.randn(D_OUT, D_IN) / np.sqrt(D_IN)).astype(np.float32)
    return jnp.asarray(L), g, q


def membership(index):
    """The list of each gallery row, read from the index's layout."""
    ids = np.asarray(index.ids_pad)
    assign = np.full(index.n_rows, -1, np.int32)
    slots = np.flatnonzero(ids >= 0)
    assign[ids[slots]] = slots // index.cap
    return assign


def assert_close_dists(d, ref, L, q, g, ids):
    """Distances within DIST_TOL of |q|^2 + |g|^2 (both projected)."""
    qp = q @ np.asarray(L).T
    gp = g @ np.asarray(L).T
    scale = np.sum(qp ** 2, 1)[:, None] + np.sum(gp[ids] ** 2, -1)
    assert np.max(np.abs(d - np.asarray(ref)) / scale) < DIST_TOL


def serve(index, queries, k):
    """Every query through the scheduler's front door, as one client: the
    miner's class, with a deadline far past the first batch's compile (the
    answers are checked here, not their latency)."""
    engine = RetrievalEngine(index, k_top=k, buckets=(8, 32, 64),
                             cache_size=1024)
    sched = RequestScheduler(engine, max_batch=64, max_wait_ms=2,
                             degrade=False)
    try:
        futs = [sched.submit(q, k_top=k, priority="mining", deadline_s=120.0)
                for q in queries]
        out = [f.result(timeout=120) for f in futs]
    finally:
        assert sched.close(timeout=60.0)
    return (np.stack([d for d, _ in out]), np.stack([i for _, i in out]),
            engine)


@pytest.fixture(scope="module")
def skewed_index():
    L, g, q = _skewed()
    index = IVFIndex.build(L, jnp.asarray(g), n_clusters=C, nprobe=3,
                           cap_factor=1.25, seed=0)
    return L, g, q, index


def test_the_skewed_gallery_spills(skewed_index):
    L, g, _, index = skewed_index
    gp = jnp.asarray(g) @ L.T
    _, assign, _ = kmeans_projected(gp, C, iters=10, seed=0)
    assert np.bincount(np.asarray(assign), minlength=C).max() > index.cap
    assert np.bincount(membership(index), minlength=C).max() <= index.cap


@pytest.mark.parametrize("scan_impl", ["xla", "pallas"])
def test_served_answers_match_the_reference(skewed_index, scan_impl):
    L, g, q, index = skewed_index
    index.scan_impl = scan_impl
    try:
        d, i, engine = serve(index, q, K)
    finally:
        index.scan_impl = "auto"
    rd, ri = ivf_search_ref(L, q, g, index.centroids, membership(index),
                            index.nprobe, K)
    np.testing.assert_array_equal(i, np.asarray(ri))
    assert_close_dists(d, rd, L, q, g, i)
    assert engine.stats()["nprobe"] == 3 < engine.stats()["n_clusters"]


def test_probing_every_list_equals_the_exact_scan(skewed_index):
    L, g, q, _ = skewed_index
    index = IVFIndex.build(L, jnp.asarray(g), n_clusters=C, nprobe=C,
                           cap_factor=1.25, seed=0)
    d, i, _ = serve(index, q, K)
    de, ie = ExactIndex.build(L, jnp.asarray(g)).topk(jnp.asarray(q), K)
    np.testing.assert_array_equal(i, np.asarray(ie))
    assert_close_dists(d, de, L, q, g, i)
    rd, ri = ivf_search_ref(L, q, g, index.centroids, membership(index), C,
                            K)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(ie))


def test_the_reference_marks_under_filled_lists():
    L = jnp.eye(2, dtype=jnp.float32)
    g = np.array([[0, 0], [0, 1], [10, 0]], np.float32)
    d, i = ivf_search_ref(L, np.array([[0.0, 0.2]], np.float32), g,
                          jnp.array([[0, 0.5], [10, 0]], jnp.float32),
                          np.array([0, 0, 1], np.int32), 1, 3)
    assert list(np.asarray(i)[0]) == [0, 1, -1]
    assert np.isinf(np.asarray(d)[0, 2])


def _hand_layout():
    """Three lists of capacity 4 on a line at 0, 10 and 30, holding 3, 4
    and 1 rows."""
    rows = {0: [(0, .1), (0, .2), (0, .3)],
            1: [(10, .1), (10, .2), (10, .3), (10, .4)],
            2: [(30, 0)]}
    gp = np.zeros((12, 2), np.float32)
    gn = np.full(12, 1e30, np.float32)
    ids = np.full(12, -1, np.int32)
    nid = 0
    for c, pts in rows.items():
        for j, p in enumerate(pts):
            gp[4 * c + j], gn[4 * c + j], ids[4 * c + j] = p, np.dot(p, p), nid
            nid += 1
    L = jnp.eye(2, dtype=jnp.float32)
    return IVFIndex(L=L, centroids=jnp.array([[0, 0], [10, 0], [30, 0]],
                                             jnp.float32),
                    gp_pad=jnp.asarray(gp), gn_pad=jnp.asarray(gn),
                    ids_pad=jnp.asarray(ids), cap=4, n_clusters=3, nprobe=2,
                    n_rows=8)


@pytest.mark.parametrize("scan_impl", ["xla", "pallas"])
def test_the_engine_counts_what_the_scan_touched(scan_impl):
    index = _hand_layout()
    index.scan_impl = scan_impl
    engine = RetrievalEngine(index, k_top=6, buckets=(4,), cache_size=0)
    # two live queries in a bucket of four; the zero pad rows probe
    # lists 0 and 1 too, and must not count
    _, ids = engine.search(np.array([[1, 0], [29, 0]], np.float32))
    counters = engine.registry.snapshot()["counters"]

    def total(name):
        return sum(counters[f"ivf_{name}_total"]["values"].values())

    assert total("probes") == 4                  # 2 queries x nprobe 2
    assert total("rows_probed") == (3 + 4) + (1 + 4)
    assert total("rows_read") == 3 + 4 + 1       # list 1 read once
    assert total("pad_slots") == 4 * 4 - 12
    assert total("underfilled") == 1             # lists 2 + 1 hold 5 < 6
    assert (ids[1] == -1).sum() == 1 and (ids[0] >= 0).all()
    st = engine.stats()
    assert (st["n_clusters"], st["nprobe"], st["cap"]) == (3, 2, 4)
    assert st["pad_share"] == pytest.approx(1 - 8 / 12)


def test_the_build_reports_its_stages(skewed_index):
    _, _, _, index = skewed_index
    stages = index.build_seconds
    assert set(stages) == {"kmeans", "balance", "layout", "build"}
    assert stages["build"] >= max(stages["kmeans"], stages["balance"],
                                  stages["layout"]) > 0
    engine = RetrievalEngine(index)
    gauge = engine.registry.snapshot()["gauges"]["index_build_seconds"]
    assert len(gauge["values"]) == 4
    assert sorted(gauge["values"].values()) == sorted(stages.values())


def test_the_index_counts_its_segments_once(skewed_index):
    # the real rows of each segment, kept on the index for the scan's
    # counts: from the build, and counted from ids_pad for an index made
    # from its arrays (a snapshot, a compaction)
    _, _, _, index = skewed_index
    held = np.bincount(membership(index), minlength=index.n_clusters)
    assert list(np.asarray(index.seg_rows)) == list(held)
    assert list(np.asarray(_hand_layout().seg_rows)) == [3, 4, 1]
    assert [n for n, _ in IVFIndex.scan_counts] == [
        "probes", "rows_probed", "rows_read", "pad_slots", "underfilled"]
