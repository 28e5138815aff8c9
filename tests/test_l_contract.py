"""Cross-layer conformance suite for the (d_out, d_in) metric-factor contract.

One parameterized suite over every MetricIndex backend (Exact / IVF /
IVFPQ / Mutable-over-each) × {square L, rectangular L, identity}:

  (a) factored-distance oracle — ``topk`` under L equals ``topk`` under
      the identity factor on pre-projected rows: d(x, y) = ||Lx - Ly||²
      means projecting first and scanning with I_{d_out} must return the
      same neighbors;
  (b) golden square-L bit-identity — answers match the pre-refactor
      stack exactly (fixtures in tests/golden/, regenerated only when a
      behavior change is intentional);
  (c) ``swap_metric`` square→rect→square round-trips agree with fresh
      builds at each rank (the retained raw rows make rank changes
      legal);
  (d) snapshots record ``l_shape`` and reject a rank-mismatched
      ``expect_L`` with a structural error, before the fingerprint gate;

plus the up-front L validation regressions (transposed / 1-D factors
used to die deep inside a jit with an opaque dot-dimension error).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dml
from repro.serve import scan, snapshot
from repro.serve.engine import RetrievalEngine
from repro.serve.index import ExactIndex
from repro.serve.ivf import IVFIndex
from repro.serve.mutable import MutableIndex
from repro.serve.pq import IVFPQIndex

D_IN = 24
M = 240
NQ = 6
KTOP = 5

# nprobe == n_clusters and rerank == M: every row is visited and the
# exact rerank covers the whole candidate pool, so approximate backends
# are deterministic oracles regardless of how k-means falls out
IVF_KW = dict(n_clusters=8, nprobe=8, seed=0)
PQ_KW = dict(n_clusters=8, nprobe=8, seed=0, n_subspaces=5, bits=6,
             rerank_depth=M, store="device")

BACKENDS = ("exact", "ivf", "ivfpq",
            "mutable_exact", "mutable_ivf", "mutable_ivfpq")
L_KINDS = ("square", "rect", "identity")


def _data():
    rs = np.random.RandomState(7)
    gallery = rs.randn(M, D_IN).astype(np.float32)
    queries = rs.randn(NQ, D_IN).astype(np.float32)
    up_rows = rs.randn(8, D_IN).astype(np.float32)
    return gallery, queries, up_rows


def _make_L(kind: str) -> np.ndarray:
    rs = np.random.RandomState(11)
    if kind == "square":
        return (rs.randn(D_IN, D_IN) / np.sqrt(D_IN)).astype(np.float32)
    if kind == "rect":
        return (rs.randn(10, D_IN) / np.sqrt(D_IN)).astype(np.float32)
    return np.eye(D_IN, dtype=np.float32)


def _build(backend: str, L, gallery, up_rows=None):
    """Build one backend; mutable flavors get churn (upserts + deletes)."""
    if backend == "exact":
        return ExactIndex.build(L, jnp.asarray(gallery))
    if backend == "ivf":
        return IVFIndex.build(L, jnp.asarray(gallery), **IVF_KW)
    if backend == "ivfpq":
        return IVFPQIndex.build(L, jnp.asarray(gallery), **PQ_KW)
    base = backend.split("_", 1)[1]
    kw = {"exact": {}, "ivf": IVF_KW, "ivfpq": PQ_KW}[base]
    mut = MutableIndex.build(L, gallery, base=base, retain_raw=True, **kw)
    if up_rows is not None:
        mut.upsert(up_rows)                     # external ids M..M+7
        mut.delete([2, 17, M + 1])
    return mut


# -- (a) the factored-distance oracle ----------------------------------------

@pytest.mark.parametrize("l_kind", L_KINDS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_topk_matches_identity_on_preprojected(backend, l_kind):
    gallery, queries, up_rows = _data()
    L = _make_L(l_kind)
    d_out = L.shape[0]
    idx = _build(backend, L, gallery, up_rows)
    d1, i1 = idx.topk(jnp.asarray(queries), KTOP)

    eye = np.eye(d_out, dtype=np.float32)
    oracle = _build(backend, eye, gallery @ L.T,
                    None if up_rows is None else up_rows @ L.T)
    d2, i2 = oracle.topk(jnp.asarray(queries @ L.T), KTOP)

    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-5, atol=1e-5)


def test_exact_pallas_backend_rect_rank_parity():
    """The fused metric_topk kernel serves rectangular L too: ids match
    the XLA path exactly at a non-lane-aligned low rank."""
    gallery, queries, _ = _data()
    L = _make_L("rect")
    idx = _build("exact", L, gallery)
    d_x, i_x = idx.topk(jnp.asarray(queries), KTOP, backend="xla")
    d_p, i_p = idx.topk(jnp.asarray(queries), KTOP, backend="pallas")
    np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_p))
    np.testing.assert_allclose(np.asarray(d_x), np.asarray(d_p),
                               rtol=1e-4, atol=1e-4)


# -- (b) golden square-L bit-identity ----------------------------------------

def _load_golden_gen():
    path = os.path.join(os.path.dirname(__file__), "golden",
                        "gen_l_contract_golden.py")
    spec = importlib.util.spec_from_file_location("gen_l_contract_golden",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_square_l_bit_identical_to_golden():
    gen = _load_golden_gen()
    with np.load(gen.GOLDEN) as z:
        inputs = {k: z[k] for k in ("gallery", "queries", "L", "up_rows")}
        golden = {name: (z[f"dist_{name}"], z[f"ids_{name}"])
                  for name in ("exact", "ivf", "ivfpq", "mutable_exact",
                               "mutable_ivf", "mutable_ivfpq")}
    # the fixture predates jax 0.5, which made the partitionable
    # threefry stream the default; k-means seeding draws from jax.random,
    # so the fixture pins the stream it was captured under
    with jax.threefry_partitionable(False):
        cases = gen.build_cases(inputs)
    for name, (d, i) in cases.items():
        gd, gi = golden[name]
        np.testing.assert_array_equal(np.asarray(i), gi, err_msg=name)
        # the fixture was also captured under an older XLA, whose CPU
        # fusion rounds some distances ~1 ulp differently: ids must not
        # move, distances agree to f32 rounding
        np.testing.assert_allclose(np.asarray(d, np.float32), gd,
                                   rtol=1e-5, atol=0, err_msg=name)


# -- (c) swap_metric rank round trip -----------------------------------------

@pytest.mark.parametrize("base", ("exact", "ivf", "ivfpq"))
def test_swap_metric_rank_round_trip(base):
    gallery, queries, up_rows = _data()
    L_sq, L_rect = _make_L("square"), _make_L("rect")
    kw = {"exact": {}, "ivf": IVF_KW, "ivfpq": PQ_KW}[base]

    mut = MutableIndex.build(L_sq, gallery, base=base, retain_raw=True,
                             **kw)
    mut.swap_metric(L_rect)                       # square -> rect
    fresh_rect = MutableIndex.build(L_rect, gallery, base=base,
                                    retain_raw=True, **kw)
    d_s, i_s = mut.topk(jnp.asarray(queries), KTOP)
    d_f, i_f = fresh_rect.topk(jnp.asarray(queries), KTOP)
    np.testing.assert_array_equal(i_s, i_f)
    np.testing.assert_array_equal(d_s, d_f)

    # mutation keeps working at the new rank (the delta buffer must be
    # re-sized to the new d_out, not the stale pre-swap one)
    ids = mut.upsert(up_rows)
    assert mut.delta_gp.shape[1] == L_rect.shape[0]
    mut.delete(ids[:2])

    mut.swap_metric(L_sq)                         # rect -> square, churn kept
    # mirror the same churn on a fresh square index: external ids line up,
    # and answers agree (allclose: the fresh index still holds the churn
    # in its delta buffer while the swap compacted it into the base)
    fresh_sq = MutableIndex.build(L_sq, gallery, base=base,
                                  retain_raw=True, **kw)
    fresh_sq.upsert(up_rows)
    fresh_sq.delete(ids[:2])
    d_s, i_s = mut.topk(jnp.asarray(queries), KTOP)
    d_f, i_f = fresh_sq.topk(jnp.asarray(queries), KTOP)
    np.testing.assert_array_equal(i_s, i_f)
    np.testing.assert_allclose(d_s, d_f, rtol=1e-5, atol=1e-5)


# -- (d) snapshot l_shape + rank-mismatch rejection --------------------------

@pytest.mark.parametrize("l_kind", ("square", "rect"))
@pytest.mark.parametrize("backend", ("exact", "mutable_ivf"))
def test_snapshot_preserves_l_shape(tmp_path, backend, l_kind):
    gallery, queries, up_rows = _data()
    L = _make_L(l_kind)
    idx = _build(backend, L, gallery, up_rows)
    manifest = snapshot.save_index(idx, str(tmp_path))
    assert manifest["l_shape"] == list(L.shape)

    loaded = snapshot.load_index(str(tmp_path), expect_L=L)
    d1, i1 = idx.topk(jnp.asarray(queries), KTOP)
    d2, i2 = loaded.topk(jnp.asarray(queries), KTOP)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def test_snapshot_rejects_rank_mismatched_expect_l(tmp_path):
    gallery, _, _ = _data()
    L_rect = _make_L("rect")
    idx = ExactIndex.build(L_rect, jnp.asarray(gallery))
    snapshot.save_index(idx, str(tmp_path))
    # wrong rank: the structural (shape) diagnosis, not the fingerprint one
    with pytest.raises(ValueError, match="rank-mismatched"):
        snapshot.load_index(str(tmp_path), expect_L=_make_L("square"))
    # same shape, different values: still the fingerprint gate
    other = _make_L("rect") + 1.0
    with pytest.raises(ValueError, match="fingerprint"):
        snapshot.load_index(str(tmp_path), expect_L=other)


# -- validation regressions (transposed / 1-D L used to die inside jit) ------

def test_project_queries_rejects_bad_l():
    _, queries, _ = _data()
    L = _make_L("rect")
    with pytest.raises(ValueError, match="d_in"):
        scan.project_queries(jnp.asarray(L.T), jnp.asarray(queries))
    with pytest.raises(ValueError, match="2-D"):
        scan.project_queries(jnp.asarray(L[0]), jnp.asarray(queries))


@pytest.mark.parametrize("build", (
    lambda L, g: ExactIndex.build(L, jnp.asarray(g)),
    lambda L, g: IVFIndex.build(L, jnp.asarray(g), **IVF_KW),
    lambda L, g: IVFPQIndex.build(L, jnp.asarray(g), **PQ_KW),
    lambda L, g: MutableIndex.build(L, g, base="exact"),
), ids=("exact", "ivf", "ivfpq", "mutable"))
def test_index_build_rejects_bad_l(build):
    gallery, _, _ = _data()
    L = _make_L("rect")
    with pytest.raises(ValueError, match="d_in"):
        build(jnp.asarray(L.T), gallery)          # transposed
    with pytest.raises(ValueError, match="2-D"):
        build(jnp.asarray(L[0]), gallery)         # 1-D


def test_square_transposed_l_names_the_transposition():
    """A square-but-transposed factor can't be caught by shape alone, but
    a (d_in, d_out) rectangular transposition gets the explicit hint."""
    gallery, _, _ = _data()
    bad = _make_L("rect").T                       # (24, 10): rows == d_in
    with pytest.raises(ValueError, match="transposed"):
        ExactIndex.build(jnp.asarray(bad), jnp.asarray(gallery))


def test_from_projected_rejects_dout_mismatch():
    gallery, _, _ = _data()
    L = _make_L("rect")                           # d_out = 10
    gp = (gallery @ _make_L("square").T).astype(np.float32)   # dim 24
    gn = np.sum(gp * gp, axis=1).astype(np.float32)
    with pytest.raises(ValueError, match="d_out"):
        ExactIndex.from_projected(L, gp, gn)
    with pytest.raises(ValueError, match="d_out"):
        IVFIndex.build_projected(L, gp, gn, **IVF_KW)
    with pytest.raises(ValueError, match="d_out"):
        IVFPQIndex.build_projected(L, gp, gn, **PQ_KW)


def test_swap_metric_rejects_bad_l():
    gallery, _, _ = _data()
    mut = MutableIndex.build(_make_L("square"), gallery, base="exact",
                             retain_raw=True)
    with pytest.raises(ValueError, match="d_in"):
        mut.swap_metric(_make_L("rect").T)
    with pytest.raises(ValueError, match="2-D"):
        mut.swap_metric(_make_L("rect")[0])


# -- the low-rank trainer knob -----------------------------------------------

def test_dml_config_l_rank_knob():
    cfg = dml.DMLConfig(feat_dim=64, l_rank=16)
    assert cfg.proj_dim == 16
    L = dml.init_params(cfg, jax.random.PRNGKey(0))
    assert L.shape == (16, 64)
    # M = L^T L is PSD by construction at any rank — no projection step
    w = np.linalg.eigvalsh(np.asarray(dml.M_from_L(L)))
    assert w.min() >= -1e-5
    assert np.sum(w > 1e-6) <= 16

    assert dml.DMLConfig(feat_dim=64).proj_dim == 64     # square default
    with pytest.raises(ValueError, match="disagree"):
        dml.DMLConfig(feat_dim=64, proj_dim=32, l_rank=16)
    with pytest.raises(ValueError, match="1..feat_dim"):
        dml.DMLConfig(feat_dim=64, l_rank=0)
    with pytest.raises(ValueError, match="1..feat_dim"):
        dml.DMLConfig(feat_dim=64, l_rank=65)


def test_lowrank_l_serves_through_engine():
    """A rectangular trained-shape L drops into the engine unchanged and
    stats report the (d_out, d_in) shape."""
    gallery, queries, _ = _data()
    L = _make_L("rect")
    engine = RetrievalEngine(ExactIndex.build(L, jnp.asarray(gallery)),
                             k_top=KTOP)
    d, i = engine.search(queries[:2])
    assert np.asarray(i).shape == (2, KTOP)
    assert engine.stats()["l_shape"] == [10, D_IN]
