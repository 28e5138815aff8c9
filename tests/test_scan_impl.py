"""The ``scan_impl`` knob through the serving stack.

Pins the tentpole's serving contract: ``scan_impl="pallas"`` (interpret
mode on CPU) answers **identically** to ``scan_impl="xla"`` through
IVFPQIndex (bit-identical — both routes share kernels/pq_adc) and
IVFIndex (ids exact, distances to f32 rounding), composes with the
exact-rerank ladder and the ExactIndex oracle, survives MutableIndex
compaction and snapshot round-trips, and rejects falsy/unknown values
at every entry point instead of silently remapping them (the k_top=0
bug class).
"""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.serve import (ExactIndex, IVFIndex, IVFPQIndex, MutableIndex,
                         load_index, save_index)
from repro.serve.scan import SCAN_IMPLS, resolve_scan_impl


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(0)
    d, k, M = 20, 10, 300
    L = (0.3 * rng.randn(k, d)).astype(np.float32)
    G = rng.randn(M, d).astype(np.float32)
    Q = rng.randn(7, d).astype(np.float32)
    return L, G, Q


def test_resolve_scan_impl_contract():
    assert resolve_scan_impl("xla") == "xla"
    assert resolve_scan_impl("pallas") == "pallas"
    assert resolve_scan_impl("xla", "pallas") == "pallas"
    assert resolve_scan_impl("auto") in ("xla", "pallas")
    # `is None` defers to the default; explicit falsy values raise
    assert resolve_scan_impl("pallas", None) == "pallas"
    for bad in ("", 0, False, "fused"):
        with pytest.raises(ValueError, match="scan_impl"):
            resolve_scan_impl("auto", bad)
        with pytest.raises(ValueError, match="scan_impl"):
            resolve_scan_impl(bad)


def test_auto_resolves_from_platform_and_sharding(monkeypatch):
    import repro.serve.scan as scan
    monkeypatch.setattr(scan.jax, "default_backend", lambda: "tpu")
    assert resolve_scan_impl("auto") == "pallas"
    # the fused kernel does not compose with shard_map: a sharded index's
    # auto is the per-shard XLA scan, an explicit pallas stays pallas (the
    # index rejects it)
    assert resolve_scan_impl("auto", sharded=True) == "xla"
    assert resolve_scan_impl("pallas", sharded=True) == "pallas"
    monkeypatch.setattr(scan.jax, "default_backend", lambda: "cpu")
    assert resolve_scan_impl("auto") == "xla"


def _chip_smoke():
    """The repo-root smoke script as a module: its on-chip parity check
    (tie_mismatches) lives there, not in the serving library."""
    if "chip_smoke" not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chip_smoke.py")
        spec = importlib.util.spec_from_file_location("chip_smoke", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = mod     # dataclasses look it up
        spec.loader.exec_module(mod)
    return sys.modules["chip_smoke"]


@pytest.mark.parametrize("case", ["same", "tie_swap", "far_swap",
                                  "boundary_tie", "boundary_far",
                                  "wide_tol"])
def test_tie_mismatches(case):
    tie_mismatches = _chip_smoke().tie_mismatches
    d = np.array([[1.0, 2.0, 3.0, 4.0]], np.float32)
    i = np.array([[10, 11, 12, 13]])
    d2, i2 = d.copy(), i.copy()
    want, tol = (0, 0), np.array([4e-5])
    if case == "tie_swap":          # two rows within f32 rounding swap
        d[0, 1] = d[0, 2] = d2[0, 1] = d2[0, 2] = 2.5
        i2[0, 1:3] = [12, 11]
        want = (2, 0)
    elif case == "far_swap":        # a real reorder is never a tie
        i2[0, 1:3] = [12, 11]
        want = (2, 2)
    elif case == "boundary_tie":    # k-th row replaced by an equidistant one
        i2[0, 3], d2[0, 3] = 99, 4.0 + 1e-6
        want = (1, 0)
    elif case == "boundary_far":
        i2[0, 3], d2[0, 3] = 99, 3.5
        want = (1, 1)
    elif case == "wide_tol":        # the tolerance is the caller's: a
        i2[0, 1:3] = [12, 11]       # gap of 1.0 is a tie under tol 2.0
        want, tol = (2, 0), 2.0
    assert tie_mismatches(i, d, i2, d2, tol)[:2] == want


def test_ivf_pallas_matches_xla(data):
    L, G, Q = data
    ivf = IVFIndex.build(L, jnp.asarray(G), n_clusters=8, nprobe=3)
    d_x, i_x = ivf.topk(Q, 5, scan_impl="xla")
    d_p, i_p = ivf.topk(Q, 5, scan_impl="pallas")
    np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_p))
    np.testing.assert_allclose(np.asarray(d_x), np.asarray(d_p),
                               rtol=1e-4, atol=1e-4)


def test_ivfpq_pallas_bit_identical(data):
    L, G, Q = data
    pq = IVFPQIndex.build(L, jnp.asarray(G), n_clusters=8, nprobe=3,
                          n_subspaces=5, bits=6, rerank_depth=12)
    for kw in ({}, {"rerank": 0}, {"nprobe": 8}):
        d_x, i_x = pq.topk(Q, 5, scan_impl="xla", **kw)
        d_p, i_p = pq.topk(Q, 5, scan_impl="pallas", **kw)
        np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_p))
        np.testing.assert_array_equal(np.asarray(d_x), np.asarray(d_p))


def test_ivfpq_pallas_host_store_bit_identical(data):
    L, G, Q = data
    pq = IVFPQIndex.build(L, jnp.asarray(G), n_clusters=8, nprobe=3,
                          n_subspaces=5, rerank_depth=12, store="host")
    d_x, i_x = pq.topk(Q, 5, scan_impl="xla")
    d_p, i_p = pq.topk(Q, 5, scan_impl="pallas")
    np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_p))
    np.testing.assert_array_equal(np.asarray(d_x), np.asarray(d_p))


def test_ivfpq_pallas_full_probe_matches_exact_oracle(data):
    # full probe + full-depth rerank under the kernel path must equal
    # the exact scan — the same oracle the XLA path pins
    L, G, Q = data
    exact = ExactIndex.build(L, jnp.asarray(G))
    pq = IVFPQIndex.build(L, jnp.asarray(G), n_clusters=8, nprobe=8,
                          n_subspaces=5, rerank_depth=len(G))
    _, i_e = exact.topk(Q, 5)
    _, i_p = pq.topk(Q, 5, nprobe=8, rerank=len(G), scan_impl="pallas")
    np.testing.assert_array_equal(np.asarray(i_e), np.asarray(i_p))


def test_build_default_flows_to_topk(data):
    L, G, Q = data
    pq = IVFPQIndex.build(L, jnp.asarray(G), n_clusters=8, nprobe=3,
                          n_subspaces=5, scan_impl="pallas")
    assert pq.scan_impl == "pallas"
    d_p, i_p = pq.topk(Q, 5)                 # default = build setting
    d_x, i_x = pq.topk(Q, 5, scan_impl="xla")
    np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_p))
    np.testing.assert_array_equal(np.asarray(d_x), np.asarray(d_p))


def test_falsy_scan_impl_rejected_everywhere(data):
    L, G, Q = data
    ivf = IVFIndex.build(L, jnp.asarray(G), n_clusters=8, nprobe=3)
    pq = IVFPQIndex.build(L, jnp.asarray(G), n_clusters=8, nprobe=3,
                          n_subspaces=5)
    for bad in ("", 0, "kernel"):
        with pytest.raises(ValueError, match="scan_impl"):
            IVFIndex.build(L, jnp.asarray(G), n_clusters=8,
                           scan_impl=bad)
        with pytest.raises(ValueError, match="scan_impl"):
            IVFPQIndex.build(L, jnp.asarray(G), n_clusters=8,
                             n_subspaces=5, scan_impl=bad)
        with pytest.raises(ValueError, match="scan_impl"):
            ivf.topk(Q, 5, scan_impl=bad)
        with pytest.raises(ValueError, match="scan_impl"):
            pq.topk(Q, 5, scan_impl=bad)
    assert "auto" in SCAN_IMPLS and len(SCAN_IMPLS) == 3


def test_mutable_compaction_preserves_scan_impl(data):
    L, G, _ = data
    mut = MutableIndex.build(L, G, base="ivfpq", n_clusters=8, nprobe=3,
                             n_subspaces=5, scan_impl="pallas",
                             auto_compact_delta=0.0,
                             auto_compact_dead=0.0)
    assert mut.scan_impl == "pallas"
    rng = np.random.RandomState(1)
    mut.upsert(rng.randn(4, G.shape[1]).astype(np.float32))
    mut.delete(mut.live_ids()[:2])
    assert mut.compact()
    assert mut.base.scan_impl == "pallas"     # headroom fold
    # spill path (rebuild) keeps it too
    mut.upsert(rng.randn(2 * len(G), G.shape[1]).astype(np.float32))
    assert mut.compact()
    assert mut.base.scan_impl == "pallas"
    assert mut.n_rebuilds >= 1


def test_snapshot_roundtrip_preserves_scan_impl(tmp_path, data):
    L, G, Q = data
    for build in (
            lambda: IVFIndex.build(L, jnp.asarray(G), n_clusters=8,
                                   nprobe=3, scan_impl="pallas"),
            lambda: IVFPQIndex.build(L, jnp.asarray(G), n_clusters=8,
                                     nprobe=3, n_subspaces=5,
                                     scan_impl="pallas")):
        index = build()
        path = str(tmp_path / type(index).__name__)
        save_index(index, path)
        loaded = load_index(path)
        assert loaded.scan_impl == "pallas"
        d0, i0 = index.topk(Q, 5)
        d1, i1 = loaded.topk(Q, 5)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


def test_mutable_topk_forwards_scan_impl(data):
    L, G, Q = data
    mut = MutableIndex.build(L, G, base="ivfpq", n_clusters=8, nprobe=3,
                             n_subspaces=5, auto_compact_delta=0.0,
                             auto_compact_dead=0.0)
    mut.upsert(np.random.RandomState(2)
               .randn(3, G.shape[1]).astype(np.float32))
    d_x, i_x = mut.topk(Q, 5, scan_impl="xla")
    d_p, i_p = mut.topk(Q, 5, scan_impl="pallas")
    np.testing.assert_array_equal(i_x, i_p)
    np.testing.assert_array_equal(d_x, d_p)
    with pytest.raises(ValueError, match="scan_impl"):
        mut.topk(Q, 5, scan_impl="")
