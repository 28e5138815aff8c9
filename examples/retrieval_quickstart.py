"""Retrieval quickstart: train a metric, index a gallery, query neighbors.

Run:  PYTHONPATH=src python examples/retrieval_quickstart.py

The end product of DML training is only realized at query time: nearest
neighbors under M = L^T L. This example learns L on pair constraints
(paper Eq. 4), pre-projects a gallery once (ExactIndex), and shows that
top-k neighbors under the learned metric are far more class-pure than
Euclidean neighbors on the same data. A low-rank detour trains a
rectangular (8, 64) factor (`l_rank`) on the same pairs and serves the
same gallery from ~7x less projected memory at near-square class
purity. It then swaps the same engine onto
the cluster-pruned IVFIndex and shows near-identical neighbors while
scanning a fraction of the gallery per query, and onto the
product-quantized IVFPQIndex — the same probes over uint8 residual codes
(~8x less segment memory), with an exact re-rank recovering the
quantization loss. Finally it walks the
mutable-gallery lifecycle: stream rows in and out (MutableIndex), compact
the delta, snapshot to disk and reload bit-for-bit, and hot-swap the
metric — starting from the identity (Euclidean) factor and swapping in
the trained L without rebuilding from raw data, the trainer -> server
loop.
"""

import tempfile

import jax.numpy as jnp
import numpy as np

from repro.core import dml
from repro.core.ps.sync import PSConfig
from repro.core.ps.trainer import DMLTrainConfig, train_dml_distributed
from repro.data import pairs as pairdata
from repro.serve import (ExactIndex, IVFIndex, IVFPQIndex, MutableIndex,
                         RetrievalEngine, load_index, recall_at_k,
                         save_index)


def purity(labels, query_labels, neighbor_ids):
    """Mean fraction of retrieved neighbors sharing the query's class."""
    return float(np.mean(labels[neighbor_ids] == query_labels[:, None]))


def train(dml_cfg, train_pairs):
    """Eq. 4 on one worker: 300 steps of 512 pairs."""
    cfg = DMLTrainConfig(dml=dml_cfg, ps=PSConfig(n_workers=1, seed=0),
                         batch_size=512, steps=300, lr=2e-2)
    return train_dml_distributed(cfg, train_pairs)


def main():
    # class signal in a small subspace, Euclidean-dominating noise elsewhere
    cfg = pairdata.PairDatasetConfig(
        n_samples=4000, feat_dim=64, n_classes=8, kind="noisy_subspace",
        noise=0.5, seed=0)
    feats, labels = pairdata.make_features(cfg)
    train_pairs, _ = pairdata.train_eval_split(
        cfg, n_train_sim=4000, n_train_dis=4000,
        n_eval_sim=500, n_eval_dis=500)

    dml_cfg = dml.DMLConfig(feat_dim=64, proj_dim=32)
    L, history = train(dml_cfg, train_pairs)
    print(f"objective: {history[0]['loss']:.3f} -> {history[-1]['loss']:.3f}")

    # gallery = first 3500 points; queries = the held-out tail
    gallery, g_labels = feats[:3500], labels[:3500]
    queries, q_labels = feats[3500:], labels[3500:]

    # amortize the metric once, then serve
    index = ExactIndex.build(L, jnp.asarray(gallery))
    engine = RetrievalEngine(index, k_top=10)
    _, nbrs = engine.search(queries)
    p_learned = purity(g_labels, q_labels, nbrs)

    # Euclidean baseline = identity metric over the same gallery
    eye = jnp.eye(64, dtype=jnp.float32)
    _, nbrs_e = RetrievalEngine(ExactIndex.build(eye, jnp.asarray(gallery)),
                                k_top=10).search(queries)
    p_euclid = purity(g_labels, q_labels, nbrs_e)

    print(f"neighbor class purity@10: learned {p_learned:.3f} "
          f"vs euclidean {p_euclid:.3f} (chance {1 / 8:.3f})")
    print(f"engine: {engine.stats()}")
    assert p_learned > p_euclid

    # --- low-rank L: same contract, a fraction of the memory -------------
    # l_rank trains a genuinely rectangular (d', D) factor directly on
    # the pair objective (M = L^T L is PSD at any rank — no projection
    # step), and every projected artifact downstream is sized d', so the
    # serving gallery shrinks by ~D/d'
    from repro.obs import index_memory

    L_sq, _ = train(dml.DMLConfig(feat_dim=64, l_rank=64), train_pairs)
    L_lr, _ = train(dml.DMLConfig(feat_dim=64, l_rank=8), train_pairs)
    idx_sq = ExactIndex.build(L_sq, jnp.asarray(gallery))
    idx_lr = ExactIndex.build(L_lr, jnp.asarray(gallery))
    mem_sq = index_memory(idx_sq)["gallery"]
    mem_lr = index_memory(idx_lr)["gallery"]
    _, nbrs_sq = RetrievalEngine(idx_sq, k_top=10).search(queries)
    _, nbrs_lr = RetrievalEngine(idx_lr, k_top=10).search(queries)
    r_lr = recall_at_k(nbrs_lr, nbrs_sq)
    p_lr = purity(g_labels, q_labels, nbrs_lr)
    print(f"low-rank L {tuple(np.shape(L_lr))} vs square "
          f"{tuple(np.shape(L_sq))}: projected gallery "
          f"{mem_lr / 1e3:.0f} kB vs {mem_sq / 1e3:.0f} kB "
          f"({mem_sq / mem_lr:.1f}x smaller), recall@10 vs square-L "
          f"neighbors {r_lr:.3f}, purity {p_lr:.3f}")
    assert mem_sq / mem_lr >= 4.0       # d' = D/8 -> ~7x measured
    assert p_lr > p_euclid              # rank 8 still beats Euclidean

    # same engine API, cluster-pruned backend: scan nprobe of n_clusters
    # gallery segments per query instead of all 3500 rows
    ivf = IVFIndex.build(L, jnp.asarray(gallery), n_clusters=16, nprobe=4)
    _, nbrs_ivf = RetrievalEngine(ivf, k_top=10).search(queries)
    recall = recall_at_k(nbrs_ivf, nbrs)
    p_ivf = purity(g_labels, q_labels, nbrs_ivf)
    print(f"ivf (nprobe {ivf.nprobe}/{ivf.n_clusters}, <= "
          f"{ivf.nprobe * ivf.cap} of {ivf.size} rows/query): "
          f"recall@10 vs exact {recall:.3f}, purity {p_ivf:.3f}")
    assert recall > 0.8

    # --- product-quantized segments: same probes, ~8x fewer bytes --------
    # each scanned row is n_subspaces uint8 codes (of its residual to the
    # cluster centroid) + one f32, scored via per-query ADC lookup tables;
    # the top rerank_depth candidates re-score exactly at full precision
    pq = IVFPQIndex.build(L, jnp.asarray(gallery), n_clusters=16,
                          nprobe=4, n_subspaces=8, bits=8,
                          rerank_depth=30)
    ivf_bytes = ivf.gp_pad.nbytes + ivf.gn_pad.nbytes
    pq_bytes = pq.codes_pad.nbytes + pq.t_pad.nbytes
    print(f"ivfpq segment memory: {pq_bytes / 1e3:.0f} kB vs IVF "
          f"{ivf_bytes / 1e3:.0f} kB ({ivf_bytes / pq_bytes:.1f}x "
          f"smaller; {pq.pq.code_bytes} code bytes/row)")
    _, nbrs_raw = pq.topk(queries, 10, rerank=0)       # raw ADC order
    _, nbrs_rr = pq.topk(queries, 10)                  # + exact rerank
    r_raw = recall_at_k(np.asarray(nbrs_raw), nbrs)
    r_rr = recall_at_k(np.asarray(nbrs_rr), nbrs)
    print(f"ivfpq recall@10 vs exact: {r_raw:.3f} raw ADC -> {r_rr:.3f} "
          f"with rerank {pq.rerank_depth} (quantization error recovered; "
          f"remaining loss is probe-limited, same as IVF)")
    assert r_rr >= r_raw and r_rr > 0.8

    # --- mutable gallery: stream rows, compact, snapshot, hot-swap -------
    # start from the identity metric (= Euclidean serving) and keep the
    # raw rows so the trained L can be swapped in later without touching
    # the original feature store
    mut = MutableIndex.build(eye, gallery, base="exact", retain_raw=True)
    live_engine = RetrievalEngine(mut, k_top=10)

    new_ids = mut.upsert(queries[:100])         # tail rows join the gallery
    mut.delete(np.arange(50))                   # first 50 retire
    d_self, n_self = live_engine.search(queries[0])
    print(f"mutable: size {mut.size} (delta {mut.delta_rows}, "
          f"tombstones {mut.tombstones}), upserted row is its own "
          f"nearest neighbor: {n_self[0] == new_ids[0]} "
          f"(dist {d_self[0]:.2g})")
    assert n_self[0] == new_ids[0]              # dist 0 to itself
    assert not np.isin(np.arange(50), n_self).any(), "deleted row served"

    mut.compact()                               # delta folds into the base
    _, n_compacted = live_engine.search(queries[0])
    assert np.array_equal(n_compacted, n_self)  # same answers, zero delta

    with tempfile.TemporaryDirectory() as snap:
        save_index(mut, snap)                   # restartable: npz + manifest
        restored = load_index(snap, expect_L=eye)
        _, n_restored = RetrievalEngine(restored, k_top=10) \
            .search(queries[0])
        assert np.array_equal(n_restored, n_self), "snapshot drifted"
        print(f"snapshot round-trip: top-k identical, "
              f"version {restored.version}")

    # the trainer -> server loop: swap the trained metric in, in place.
    # external ids are stable, so one label table covers original gallery
    # rows (ids 0..3499) and the upserted ones (ids 3500..3599)
    labels_by_id = np.concatenate([g_labels, q_labels[:100]])
    q_rest, ql_rest = queries[100:], q_labels[100:]
    _, nbrs_eye = live_engine.search(q_rest)
    p_eye = purity(labels_by_id, ql_rest, nbrs_eye)
    mut.swap_metric(L)                          # re-projects retained raw
    _, nbrs_swap = live_engine.search(q_rest)
    p_swap = purity(labels_by_id, ql_rest, nbrs_swap)
    print(f"metric hot-swap: purity@10 {p_eye:.3f} (euclidean) -> "
          f"{p_swap:.3f} (trained L), no raw-gallery rebuild")
    assert p_swap > p_eye


if __name__ == "__main__":
    main()
