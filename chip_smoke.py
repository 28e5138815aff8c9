"""Bring-up smoke test: Eq. 4 training and k-NN serving on a TPU.

Drives the system's main path once at the paper's ImageNet-1M widths
(Xie & Xing 2014 Table 1; ``configs/dml_paper.IMNET_1M``: d_in 21,504,
d_out 1,000, 1,000 pairs per step) through the entry points a user
calls, and checks what comes out. Run from the repository root:

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips: the distributed paths only

One chip:
  train   ``train_dml_distributed`` (1 worker, BSP) for 20 steps on 20,480
          ``llc_like`` rows made on the device from a seed; the objective
          must stay finite and fall. One batch then runs through the fused
          Pallas pair loss, compiled, and its value and grad w.r.t. L are
          checked against ``dml_pair_loss_reference``.
  serve   204,800 gallery rows at d_in, made and projected (``project_
          gallery``) under the trained L in chunks: a 0.82 GB f32
          projected gallery. ExactIndex (the reference), IVFIndex and
          IVFPQIndex with ``scan_impl="auto"`` (must resolve to the Pallas
          kernels) each serve 512 raw 21,504-d queries through
          ``RequestScheduler -> RetrievalEngine -> MetricIndex``. Checks:
          kernel vs XLA ids on the same index (IVF and exact: equal up to
          f32 ties, see ``tie_mismatches``; IVFPQ: bit-identical),
          and recall@10 of IVF and IVFPQ against the exact scan.

Four chips (``--chips 4``), and nothing else:
  train   BSP over 4 workers (1,000 pairs each) against one chip training
          on the union of the same batches (4,000 pairs): the objectives
          and the final L must agree.
  serve   ExactIndex and IVFIndex sharded over ``make_local_mesh(data=4)``
          against the unsharded builds (XLA scan): ids equal up to f32
          ties.

Any failed check exits non-zero; no phase catches a failure and carries
on. Without a TPU the script exits non-zero before any phase runs (there
is no CPU fallback). The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.dml_paper import IMNET_1M  # noqa: E402
from repro.core.ps import sync  # noqa: E402
from repro.core.ps.trainer import (DMLTrainConfig,  # noqa: E402
                                   train_dml_distributed)
from repro.data import pairs as pairdata  # noqa: E402
from repro.kernels._dispatch import default_interpret  # noqa: E402
from repro.kernels.dml_pair import (dml_pair_loss_fused,  # noqa: E402
                                    dml_pair_loss_reference)
from repro.kernels.metric_topk import project_gallery  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.serve import (ExactIndex, IVFIndex, IVFPQIndex,  # noqa: E402
                         RequestScheduler, RetrievalEngine, scan)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke runs at. The defaults are IMNET_1M's widths; the
    depth (rows, steps) is cut to fit one chip and the time limit."""
    d_in: int = IMNET_1M.dml.feat_dim           # 21,504
    d_out: int = IMNET_1M.dml.proj_dim          # 1,000
    n_classes: int = IMNET_1M.n_classes         # 1,000
    pairs_per_step: int = IMNET_1M.batch_size   # 1,000 per worker
    train_rows: int = 20_480
    n_pairs: int = 50_000                       # per kind (S and D)
    steps: int = 20
    lr: float = 2.0
    chunk_rows: int = 4_096
    gallery_rows: int = 204_800                 # 0.82 GB projected f32
    queries: int = 512
    k: int = 10
    batch: int = 64                             # scheduler batch = bucket
    # one IVF segment per class (1,000 classes of ~205 rows): at 256
    # clusters k-means lumps several classes per cluster and the capped
    # segments spill ~8% of the rows to far clusters, which cost recall@10
    # (0.83 at nprobe 16 in a reduced-width CPU rehearsal; 1.0 at 1,024)
    n_clusters: int = 1024
    nprobe: int = 16
    n_subspaces: int = 20
    # exact rerank of the ADC top 256: one class's ~205 rows. 20 x 8-bit
    # codes of 1,000-d rows rank classes apart but not rows within one;
    # at rerank 100 recall@10 was 0.79 on the chip
    rerank: int = 256
    seed: int = 0


# check limits, each with the reason it is what it is
LOSS_RTOL = 1e-4        # fused vs reference value: both sum 21,504-term
                        # f32 products at full precision, in other orders
GRAD_RTOL = 1e-3        # grad, max |diff| / max |ref|: the backward
                        # matmuls contract over the 1,000-pair batch too
RECALL_FLOOR = {"ivf": 0.9, "ivfpq": 0.9}   # 1.0 and 1.0 in a CPU run at
                                            # d_in 2,048, 205 rows/class.
                                            # The IVFPQ floor is a rerank
                                            # floor: rerank 256 > a class's
                                            # rows (the codes' own recall,
                                            # rerank 0, is printed only)
TIE_RTOL = 1e-5         # two paths' f32 roundings of one distance, as a
                        # fraction of ||qp||^2 + ||gp||^2 (Scale): ~84
                        # ulps of the operands the k=1,000-term cross
                        # product is summed from in another order; a
                        # wrong row is off by a whole class gap (~40x the
                        # near-neighbour distance in the smoke's data)
BSP_RTOL = 1e-3         # 4-worker pmean vs one 4,000-pair mean: same
                        # gradient, summed in another order


def log(msg: str) -> None:
    print(msg, flush=True)


class CheckFailed(RuntimeError):
    pass


def check(ok, msg: str) -> None:
    """A failed check ends the run (raises; survives python -O)."""
    if not ok:
        raise CheckFailed(msg)


class Timer:
    def __init__(self, what: str):
        self.what = what

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            log(f"  [{self.what}: {time.perf_counter() - self.t0:.1f} s]")


def data_cfg(sz: Sizes) -> pairdata.PairDatasetConfig:
    return pairdata.PairDatasetConfig(
        n_samples=sz.gallery_rows, feat_dim=sz.d_in,
        n_classes=sz.n_classes, kind="llc_like", seed=sz.seed)


def make_rows(sz: Sizes, first_chunk: int, n_rows: int):
    """n_rows llc_like rows from chunk ids first_chunk.. (on device)."""
    cfg = data_cfg(sz)
    xs, ys = [], []
    for c in range(-(-n_rows // sz.chunk_rows)):
        x, y = pairdata.llc_like_chunk(cfg, first_chunk + c,
                                       min(sz.chunk_rows, n_rows))
        xs.append(x)
        ys.append(y)
    return jnp.concatenate(xs)[:n_rows], jnp.concatenate(ys)[:n_rows]


# chunk ids: training rows, then gallery rows, then queries
def _gallery_chunk0(sz: Sizes) -> int:
    return -(-sz.train_rows // sz.chunk_rows)


def _query_chunk(sz: Sizes) -> int:
    return _gallery_chunk0(sz) + -(-sz.gallery_rows // sz.chunk_rows)


def pair_source(sz: Sizes):
    feats, labels = make_rows(sz, 0, sz.train_rows)
    idx = pairdata.sample_pair_indices(np.asarray(labels), sz.n_pairs,
                                       sz.n_pairs, seed=sz.seed)
    return pairdata.IndexPairSource(feats, idx)


class UnionSource:
    """One worker's stream = the concatenated batches that ``parts``
    workers would each draw from ``src`` — the single-chip twin of a
    BSP run over ``parts`` workers."""

    def __init__(self, src, parts: int):
        self.src, self.parts = src, parts

    def worker_streams(self, n_workers: int, batch_size: int, seed: int):
        check(n_workers == 1, f"a union feeds one worker, not {n_workers}")
        streams = self.src.worker_streams(self.parts,
                                          batch_size // self.parts, seed)

        def union():
            while True:
                bs = [next(s) for s in streams]
                yield {k: jnp.concatenate([b[k] for b in bs]) for k in bs[0]}

        return [union()]


def train(sz: Sizes, source, n_workers: int, batch_size: int, mesh=None):
    dml = dataclasses.replace(IMNET_1M.dml, feat_dim=sz.d_in,
                              proj_dim=sz.d_out)
    cfg = DMLTrainConfig(
        dml=dml, ps=sync.PSConfig(n_workers=n_workers, sync="bsp",
                                  seed=sz.seed),
        batch_size=batch_size, steps=sz.steps, lr=sz.lr, log_every=1)
    L, hist = train_dml_distributed(cfg, source, mesh=mesh)
    losses = np.array([h["loss"] for h in hist])
    return jax.block_until_ready(L), losses


def check_objective(losses: np.ndarray, what: str) -> None:
    log(f"  {what} objective: " + " ".join(f"{x:.4g}" for x in losses))
    check(np.isfinite(losses).all(), f"{what}: non-finite objective")
    head, tail = losses[:5].mean(), losses[-5:].mean()
    check(tail < 0.9 * head,
          f"{what}: objective did not fall ({head:.4g} -> {tail:.4g})")


# -- one chip -----------------------------------------------------------------

def phase_train(sz: Sizes):
    log(f"phase train: IMNET_1M widths d_in={sz.d_in} d_out={sz.d_out}, "
        f"{sz.pairs_per_step} pairs/step, {sz.steps} steps, "
        f"{sz.train_rows} llc_like rows")
    with Timer("rows + pair sampling"):
        source = pair_source(sz)
    with Timer("train_dml_distributed (1 worker, BSP; incl. compile)"):
        L, losses = train(sz, source, 1, sz.pairs_per_step)
    check_objective(losses, "train")
    check(L.shape == (sz.d_out, sz.d_in), f"L shape {L.shape}")
    return L, source


def phase_kernel(sz: Sizes, L, source) -> None:
    log("phase kernel: fused Pallas pair loss vs reference, one batch")
    batch = next(source.worker_streams(1, sz.pairs_per_step, sz.seed + 7)[0])
    args = (L, batch["xs"], batch["ys"], batch["sim"])
    fused = jax.jit(jax.value_and_grad(dml_pair_loss_fused))
    ref = jax.jit(jax.value_and_grad(dml_pair_loss_reference))
    with Timer("compile + run fused and reference"):
        (v_k, g_k), (v_r, g_r) = jax.block_until_ready((fused(*args),
                                                        ref(*args)))
    check("tpu_custom_call" in fused.lower(*args).as_text(),
          "the pair loss did not lower to a compiled TPU kernel")
    v_err = abs(float(v_k) - float(v_r)) / abs(float(v_r))
    g_err = float(jnp.max(jnp.abs(g_k - g_r)) / jnp.max(jnp.abs(g_r)))
    log(f"  loss fused {float(v_k):.7g} ref {float(v_r):.7g} "
        f"rel err {v_err:.3g} (limit {LOSS_RTOL}); grad max rel err "
        f"{g_err:.3g} (limit {GRAD_RTOL})")
    check(v_err <= LOSS_RTOL, f"pair loss value rel err {v_err}")
    check(g_err <= GRAD_RTOL, f"pair loss grad rel err {g_err}")


def project_rows(sz: Sizes, L, first_chunk: int, n_rows: int):
    """Make and project n_rows gallery rows chunk by chunk: the raw
    (n_rows, d_in) matrix never exists at once."""
    cfg = data_cfg(sz)
    gps, gns = [], []
    for c in range(n_rows // sz.chunk_rows):
        x, _ = pairdata.llc_like_chunk(cfg, first_chunk + c, sz.chunk_rows)
        gp, gn = project_gallery(L, x)
        gps.append(gp)
        gns.append(gn)
    return jax.block_until_ready((jnp.concatenate(gps),
                                  jnp.concatenate(gns)))


def serve(index, sz: Sizes, queries: np.ndarray, backend: str = "xla"):
    """Serve every query through RequestScheduler -> RetrievalEngine ->
    index. Returns (dists, ids) in submission order."""
    engine = RetrievalEngine(index, k_top=sz.k, backend=backend,
                             buckets=(sz.batch,), cache_size=0)
    with Timer(f"{type(index).__name__} warmup (compile)"):
        engine.warmup()
    front = RequestScheduler(engine, max_batch=sz.batch, max_wait_ms=5.0,
                             degrade=False)
    with Timer(f"{len(queries)} queries through the scheduler"):
        futs = [front.submit(q, priority="batch", deadline_s=600.0)
                for q in queries]
        res = [f.result(timeout=600) for f in futs]
        check(front.close(), "scheduler workers did not exit")
    done = engine.stats()["frontend"]["classes"]["batch"]["completed"]
    check(done == len(queries), f"{done} of {len(queries)} completed")
    return (np.stack([r[0] for r in res]), np.stack([r[1] for r in res]))


def direct(index, sz: Sizes, queries: np.ndarray, **kw):
    """The same queries straight through index.topk, one batch at a time
    (the scheduler's batch shape, so no new bucket compiles)."""
    out = [index.topk(jnp.asarray(queries[s:s + sz.batch]), sz.k, **kw)
           for s in range(0, len(queries), sz.batch)]
    return (np.concatenate([np.asarray(d) for d, _ in out]),
            np.concatenate([np.asarray(i) for _, i in out]))


class Scale:
    """Per query, the magnitude the f32 rounding of its distances scales
    with: ||qp||^2 + ||gp||^2 of the factored distance
    ``||qp||^2 + ||gp||^2 - 2 qp.gp``, whose cancellation leaves the
    (much smaller) distance to a near neighbour."""

    def __init__(self, L, queries: np.ndarray, gn):
        qp = scan.project_queries(L, jnp.asarray(queries))
        self.qn = np.asarray(jnp.sum(jnp.square(qp), axis=1), np.float64)
        self.gn = np.asarray(gn, np.float64)

    def tol(self, ids_a, ids_b) -> np.ndarray:
        """(Nq,) tie tolerance for two id lists (-1 pads read row 0)."""
        ids = np.concatenate([ids_a, ids_b], axis=1)
        return TIE_RTOL * (self.qn + self.gn[np.maximum(ids, 0)].max(axis=1))


def tie_mismatches(ids_a, d_a, ids_b, d_b, tol):
    """Compare two (Nq, k) neighbor lists that should be the same answer
    computed two ways. Returns (n_differ, n_unexplained, worst): ranks
    whose ids differ, those of them that no f32 tie explains, and the
    largest gap a differing rank shows, as a multiple of ``tol``.

    Two paths that sum the same f32 terms in a different order (a shard
    vs the whole gallery, a Pallas tile vs an XLA fusion) compute one
    row's distance a few roundings apart, and can put two nearly
    equidistant rows in opposite order. A differing rank is explained
    when its id sits in the other list at a distance within ``tol`` of
    its own, or is missing from the other list but lies within ``tol``
    of that list's k-th distance. ``tol`` is an absolute distance, a
    scalar or one per query (Nq,)."""
    ia, ib = np.asarray(ids_a), np.asarray(ids_b)
    da, db = np.asarray(d_a, np.float64), np.asarray(d_b, np.float64)
    tol = np.broadcast_to(np.asarray(tol, np.float64), (ia.shape[0],))
    n_differ = n_bad = 0
    worst = 0.0
    for q in range(ia.shape[0]):
        for r in np.flatnonzero(ia[q] != ib[q]):
            n_differ += 1
            gap = 0.0
            for ids, d, oi, od in ((ia, da, ib, db), (ib, db, ia, da)):
                at = np.flatnonzero(oi[q] == ids[q, r])
                gap = max(gap, abs(od[q, at[0] if len(at) else -1]
                                   - d[q, r]))
            n_bad += gap > tol[q]
            worst = max(worst, gap / tol[q])
    return n_differ, n_bad, worst


def same_ids(what: str, a, b, scale: Scale) -> None:
    """a, b: (dists, ids). Ids equal rank for rank up to f32 ties."""
    (da, ia), (db, ib) = a, b
    n_differ, n_bad, worst = tie_mismatches(ia, da, ib, db,
                                            scale.tol(ia, ib))
    exact = float(np.mean(np.all(ia == ib, axis=1)))
    d_err = float(np.max(np.abs(da - db) / np.maximum(np.abs(db), 1.0)))
    log(f"  {what}: {exact:.4f} of queries with identical id lists, "
        f"{n_differ} differing ranks, {n_bad} not explained by an f32 tie "
        f"(largest gap {worst:.3g} of the tie tolerance); max dist rel "
        f"diff {d_err:.3g}")
    check(n_bad == 0, f"{what}: {n_bad} ranks differ beyond f32 ties")


def phase_serve(sz: Sizes, L) -> None:
    log(f"phase serve: {sz.gallery_rows} gallery rows x {sz.d_in} raw, "
        f"{sz.queries} queries, k={sz.k}")
    with Timer("make + project gallery"):
        gp, gn = project_rows(sz, L, _gallery_chunk0(sz), sz.gallery_rows)
    log(f"  projected gallery {gp.shape} f32 = {gp.nbytes / 1e9:.3f} GB "
        f"on {next(iter(gp.devices())).device_kind}")
    check(gp.shape == (sz.gallery_rows, sz.d_out), f"gallery {gp.shape}")
    q, _ = make_rows(sz, _query_chunk(sz), sz.queries)
    queries = np.asarray(q)
    scale = Scale(L, queries, gn)

    exact = ExactIndex.from_projected(L, gp, gn)
    ex_kernel = serve(exact, sz, queries, backend="pallas")
    ex_xla = direct(exact, sz, queries, backend="xla")
    same_ids("exact, pallas kernel vs xla", ex_kernel, ex_xla, scale)

    with Timer("IVFIndex.build_projected"):
        ivf = IVFIndex.build_projected(L, gp, gn, n_clusters=sz.n_clusters,
                                       nprobe=sz.nprobe, seed=sz.seed)
    impl = scan.resolve_scan_impl(ivf.scan_impl)
    log(f"  ivf: {ivf.n_clusters} clusters, cap {ivf.cap}, nprobe "
        f"{ivf.nprobe}, scan_impl {ivf.scan_impl} -> {impl}")
    check(impl == "pallas", f"ivf auto scan resolved to {impl}")
    ivf_kernel = serve(ivf, sz, queries)
    same_ids("ivf, pallas kernel vs xla", ivf_kernel,
             direct(ivf, sz, queries, scan_impl="xla"), scale)

    with Timer("IVFPQIndex.build_projected"):
        pq = IVFPQIndex.build_projected(
            L, gp, gn, n_clusters=sz.n_clusters, nprobe=sz.nprobe,
            n_subspaces=sz.n_subspaces, bits=8, rerank_depth=sz.rerank,
            seed=sz.seed)
    check(scan.resolve_scan_impl(pq.scan_impl) == "pallas",
          "ivfpq auto scan did not resolve to pallas")
    pq_kernel = serve(pq, sz, queries)
    pq_xla = direct(pq, sz, queries, scan_impl="xla")
    same_d = np.array_equal(pq_kernel[0], pq_xla[0])
    same_i = np.array_equal(pq_kernel[1], pq_xla[1])
    log(f"  ivfpq ({sz.n_subspaces} x 8-bit codes, rerank {sz.rerank}), "
        f"pallas kernel vs xla: dists bit-identical {same_d}, ids "
        f"bit-identical {same_i}")
    check(same_d and same_i, "ivfpq kernel is not bit-identical to xla")

    for name, got in (("ivf", ivf_kernel), ("ivfpq", pq_kernel)):
        r = scan.recall_at_k(got[1], ex_xla[1])
        log(f"  recall@{sz.k} {name} vs exact: {r:.4f} "
            f"(floor {RECALL_FLOOR[name]})")
        check(r >= RECALL_FLOOR[name], f"{name} recall@{sz.k} {r}")
    adc = direct(pq, sz, queries, rerank=0)
    log(f"  recall@{sz.k} ivfpq ADC top-{sz.k} before rerank vs exact: "
        f"{scan.recall_at_k(adc[1], ex_xla[1]):.4f} (no floor)")


# -- four chips ---------------------------------------------------------------

def phase_train_4(sz: Sizes):
    log(f"phase train x4: BSP over 4 workers x {sz.pairs_per_step} pairs "
        f"vs one chip on the union ({4 * sz.pairs_per_step} pairs), "
        f"{sz.steps} steps")
    source = pair_source(sz)
    with Timer("4 workers"):
        L4, l4 = train(sz, source, 4, sz.pairs_per_step)
    with Timer("1 chip, union batches"):
        L1, l1 = train(sz, UnionSource(source, 4), 1,
                       4 * sz.pairs_per_step,
                       mesh=sync.make_worker_mesh(1))
    check_objective(l4, "4 workers")
    check_objective(l1, "1 chip")
    # the 4-worker L is replicated over the worker mesh: compare on host,
    # then hand serving a single-device copy
    L4, L1 = (jax.device_put(x, jax.devices()[0]) for x in (L4, L1))
    loss_err = float(np.max(np.abs(l4 - l1) / np.abs(l1)))
    L_err = float(jnp.max(jnp.abs(L4 - L1)) / jnp.max(jnp.abs(L1)))
    log(f"  objective max rel diff {loss_err:.3g}, final L max rel diff "
        f"{L_err:.3g} (limit {BSP_RTOL})")
    check(loss_err <= BSP_RTOL and L_err <= BSP_RTOL,
          f"4-worker BSP vs one chip: {loss_err}, {L_err}")
    return L4


def phase_serve_4(sz: Sizes, L) -> None:
    mesh = make_local_mesh(data=4)
    log(f"phase serve x4: {sz.gallery_rows} rows sharded over "
        f"{dict(mesh.shape)}")
    gp, gn = project_rows(sz, L, _gallery_chunk0(sz), sz.gallery_rows)
    q, _ = make_rows(sz, _query_chunk(sz), sz.queries)
    queries = np.asarray(q)
    scale = Scale(L, queries, gn)

    sharded = ExactIndex.from_projected(L, gp, gn, mesh=mesh)
    check(sharded.n_shards == 4, f"{sharded.n_shards} shards")
    single = ExactIndex.from_projected(L, gp, gn)
    same_ids("exact, 4 shards vs unsharded", serve(sharded, sz, queries),
             direct(single, sz, queries), scale)

    ivf_s = IVFIndex.build_projected(L, gp, gn, n_clusters=sz.n_clusters,
                                     nprobe=sz.nprobe, seed=sz.seed,
                                     mesh=mesh)
    ivf_1 = IVFIndex.build_projected(L, gp, gn, n_clusters=sz.n_clusters,
                                     nprobe=sz.nprobe, seed=sz.seed)
    check(ivf_s.n_shards == 4 and ivf_s.n_clusters == ivf_1.n_clusters,
          "sharded ivf layout differs from the unsharded build")
    impl = scan.resolve_scan_impl(ivf_s.scan_impl, sharded=True)
    log(f"  sharded ivf scan_impl {ivf_s.scan_impl} -> {impl}")
    same_ids("ivf, 4 shards vs unsharded (xla scan)",
             serve(ivf_s, sz, queries),
             direct(ivf_1, sz, queries, scan_impl="xla"), scale)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the paths that span four chips")
    args = ap.parse_args()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform}); "
                 f"this script does not fall back to the CPU")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")
    # every kernel resolves interpret=None here: compiled, not interpreted
    check(default_interpret() is False, "kernels would run interpreted")
    cache = enable_compile_cache()
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")
    sz = Sizes()
    check(sz.gallery_rows * sz.d_out * 4 >= 0.8e9, "gallery under 0.8 GB")
    t0 = time.perf_counter()
    if args.chips == 1:
        L, source = phase_train(sz)
        phase_kernel(sz, L, source)
        del source
        phase_serve(sz, L)
    else:
        L = phase_train_4(sz)
        phase_serve_4(sz, L)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
