"""Metric-retrieval serving launcher.

Run:  PYTHONPATH=src python -m repro.launch.serve_retrieval \
          [--gallery-size 20000] [--train-steps 200] [--requests 500]

Builds a class-structured gallery (data.pairs), optionally trains the
metric L on pair constraints, stands up the serving stack
(index -> RetrievalEngine -> MicroBatcher), fires single-query
traffic through the batcher, and reports QPS + latency percentiles +
neighbor class purity (fraction of returned neighbors sharing the query's
class — the quality the learned metric buys at serve time).

``--index exact`` scans the whole gallery (ExactIndex); ``--index ivf``
builds the cluster-pruned ANN index (IVFIndex) and scans only the
``--nprobe`` nearest of ``--n-clusters`` gallery segments per query;
``--index ivfpq`` additionally compresses the scanned segments to uint8
product-quantization codes (``--n-subspaces`` codes of ``--bits`` bits
per row, trained on residuals to the cluster centroids) scored by
ADC lookup tables, with the top ``--rerank-depth`` candidates re-scored
exactly against the full-precision store (``--pq-store host`` keeps that
store in RAM instead of device memory). ``--scan-impl`` picks the
segment-scan implementation for both ANN indexes — "auto" serves the
fused Pallas kernels (kernels/pq_adc, kernels/ivf_scan) on TPU and the
XLA scan elsewhere. ``--cache-size`` bounds the engine's hot-query LRU
(0 disables).

``--mutable`` wraps the index in a MutableIndex (streaming upserts /
deletes / compaction / metric hot-swap); ``--churn N`` then exercises N
upserts + N deletes after the traffic run and reports the lifecycle
counters. ``--snapshot-dir`` restarts without re-projecting: if the
directory holds a snapshot it is loaded (the manifest's L fingerprint is
checked against this run's metric), otherwise the freshly built index is
saved there. ``--warmup-ks`` pre-compiles extra k values so non-default
``k_top`` requests don't pay first-request jit. ``--mine N`` runs a
``HardPairMiner`` sweep for N anchors against the live engine after the
traffic run — mining shares the engine's jit cache/warmup and its QPS
shows up in the same ``stats()`` counters as serving traffic.

``--scheduler`` swaps the MicroBatcher front door for the traffic-shaped
``RequestScheduler``: traffic is submitted under a 70/20/10 interactive /
batch / mining class mix with per-class deadlines (``--deadline-ms``
overrides), bounded admission queues, and (unless ``--no-degrade``) the
adaptive quality ladder derived from the index's own knobs —
``--high/--low-watermark`` and ``--degrade/--restore-window-ms`` tune the
load controller's hysteresis. The run then reports per-class
counters/latency percentiles and the degradation transitions alongside
the usual engine stats.

With --data > 1 the gallery shards over the first N devices of the
default backend to exercise the sharded query path (exact and IVF;
incompatible with --mutable / --snapshot-dir, which are single-shard):
the real chips where there are at least N, else N forced host devices
on the CPU.

Observability: ``--metrics-out FILE`` writes the run's final
MetricsRegistry snapshot (render it with ``launch/metrics_report.py``),
``--trace-sample R`` samples request traces at rate R (deterministic),
and ``--trace-out FILE`` exports the sampled span trees as JSONL —
``benchmarks/check_obs.py`` schema-validates both files.
"""

from __future__ import annotations

import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--gallery-size", type=int, default=20000)
    ap.add_argument("--feat-dim", type=int, default=64)
    ap.add_argument("--proj-dim", type=int, default=32)
    ap.add_argument("--l-rank", type=int, default=None,
                    help="train a low-rank rectangular L with this many "
                         "rows (d_out); overrides --proj-dim. The whole "
                         "serving stack (projected gallery, PQ codes, "
                         "snapshots) shrinks by feat_dim/l_rank")
    ap.add_argument("--n-classes", type=int, default=16)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--train-steps", type=int, default=200,
                    help="0 = random L (no learned metric)")
    ap.add_argument("--requests", type=int, default=500)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--backend", choices=["xla", "pallas"], default="xla")
    ap.add_argument("--index", choices=["exact", "ivf", "ivfpq"],
                    default="exact")
    ap.add_argument("--n-clusters", type=int, default=64,
                    help="ivf/ivfpq: gallery segments (ivf rounds up to "
                         "a multiple of the shard count)")
    ap.add_argument("--nprobe", type=int, default=8,
                    help="ivf/ivfpq: clusters scanned per query")
    ap.add_argument("--n-subspaces", type=int, default=8,
                    help="ivfpq: uint8 codes per row (code bytes/row)")
    ap.add_argument("--bits", type=int, default=8,
                    help="ivfpq: log2 codewords per subspace (1..8)")
    ap.add_argument("--rerank-depth", type=int, default=50,
                    help="ivfpq: ADC candidates re-scored exactly per "
                         "query (0 serves raw ADC distances)")
    ap.add_argument("--pq-store", choices=["device", "host"],
                    default="device",
                    help="ivfpq: where the full-precision rerank rows "
                         "live (host = RAM only, saves device memory)")
    ap.add_argument("--scan-impl", choices=["auto", "xla", "pallas"],
                    default="auto",
                    help="ivf/ivfpq: segment-scan implementation — auto "
                         "picks the fused Pallas kernel on TPU and XLA "
                         "elsewhere; pallas forces the kernel (interpret "
                         "mode off TPU, correctness only)")
    ap.add_argument("--cache-size", type=int, default=1024,
                    help="engine hot-query LRU entries (0 disables)")
    ap.add_argument("--mutable", action="store_true",
                    help="wrap the index in a MutableIndex (retains raw "
                         "features for metric hot-swap)")
    ap.add_argument("--churn", type=int, default=0,
                    help="with --mutable: upsert+delete this many rows "
                         "after the traffic run")
    ap.add_argument("--snapshot-dir", default=None,
                    help="load the index from this snapshot if present, "
                         "else save the built index there")
    ap.add_argument("--warmup-ks", default=None,
                    help="comma-separated extra k values to pre-compile "
                         "(e.g. 5,20); --k is always included")
    ap.add_argument("--mine", type=int, default=0,
                    help="after the traffic run, mine hard pairs for "
                         "this many anchors against the live serving "
                         "engine (shares its jit cache and stats) and "
                         "report yield + mining QPS")
    ap.add_argument("--data", type=int, default=1,
                    help=">1 shards the gallery over the data axis of "
                         "that many devices (chips when present, else "
                         "forced host devices)")
    ap.add_argument("--scheduler", action="store_true",
                    help="serve through the traffic-shaped "
                         "RequestScheduler (priority classes, deadlines, "
                         "adaptive degradation) instead of the plain "
                         "MicroBatcher")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="scheduler: per-request deadline override in ms "
                         "(default: each class's own deadline)")
    ap.add_argument("--no-degrade", action="store_true",
                    help="scheduler: disable the adaptive quality ladder "
                         "(admission control + deadlines only)")
    ap.add_argument("--high-watermark", type=int, default=32,
                    help="scheduler: queue depth that starts the "
                         "degrade window")
    ap.add_argument("--low-watermark", type=int, default=4,
                    help="scheduler: queue depth that starts the "
                         "restore window")
    ap.add_argument("--degrade-window-ms", type=float, default=50.0,
                    help="scheduler: sustained pressure before stepping "
                         "the ladder down")
    ap.add_argument("--restore-window-ms", type=float, default=500.0,
                    help="scheduler: sustained drain before stepping "
                         "back up")
    ap.add_argument("--tenants", type=int, default=0,
                    help="after the main run, stand up a TenantRouter "
                         "serving this many metrics over ONE shared raw "
                         "gallery (tenant 0 serves this run's L; the "
                         "rest get seeded low-rank factors) and report "
                         "per-tenant purity + the shared-gallery memory "
                         "ratio vs independent stacks")
    ap.add_argument("--shadow", action="store_true",
                    help="with --tenants: register this run's L as a "
                         "shadow arm behind tenant 1, mirror the tenant "
                         "traffic through it, report overlap/latency "
                         "deltas, and promote it live")
    ap.add_argument("--metrics-out", default=None,
                    help="write the final MetricsRegistry snapshot (JSON) "
                         "here — launch/metrics_report.py renders it")
    ap.add_argument("--trace-out", default=None,
                    help="write sampled request traces here as JSONL "
                         "(one span tree per line)")
    ap.add_argument("--trace-sample", type=float, default=0.0,
                    help="trace sampling rate in [0, 1] (deterministic: "
                         "rate 0.25 samples every 4th request)")
    args = ap.parse_args()
    if not 0.0 <= args.trace_sample <= 1.0:
        ap.error(f"--trace-sample must be in [0, 1], got "
                 f"{args.trace_sample}")
    if args.shadow and args.tenants < 2:
        ap.error("--shadow needs --tenants >= 2 (tenant 1 hosts the arm)")
    if args.tenants and args.data > 1:
        ap.error("--tenants is single-shard (incompatible with "
                 "--data > 1)")
    if args.index in ("ivf", "ivfpq") and args.backend == "pallas":
        ap.error(f"--index {args.index} only supports --backend xla (the "
                 "fused pallas kernel serves the exact full-scan path)")
    if args.data > 1 and (args.mutable or args.snapshot_dir):
        ap.error("--mutable / --snapshot-dir are single-shard "
                 "(incompatible with --data > 1)")
    if args.data > 1 and args.index == "ivfpq":
        ap.error("--index ivfpq is single-shard (incompatible with "
                 "--data > 1)")
    if args.data > 1 and args.scan_impl == "pallas":
        ap.error("--scan-impl pallas is single-shard (incompatible with "
                 "--data > 1)")
    if args.churn and not args.mutable:
        ap.error("--churn requires --mutable")

    if args.data > 1:   # must precede first jax import; it only sizes the
                        # CPU platform, so chips still win where present
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.data} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.core import dml
    from repro.core.ps.sync import PSConfig
    from repro.core.ps.trainer import DMLTrainConfig, train_dml_distributed
    from repro.data import pairs as pairdata
    from repro.launch.mesh import make_local_mesh
    from repro.serve import (ExactIndex, IVFIndex, IVFPQIndex,
                             MicroBatcher, MutableIndex, RequestScheduler,
                             RetrievalEngine, SchedulerError, has_snapshot,
                             load_index, save_index)

    # --- data + metric ---------------------------------------------------
    cfg = pairdata.PairDatasetConfig(
        n_samples=args.gallery_size, feat_dim=args.feat_dim,
        n_classes=args.n_classes, kind="noisy_subspace", noise=0.5, seed=0)
    feats, labels = pairdata.make_features(cfg)
    if args.l_rank is not None:         # low-rank knob wins over proj-dim
        args.proj_dim = args.l_rank
    dcfg = dml.DMLConfig(feat_dim=args.feat_dim, l_rank=args.proj_dim)
    if args.train_steps > 0:
        train_pairs, _ = pairdata.train_eval_split(
            cfg, n_train_sim=4000, n_train_dis=4000,
            n_eval_sim=100, n_eval_dis=100)
        tcfg = DMLTrainConfig(dml=dcfg, ps=PSConfig(n_workers=1, seed=0),
                              batch_size=512, steps=args.train_steps,
                              lr=2e-2)
        L, hist = train_dml_distributed(tcfg, train_pairs)
        print(f"trained L: objective {hist[0]['loss']:.3f} -> "
              f"{hist[-1]['loss']:.3f}")
    else:
        L = dml.init_params(dcfg, jax.random.PRNGKey(0))

    # --- serving stack ---------------------------------------------------
    mesh = None
    if args.data > 1:
        if jax.device_count() < args.data:
            ap.error(f"--data {args.data}: the {jax.default_backend()} "
                     f"backend has {jax.device_count()} device(s)")
        mesh = make_local_mesh(data=args.data)
        print(f"gallery mesh {dict(mesh.shape)} over "
              f"{mesh.devices.flat[0].platform} devices")
    ivf_kw = dict(n_clusters=args.n_clusters, nprobe=args.nprobe,
                  scan_impl=args.scan_impl)
    ivfpq_kw = dict(ivf_kw, n_subspaces=args.n_subspaces, bits=args.bits,
                    rerank_depth=args.rerank_depth, store=args.pq_store)
    base_kw = {"exact": {}, "ivf": ivf_kw, "ivfpq": ivfpq_kw}[args.index]
    t0 = time.perf_counter()
    loaded = bool(args.snapshot_dir) and has_snapshot(args.snapshot_dir)
    if loaded:
        index = load_index(args.snapshot_dir, expect_L=L)
        if args.mutable and not isinstance(index, MutableIndex):
            ap.error(f"--mutable requested but {args.snapshot_dir} holds "
                     f"a frozen {type(index).__name__} snapshot; point "
                     f"--snapshot-dir elsewhere or drop --mutable")
    elif args.mutable:
        index = MutableIndex.build(
            L, feats, base=args.index, retain_raw=True, **base_kw)
    elif args.index == "ivfpq":
        index = IVFPQIndex.build(L, jnp.asarray(feats), mesh=mesh,
                                 **ivfpq_kw)
    elif args.index == "ivf":
        index = IVFIndex.build(L, jnp.asarray(feats), mesh=mesh, **ivf_kw)
    else:
        index = ExactIndex.build(L, jnp.asarray(feats), mesh=mesh)
    build_s = time.perf_counter() - t0
    if args.snapshot_dir and not loaded:
        save_index(index, args.snapshot_dir)
        print(f"snapshot saved to {args.snapshot_dir}")
    engine = RetrievalEngine(index, k_top=args.k, backend=args.backend,
                             cache_size=args.cache_size)
    engine.tracer.sample_rate = args.trace_sample
    warm_ks = [args.k]
    if args.warmup_ks:
        warm_ks += [int(x) for x in args.warmup_ks.split(",")]
    engine.warmup(ks=sorted(set(warm_ks)))
    verb = "loaded from snapshot" if loaded else "built+projected"
    print(f"index[{type(index).__name__}]: {index.size} x {args.proj_dim} "
          f"({index.n_shards} shard(s)), {verb} in {build_s:.2f}s")
    ivf = index.base if isinstance(index, MutableIndex) else index
    if isinstance(ivf, (IVFIndex, IVFPQIndex)):
        from repro.serve import scan as scanmod
        scanned = ivf.nprobe * ivf.cap
        resolved = scanmod.resolve_scan_impl(ivf.scan_impl,
                                             sharded=ivf.n_shards > 1)
        print(f"  {type(ivf).__name__}: {ivf.n_clusters} clusters, cap "
              f"{ivf.cap}, nprobe {ivf.nprobe} -> <= {scanned} of "
              f"{ivf.size} rows scanned per query "
              f"({scanned / max(ivf.size, 1):.1%}); "
              f"scan_impl={ivf.scan_impl} (resolves to {resolved})")
    if isinstance(ivf, IVFPQIndex):
        print(f"  pq: {ivf.pq.n_subspaces} x {ivf.pq.bits}-bit codes "
              f"({ivf.code_bytes_per_row} B/row scanned vs "
              f"{4 * args.proj_dim + 4} full precision, "
              f"{ivf.compression_ratio:.1f}x), rerank depth "
              f"{ivf.rerank_depth}, store={ivf.store}")

    if args.scheduler:
        front = RequestScheduler(
            engine, max_batch=args.max_batch,
            max_wait_ms=args.max_wait_ms, degrade=not args.no_degrade,
            high_watermark=args.high_watermark,
            low_watermark=args.low_watermark,
            degrade_window_s=args.degrade_window_ms / 1e3,
            restore_window_s=args.restore_window_ms / 1e3)
        front.warmup(ks=sorted(set(warm_ks)))   # ladder levels too
        if front.controller is not None:
            print(f"  scheduler ladder: "
                  f"{[dict(lv) for lv in front.controller.ladder]}")
    else:
        front = MicroBatcher(engine, max_batch=args.max_batch,
                             max_wait_ms=args.max_wait_ms)

    # --- traffic ---------------------------------------------------------
    rng = np.random.RandomState(1)
    qids = rng.randint(0, len(feats), args.requests)
    noisy = feats[qids] + 0.1 * rng.randn(args.requests, args.feat_dim) \
        .astype(np.float32)
    mix = rng.choice(["interactive", "batch", "mining"],
                     size=args.requests, p=[0.7, 0.2, 0.1])
    t0 = time.perf_counter()
    pending, n_rejected = [], 0
    for i, qid in enumerate(qids):
        t_sub = time.perf_counter()
        try:
            if args.scheduler:
                fut = front.submit(
                    noisy[i], priority=str(mix[i]),
                    deadline_s=(args.deadline_ms / 1e3
                                if args.deadline_ms else None))
            else:
                fut = front.submit(noisy[i])
            pending.append((qid, t_sub, fut))
        except SchedulerError:                  # typed backpressure
            n_rejected += 1
    lat, purity, n_expired = [], [], 0
    for qid, t_sub, fut in pending:
        try:
            _, nbr = fut.result(timeout=60)
        except SchedulerError:                  # deadline expired in queue
            n_expired += 1
            continue
        lat.append(time.perf_counter() - t_sub)
        # a loaded post-churn snapshot can serve rows upserted after this
        # run's synthetic label table was made; score only known ids
        nbr = np.asarray(nbr)
        known = nbr[(nbr >= 0) & (nbr < len(labels))]
        if len(known):
            purity.append(float(np.mean(labels[known] == labels[qid])))
    wall = time.perf_counter() - t0

    # --- hard-pair mining against the live engine ------------------------
    # before front.close(): under --scheduler the miner rides the front
    # end's ``mining`` priority class (admission + deadlines shape the
    # mining load exactly like third-tier traffic), so the front door
    # must still be open. k_neighbors is sized so the mined k equals
    # --k — the scheduler rejects k above the engine's k_top.
    mine_stats = None
    if args.mine > 0:
        from repro.mining import HardPairMiner, MinerConfig
        use_front = args.scheduler and args.k >= 3
        miner = HardPairMiner(
            engine, feats, labels,
            MinerConfig(k_neighbors=(args.k - 1 if use_front
                                     else max(args.k, 5))),
            frontend=front if use_front else None)
        mine_stats = miner.mine(n_queries=args.mine, seed=2).stats
        mine_stats["via_scheduler"] = use_front
    front.close()

    from repro.obs import percentile

    lat_ms = np.sort(np.asarray(lat)) * 1e3
    st = engine.stats()
    print(f"requests={args.requests} wall={wall:.2f}s "
          f"qps={args.requests / wall:.0f} "
          f"(device-side qps={st['qps']:.0f})")
    if lat_ms.size:
        # obs.percentile interpolates — the old index math
        # (lat[int(n * 0.99) - 1]) underflowed to the *minimum* at small n
        p50, p99 = percentile(lat_ms, (50.0, 99.0))
        print(f"latency ms: p50={p50:.2f} p99={p99:.2f} "
              f"max={lat_ms[-1]:.2f}")
    print(f"batches={front.n_batches} "
          f"mean batch={np.mean(front.batch_sizes):.1f}")
    print(f"cache: {st['cache_hits']} hits / {st['cache_misses']} misses "
          f"({st['cache_entries']} entries)")
    print(f"neighbor class purity@{args.k}: {np.mean(purity):.3f} "
          f"(chance {1.0 / args.n_classes:.3f})")
    if args.scheduler:
        obs = st["frontend"]
        for name, c in obs["classes"].items():
            print(f"  class {name}: admitted {c['admitted']} "
                  f"completed {c['completed']} expired {c['expired']} "
                  f"rejected {c['rejected']} queue_depth "
                  f"{c['queue_depth']} p50={c['p50_ms']:.2f}ms "
                  f"p99={c['p99_ms']:.2f}ms")
        # end-of-run gauges: depths should have drained to 0 and the
        # ladder recovered toward level 0 — nonzero values here mean the
        # run ended under pressure
        print(f"  gauges: total queue_depth {obs['queue_depth']}, "
              f"ladder level {obs['degradation_level']}")
        print(f"  degradation: level {obs['degradation_level']} "
              f"knobs {obs['degradation_knobs']} "
              f"({obs['n_transitions']} transition(s)); "
              f"{n_rejected} rejected at admission, "
              f"{n_expired} expired in queue")

    if mine_stats is not None:
        ms = mine_stats
        via = ("scheduler mining class" if ms["via_scheduler"]
               else "direct engine path")
        print(f"mining ({via}): {ms['n_pairs']} hard pairs from "
              f"{ms['n_queries']} anchors (neg yield "
              f"{ms['neg_yield']:.2f}/q, pos yield "
              f"{ms['pos_yield']:.2f}/q, {ms['n_semi_hard']} semi-hard, "
              f"{ms['n_fallback_neg']} fallback, {ms['n_dropped']} shed "
              f"by the front end) in "
              f"{ms['mine_busy_s']:.2f}s device time — engine now at "
              f"{ms['engine_qps']:.0f} qps over "
              f"{engine.stats()['n_device_queries']} device queries")

    # --- mutation lifecycle demo -----------------------------------------
    if args.mutable and args.churn > 0 and isinstance(index, MutableIndex):
        n = min(args.churn, index.size // 2)
        fresh = feats[rng.randint(0, len(feats), n)] \
            + 0.1 * rng.randn(n, args.feat_dim).astype(np.float32)
        new_ids = index.upsert(fresh)
        retire = index.live_ids()[:n]
        retire = retire[~np.isin(retire, new_ids)]
        index.delete(retire)
        d_m, i_m = engine.search(noisy[:8])
        st = engine.stats()
        print(f"churn: +{n} upserts / -{len(retire)} deletes -> "
              f"size {index.size}, delta_rows {st['delta_rows']}, "
              f"tombstones {st['tombstones']}, "
              f"compactions {st['compactions']} "
              f"(version {index.version}); new ids reachable: "
              f"{bool(np.isin(i_m, new_ids).any())}")
        if args.snapshot_dir:
            save_index(index, args.snapshot_dir)
            print(f"post-churn snapshot saved to {args.snapshot_dir}")

    # --- multi-tenant serving over the shared gallery --------------------
    if args.tenants > 0:
        from repro.serve import TenantRouter
        # fresh registry: the main engine's series are unscoped, tenant
        # engines label everything with tenant=... — one registry cannot
        # carry both shapes of the same metric name
        router = TenantRouter(feats, k_top=args.k)
        backends = {"exact": {}, "ivf": ivf_kw, "ivfpq": ivfpq_kw}
        for i in range(args.tenants):
            if i == 0:
                ti_L = np.asarray(L, np.float32)
            else:       # seeded low-rank factors standing in for other
                        # surfaces' trained metrics
                t_rng = np.random.RandomState(100 + i)
                ti_L = t_rng.randn(
                    max(args.proj_dim // 2, 2),
                    args.feat_dim).astype(np.float32) * 0.1
            router.add_tenant(f"t{i}", ti_L, backend=args.index,
                              build_kwargs=backends[args.index])
        if args.shadow:
            router.register_shadow("t1", np.asarray(L, np.float32),
                                   sample_rate=0.5)
        t_qids = rng.randint(0, len(feats), 64)
        for i, qid in enumerate(t_qids):
            name = f"t{i % args.tenants}"
            _, nbr = router.search(name, noisy[qid % args.requests]
                                   if args.requests else feats[qid])
        tob = router.observability()
        mem = tob["memory"]
        # the multi-tenant win: raw rows resident once, not per tenant
        per_tenant = mem["gallery"] + max(mem["tenants"].values())
        ratio = mem["total"] / max(per_tenant * args.tenants, 1)
        print(f"tenants: {args.tenants} metrics over one "
              f"{tob['gallery_rows']}-row gallery; resident "
              f"{mem['total'] / 1e6:.1f} MB vs ~"
              f"{per_tenant * args.tenants / 1e6:.1f} MB for "
              f"independent stacks ({ratio:.2f}x)")
        for name in sorted(tob["tenants"]):
            tb = tob["tenants"][name]
            print(f"  {name}: backend={tb['backend']} "
                  f"l_shape={tb['l_shape']} requests={tb['n_requests']} "
                  f"warm={tb['warm']}")
        if args.shadow:
            arm = router.tenant("t1").shadow
            st_sh = arm.stats()
            print(f"  shadow@t1: mirrored {st_sh['n_mirrored']} "
                  f"(rate {st_sh['sample_rate']}), overlap@{args.k} "
                  f"{st_sh['overlap_at_k']:.3f}, latency ratio "
                  f"{st_sh['latency_ratio']:.2f}")
            router.promote("t1")
            print(f"  promoted shadow -> t1 live "
                  f"(fingerprint {router.tenant('t1').fingerprint})")

    # --- obs export ------------------------------------------------------
    if args.metrics_out:
        engine.registry.write_snapshot(args.metrics_out)
        print(f"metrics snapshot -> {args.metrics_out}")
    if args.trace_out:
        n_tr = engine.tracer.write_jsonl(args.trace_out, append=False)
        print(f"traces -> {args.trace_out} ({n_tr} sampled of "
              f"{engine.tracer.n_minted} minted)")


if __name__ == "__main__":
    main()
