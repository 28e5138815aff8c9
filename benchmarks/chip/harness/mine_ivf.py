"""Mining cells served from an IVF index: the miner's closed loop of k-NN
bursts against an inverted-file index of the gallery.

The driver of the traffic kind ``mine_ivf`` (``drive``). The traffic and
the client are ``mine``'s (``mine.Bursts``, ``mine.window_stats``): bursts
of ``burst`` distinct anchors, each waited for, from a lead-in until the
window closes. The stack differs: ``IVFIndex.build_projected`` clusters
the gallery that ``serve`` makes and projects (``serve.factor``,
``serve.gallery``, ``serve.query_pool``) into the configuration's lists,
then ``RetrievalEngine -> RequestScheduler`` serve from it as the
configuration says. The unpadded gallery is dropped after the build, so
the index's segments are what stays on the device. On the TPU the
segment scan must resolve to the Pallas kernel.

Besides ``serve``'s result keys, the context carries ``ivf``: the
window's share of the engine's IVF scan counters (``ivf_*_total``) and
its device calls, and ``ivf_build_s``: the index's build stages from the
engine's ``index_build_seconds`` gauge. A program without those counters
or that gauge leaves them None, and the metrics that read them are left
out.

The check compares 2,048 completed requests of the bursts that overlap
the window with ``ivf_reference``, given the index's centroids, and holds
the index's list membership (copied from the device before the stack is
released) to the rule that places rows in lists:
``ids_invalid`` (out of range, repeated or -1), ``layout_wrong`` (gallery
ids missing from the segments, held twice, or beyond the configuration's
cap in their list), ``assign_excess`` (the worst break of the membership
rule, over |gp|^2 + max |c|^2; ``ivf_reference.assign_excess``),
``dist_err`` (as ``serve.check``), ``rank_excess`` (the served rows' true
distances over the reference's top k within the lists every correct
program probes; see ``ivf_reference.PROBE_TIE``), ``rank_missed`` (how
much nearer than the farthest served row a row of that top k lies that
the answer leaves out), ``recall_loss`` (1 - recall@k against the exact
top k of the whole gallery) and ``requests_unanswered``. The first three
and the last have the limit 0.
"""

from __future__ import annotations

import contextlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells, data, ivf_reference, mine, reference, serve

COUNTERS = ("probes", "rows_probed", "rows_read", "pad_slots", "underfilled")


class Stack(serve.Stack):
    """The program's IVF serving stack as the configuration states it,
    warmed as ``serve.Stack`` is: one search of each live size
    1..``max_batch``."""

    def __init__(self, key, L, gp, gn, cfg: dict, seed: int, traced: bool,
                 max_traces: int):
        from repro.obs import MetricsRegistry, Tracer
        from repro.serve import IVFIndex, RequestScheduler, RetrievalEngine
        from repro.serve.scan import resolve_scan_impl
        self.registry = MetricsRegistry()
        self.tracer = Tracer(sample_rate=1.0 if traced else 0.0,
                             max_traces=max_traces)
        self.index = IVFIndex.build_projected(
            L, gp, gn, n_clusters=cfg["n_clusters"], nprobe=cfg["nprobe"],
            iters=cfg["iters"], cap_factor=cfg["cap_factor"],
            scan_impl=cfg["scan_impl"], seed=int(seed) % 2 ** 31)
        impl = resolve_scan_impl(self.index.scan_impl)
        if jax.default_backend() == "tpu" and impl != "pallas":
            raise SystemExit(f"mine_ivf: scan_impl {cfg['scan_impl']!r} "
                             f"resolves to {impl!r} on the TPU; the cell "
                             f"measures the Pallas ivf_scan kernel")
        self.engine = RetrievalEngine(
            self.index, k_top=cfg["k_top"], backend=cfg["backend"],
            buckets=tuple(cfg["buckets"]), cache_size=cfg["cache_size"],
            registry=self.registry, tracer=self.tracer)
        warm, _ = data.make_rows(key, stream=data.WARM,
                                 rows=cfg["max_batch"],
                                 n_classes=cfg["n_classes"],
                                 feat_dim=cfg["feat_dim"],
                                 sparsity=cfg["sparsity"], noise=cfg["noise"])
        warm = np.asarray(warm)
        for n in range(1, cfg["max_batch"] + 1):
            self.engine.search(warm[:n])
        self.scheduler = RequestScheduler(
            self.engine, max_batch=cfg["max_batch"],
            max_wait_ms=cfg["max_wait_ms"], degrade=cfg["degrade"])

    def scan_work(self):
        """The engine's IVF scan counters and its device calls so far, or
        None where the program keeps no such counters."""
        snap = self.registry.snapshot()
        out = {}
        for name in COUNTERS:
            c = snap["counters"].get(f"ivf_{name}_total")
            if c is None:
                return None
            out[name] = sum(c["values"].values())
        out["calls"] = self.registry.histogram(
            "engine_search_seconds").count()
        return out

    def build_seconds(self):
        """The build's stages from the engine's ``index_build_seconds``
        gauge, or None where the program has no such gauge."""
        g = self.registry.snapshot()["gauges"].get("index_build_seconds")
        if not g or not g["values"]:
            return None
        from repro.obs import parse_label_key
        return {parse_label_key(k)["stage"]: v
                for k, v in g["values"].items()}

    def layout(self, cap: int):
        """(centroids, list of each gallery row, which lists hold fewer
        rows than the rule's ``cap``, layout_wrong) on the host, from the
        index's device arrays."""
        assign, counts, wrong = ivf_reference.membership(
            np.asarray(self.index.ids_pad), self.index.cap,
            self.index.n_rows, cap)
        return np.asarray(self.index.centroids), assign, counts < cap, wrong


@contextlib.contextmanager
def probe_next():
    """Fault: each query probes its 2nd to (nprobe+1)-th nearest lists,
    not its nearest ``nprobe``."""
    from repro.serve import ivf
    orig = ivf._probe

    def shifted(qp, centroids, nprobe):
        return orig(qp, centroids, nprobe + 1)[:, 1:]
    ivf._probe = shifted
    try:
        yield
    finally:
        ivf._probe = orig


@contextlib.contextmanager
def fewer_probes():
    """Fault: each query probes its nearest 3/4 ``nprobe`` lists (24 of
    32)."""
    from repro.serve import ivf
    orig = ivf._probe
    ivf._probe = lambda qp, centroids, nprobe: orig(qp, centroids,
                                                    nprobe - nprobe // 4)
    try:
        yield
    finally:
        ivf._probe = orig


@contextlib.contextmanager
def smaller_cap():
    """Fault: the index is built with a ``cap_factor`` of 1.0, segments
    of about the mean list's size, whatever the configuration says."""
    from repro.serve.ivf import IVFIndex
    orig = vars(IVFIndex)["build_projected"]
    IVFIndex.build_projected = classmethod(
        lambda cls, *a, **kw: orig.__func__(cls, *a,
                                            **dict(kw, cap_factor=1.0)))
    try:
        yield
    finally:
        IVFIndex.build_projected = orig


@contextlib.contextmanager
def spill_second():
    """Fault: the build moves each row over a list's cap to the second
    nearest list with room, not the nearest."""
    from repro.serve import ivf
    orig = ivf._nearest_open

    def second(gp, centroids, full, n_near, block_rows):
        ids, d = orig(gp, centroids, full, n_near + 1, block_rows)
        return ids[:, 1:], d[:, 1:]
    ivf._nearest_open = second
    try:
        yield
    finally:
        ivf._nearest_open = orig


FAULTS = {"probe_next": probe_next, "fewer_probes": fewer_probes,
          "smaller_cap": smaller_cap, "spill_second": spill_second}


def check(key, L, cfg: dict, pool: np.ndarray, cl, idx, seed: int, layout,
          *, control: bool = False):
    """The compared numbers of the index's layout and of the window's
    sample of completed requests (module docstring), and with ``control``
    those of the reference in bfloat16, put in the program's place,
    probing exactly its ``nprobe`` nearest lists. Besides, unjudged, the
    mean and the least number of lists the tie rule requires a query to
    probe. Returns (numbers, control numbers or None)."""
    k, M = cfg["k_top"], cfg["gallery_rows"]
    centroids, assign, has_room, layout_wrong = layout
    blocks = M // cfg["gallery_chunk"]
    a, cent = jnp.asarray(assign), jnp.asarray(centroids)
    ok = [i for i in idx if cl.outcome[i] == 1]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 3])
    if len(ok) > cfg["check_requests"]:
        ok = sorted(rng.choice(ok, cfg["check_requests"], replace=False))
    qids, rows = np.unique(cl.qid[ok], return_inverse=True)
    low = None
    if control and ok:
        dt = jnp.bfloat16
        cqp, _ = reference.project(L, jnp.asarray(pool[qids]), dt)
        cgp, cgn = serve.gallery(key, L, cfg,
                                 lambda L_, x: reference.project(L_, x, dt))
        allowed = ivf_reference.probed(cqp, cent.astype(dt),
                                       nprobe=cfg["nprobe"], tie=0.0)
        (cd, ci), _ = ivf_reference.knn(cqp, cgp.astype(dt), cgn.astype(dt),
                                        a, allowed, k=k, blocks=blocks)
        del cgp, cgn
        low = (np.asarray(cd, np.float64)[rows], np.asarray(ci)[rows])
    gp, gn = serve.gallery(key, L, cfg, reference.project)
    d_own, d_near, near, d_room = map(np.asarray, ivf_reference.assignment(
        gp, gn, cent, a, jnp.asarray(has_room), blocks=blocks))
    layout_nums = {
        "layout_wrong": float(layout_wrong),
        "assign_excess": ivf_reference.assign_excess(
            assign, d_own.astype(np.float64), d_near.astype(np.float64),
            near, d_room.astype(np.float64),
            np.asarray(gn, np.float64) + float(jnp.max(jnp.sum(
                cent * cent, axis=1))))}
    if not ok:
        return dict(layout_nums, requests_checked=0.0), None
    served = (np.stack([np.asarray(cl.results[i][0], np.float64)
                        for i in ok]),
              np.stack([np.asarray(cl.results[i][1]) for i in ok]))
    qp, qn = reference.project(L, jnp.asarray(pool[qids]), jnp.float32)
    allowed = ivf_reference.probed(qp, cent, nprobe=cfg["nprobe"],
                                   tie=ivf_reference.PROBE_TIE)
    (rd, ri), (_, ti) = ivf_reference.knn(qp, gp, gn, a, allowed, k=k,
                                          blocks=blocks)
    truth = (np.asarray(rd, np.float64)[rows], np.asarray(ri)[rows],
             np.asarray(ti)[rows], np.asarray(qn, np.float64)[rows],
             np.asarray(gn, np.float64))
    qp_rows = qp[jnp.asarray(rows)]
    lists = np.asarray(allowed).sum(axis=1)
    nums = dict(_numbers(served, truth, qp_rows, gp, M), **layout_nums,
                requests_checked=float(len(ok)),
                probe_lists_mean=float(lists.mean()),
                probe_lists_min=float(lists.min()))
    if low is not None:
        low = dict(_numbers(low, truth, qp_rows, gp, M), **layout_nums)
    return nums, low


def _numbers(served, truth, qp_rows, gp, M: int) -> dict:
    sd, si = served
    ref_d, ref_i, exact_i, qn, gn = truth
    bad = (si < 0) | (si >= M)
    dup = np.array([len(set(r)) < len(r) for r in si])
    sic = np.clip(si, 0, M - 1)
    sid = np.asarray(reference.dists_of(qp_rows, gp, jnp.asarray(sic)),
                     np.float64)
    scale = qn + np.maximum(gn[sic].max(axis=1),
                            gn[np.clip(ref_i, 0, M - 1)].max(axis=1))
    dist_err = np.abs(sd - sid).max(axis=1) / scale
    excess = (np.sort(sid, axis=1) - ref_d).max(axis=1) / scale
    left_out = (ref_i >= 0) & ~(ref_i[:, :, None] == si[:, None, :]).any(-1)
    missed = np.where(left_out, sid.max(axis=1, keepdims=True) - ref_d,
                      -np.inf).max(axis=1) / scale
    k = si.shape[1]
    recall = np.mean([len(set(s[s >= 0]) & set(e)) / k
                      for s, e in zip(si, exact_i)])
    return {
        "ids_invalid": float(bad.sum() + dup.sum()),
        "dist_err": float(dist_err.max()),
        "rank_excess": float(max(excess.max(), 0.0)),
        "rank_missed": float(max(missed.max(), 0.0)),
        "recall_loss": float(1.0 - recall),
    }


def _delta(after, before):
    if after is None or before is None:
        return None
    return {n: after[n] - before[n] for n in after}


def drive(cfg, traffic, *, seed, seconds, prof, t_start, devices,
          control=False):
    """One run of an IVF mining cell: set-up (the gallery, the index
    build, the warmed stack), the closed loop from the lead-in to the
    window's close, a wait for the burst in flight, then the check.
    Returns (ctx, numbers, control numbers or None)."""
    from repro.kernels.metric_topk import project_gallery
    key = data.base_key(seed)
    cfg = dict(cfg, k_top=traffic["k_top"])     # the miner's engine
    L = serve.factor(key, cfg["proj_dim"], cfg["feat_dim"])
    gp, gn = serve.gallery(key, L, cfg, project_gallery)
    pool = serve.query_pool(key, cfg, traffic)
    stack = Stack(key, L, gp, gn, cfg, seed, traced=prof is not None,
                  max_traces=mine.MAX_TRACES)
    del gp, gn                  # the index's segments are what stays
    build = stack.build_seconds()
    client = mine.Bursts(stack.scheduler, pool, traffic, seed)
    cells.settle_heap()
    if prof is not None:
        prof.start()
    t0 = time.perf_counter() + traffic["lead_s"]
    client.start()
    serve.sleep_until(t0)
    if prof is not None:
        prof.mark()
    hist0, work0 = stack.batch_hist(), stack.scan_work()
    serve.sleep_until(t0 + seconds)
    client.stop.set()
    hist1, work1 = stack.batch_hist(), stack.scan_work()
    if prof is not None:
        prof.stop()
    joined = client.join(timeout=60.0)
    t_end = time.perf_counter()
    ws = mine.window_stats(client, t0, seconds, t_end)
    closed = stack.close()
    peak = cells.peak_bytes(devices)
    spans = stack.tracer.drain()
    layout = stack.layout(ivf_reference.cap(
        cfg["gallery_rows"], cfg["n_clusters"], cfg["cap_factor"]))
    client.scheduler = None
    del stack
    cells.release_heap()
    print(f"mine_ivf: {ws['n_bursts']} bursts of {traffic['burst']} overlap "
          f"the window, {ws['open_at_close']} requests open at its close, "
          f"the last done {ws['drained_s']!r} s after it; build stages "
          f"(s): {build!r}", file=sys.stderr)
    mono0 = t0 + time.monotonic() - time.perf_counter()   # spans' clock
    ctx = {
        "kind": "serve",
        "setup_s": t0 - t_start,
        "window_s": seconds,
        "t0": t0,
        "latency_s": ws["latency_s"],
        "lag_s": np.zeros(0),
        "completed_in_window": ws["completed_in_window"],
        "attempted": ws["n_requests"],
        "failed": ws["n_failed"] + (0 if closed and joined else 1),
        "memory_peak_bytes": peak,
        "spans": [s for s in spans
                  if mono0 <= s["root"]["t_start"] < mono0 + seconds],
        "batches": (hist1[0] - hist0[0], hist1[1] - hist0[1]),
        "ivf": _delta(work1, work0),
        "ivf_build_s": build,
    }
    nums, low = check(key, L, cfg, pool, client, ws["in_window"], seed,
                      layout, control=control)
    nums["requests_unanswered"] = float(ws["n_unanswered"])
    return ctx, nums, low
