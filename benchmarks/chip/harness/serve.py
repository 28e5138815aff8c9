"""Serving cells: open-loop k-NN requests through the scheduler.

The driver of the traffic kind ``serve`` (``drive``); its open-loop pieces (``setup``, ``Replay``, ``window_stats``) also serve
``sweep.py``.

Set-up makes the serving factor ``L`` and the gallery from the seed on the
device, projects the gallery through the program's own index-build
projection, builds ``ExactIndex -> RetrievalEngine -> RequestScheduler``
as the configuration says, warms every bucket the scheduler can form, and
makes the query pool. The window then replays a Poisson schedule from one
client thread: each request is submitted at its due time and timed from
its due time to its result, so a stall counts against every request it
delays. Requests due in a lead-in before the window fill the queue and
are not counted.

The check compares every completed request of the window with a plain
exact scan at full f32 precision, over a gallery and queries that the
reference projects itself from the seed.
"""

from __future__ import annotations

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells, data, reference


@functools.partial(jax.jit, static_argnums=(1, 2))
def factor(key, d_out: int, d_in: int):
    """The served metric factor: N(0, 1) / sqrt(d_in), made from the seed
    on the device in one call (the weights of this cell)."""
    return (1.0 / np.sqrt(d_in)) * jax.random.normal(
        jax.random.fold_in(key, 7), (d_out, d_in), jnp.float32)


def gallery(key, L, cfg: dict, project):
    """The gallery of the cell projected by ``project(L, x)``."""
    return data.make_projected(
        key, L, project, stream=data.GALLERY, rows=cfg["gallery_rows"],
        chunk=cfg["gallery_chunk"], n_classes=cfg["n_classes"],
        feat_dim=cfg["feat_dim"], out_dim=cfg["proj_dim"],
        sparsity=cfg["sparsity"], noise=cfg["noise"])


def query_pool(key, cfg: dict, traffic: dict) -> np.ndarray:
    q, _ = data.make_rows(key, stream=data.QUERIES, rows=traffic["pool"],
                          n_classes=cfg["n_classes"],
                          feat_dim=cfg["feat_dim"],
                          sparsity=cfg["sparsity"], noise=cfg["noise"])
    return np.asarray(q)


class Stack:
    """The program's serving stack as the configuration states it, warmed
    for every batch the scheduler can form: one search of each live size
    1..``max_batch``, so that every bucket's scan and every size's pad and
    slice compile in set-up and never in the window. The warm-up rows are
    a stream of their own, so that the window's queries find none of them
    in the hot-query LRU."""

    def __init__(self, key, L, gp, gn, cfg: dict, traced: bool,
                 max_traces: int):
        from repro.obs import MetricsRegistry, Tracer
        from repro.serve import ExactIndex, RequestScheduler, RetrievalEngine
        self.registry = MetricsRegistry()
        self.tracer = Tracer(sample_rate=1.0 if traced else 0.0,
                             max_traces=max_traces)
        self.index = ExactIndex.from_projected(L, gp, gn)
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

    def batch_hist(self):
        h = self.registry.histogram("frontend_batch_size")
        return h.sum(), h.count()

    def close(self) -> bool:
        return self.scheduler.close(timeout=60.0)


def setup(key, cfg: dict, traffic: dict, *, traced: bool = False,
          max_traces: int = 0):
    """Set-up of a serving cell: the factor, the gallery projected by the
    program's index build, the query pool and the warmed stack. Returns
    (L, pool, stack)."""
    from repro.kernels.metric_topk import project_gallery
    L = factor(key, cfg["proj_dim"], cfg["feat_dim"])
    gp, gn = gallery(key, L, cfg, project_gallery)
    pool = query_pool(key, cfg, traffic)
    return L, pool, Stack(key, L, gp, gn, cfg, traced, max_traces)


def sleep_until(t: float) -> None:
    wait = t - time.perf_counter()
    if wait > 0:
        time.sleep(wait)


class Replay:
    """One client thread submitting a schedule; results land by request.
    Each request goes in under the traffic's ``priority`` class with the
    traffic's ``deadline_s``."""

    def __init__(self, scheduler, pool: np.ndarray, due, qid, traffic: dict):
        self.scheduler, self.pool = scheduler, pool
        self.due, self.qid = due, qid
        self.priority = traffic["priority"]
        self.deadline_s = traffic["deadline_s"]
        n = len(due)
        self.t_submit = np.full(n, np.nan)
        self.t_done = np.full(n, np.nan)
        self.outcome = np.zeros(n, np.int8)     # 0 none, 1 ok, 2 failed
        self.results: dict = {}
        self._lock = threading.Lock()

    def _done(self, i, fut):
        t = time.perf_counter()
        exc = fut.exception()
        with self._lock:
            self.t_done[i] = t
            if exc is None:
                self.outcome[i] = 1
                self.results[i] = fut.result()
            else:
                self.outcome[i] = 2

    def run(self, t0: float, on_window=None) -> None:
        """Submit every request at ``t0 + due`` (host clock); call
        ``on_window()`` at ``t0``, before the window's first request. The
        thread sleeps between submits, so that it never holds the
        interpreter's lock the scheduler's threads need; how late each
        submit ran is kept."""
        from repro.serve.scheduler import RejectedError
        for i, (due, q) in enumerate(zip(self.due, self.qid)):
            if on_window is not None and due >= 0:
                sleep_until(t0)
                on_window()
                on_window = None
            sleep_until(t0 + due)
            self.t_submit[i] = time.perf_counter()
            try:
                fut = self.scheduler.submit(self.pool[q],
                                            priority=self.priority,
                                            deadline_s=self.deadline_s)
            except RejectedError:
                self.outcome[i] = 2
                self.t_done[i] = self.t_submit[i]
                continue
            fut.add_done_callback(lambda f, i=i: self._done(i, f))

    def wait(self, until: float) -> None:
        while time.perf_counter() < until:
            with self._lock:
                if not (self.outcome == 0).any():
                    return
            time.sleep(0.01)


def window_stats(rp: Replay, t0: float, seconds: float, t_end: float):
    """Host-clock figures of the requests due in the window."""
    inw = (rp.due >= 0) & (rp.due < seconds)
    due_abs = t0 + rp.due[inw]
    done = rp.t_done[inw]
    ok = rp.outcome[inw] == 1
    lat = np.where(ok, done - due_abs, t_end - due_abs)  # missing: all waited
    completed = np.sum(ok & (done >= t0) & (done <= t0 + seconds))
    return {
        "n_due": int(inw.sum()),
        "n_ok": int(ok.sum()),
        "n_failed": int((~ok).sum()),
        "n_unanswered": int((rp.outcome[inw] == 0).sum()),
        "latency_s": lat,
        "completed_in_window": int(completed),
        "lag_s": rp.t_submit[inw] - due_abs,
        "in_window": np.flatnonzero(inw),
    }


def check(key, L, cfg: dict, pool: np.ndarray, rp: Replay, idx, seed: int,
          *, control: bool = False):
    """The compared numbers of a serving cell, for a sample of the window's
    completed requests drawn from the seed: the worst gap between a served
    distance and the true distance of the served row, and the worst excess
    of the served neighbours' true distances over the true k nearest, both
    as a share of |qp|^2 + |gp|^2 (the size that f32 rounding of a
    factored distance scales with); and served ids that are out of range or
    repeated. The truth is a plain exact scan at full f32 precision over a
    gallery and queries that the reference projects itself from the seed.

    With ``control``, also the numbers of the control: the reference
    computed in bfloat16, put in the program's place. Returns (numbers,
    control numbers or None)."""
    k, M = cfg["k_top"], cfg["gallery_rows"]
    ok = [i for i in idx if rp.outcome[i] == 1]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 3])
    if len(ok) > cfg["check_requests"]:
        ok = sorted(rng.choice(ok, cfg["check_requests"], replace=False))
    if not ok:
        return {"requests_checked": 0.0}, None
    qids, rows = np.unique(rp.qid[ok], return_inverse=True)
    blocks = cfg["gallery_rows"] // cfg["gallery_chunk"]
    served = (np.stack([np.asarray(rp.results[i][0], np.float64)
                        for i in ok]),
              np.stack([np.asarray(rp.results[i][1]) for i in ok]))
    low = None
    if control:
        dt = jnp.bfloat16
        cqp, _ = reference.project(L, jnp.asarray(pool[qids]), dt)
        cgp, cgn = gallery(key, L, cfg,
                           lambda L_, x: reference.project(L_, x, dt))
        cd, ci = reference.knn(cqp, cgp.astype(dt), cgn.astype(dt), k=k,
                               blocks=blocks)
        del cgp, cgn
        low = (np.asarray(cd, np.float64)[rows], np.asarray(ci)[rows])
    gp, gn = gallery(key, L, cfg, reference.project)
    qp, qn = reference.project(L, jnp.asarray(pool[qids]), jnp.float32)
    td, ti = reference.knn(qp, gp, gn, k=k, blocks=blocks)
    truth = (np.asarray(td, np.float64)[rows], np.asarray(ti)[rows],
             np.asarray(qn, np.float64)[rows], np.asarray(gn, np.float64))
    qp_rows = qp[jnp.asarray(rows)]
    nums = _gaps(served, truth, qp_rows, gp, M)
    nums["requests_checked"] = float(len(ok))
    return nums, (None if low is None else _gaps(low, truth, qp_rows, gp, M))


def _gaps(served, truth, qp_rows, gp, M: int) -> dict:
    sd, si = served
    true_d, true_i, qn, gn = truth
    bad = (si < 0) | (si >= M)
    dup = np.array([len(set(r)) < len(r) for r in si])
    sic = np.clip(si, 0, M - 1)
    sid = np.asarray(reference.dists_of(qp_rows, gp, jnp.asarray(sic)),
                     np.float64)
    scale = qn + np.maximum(gn[sic].max(axis=1), gn[true_i].max(axis=1))
    dist_err = np.abs(sd - sid).max(axis=1) / scale
    excess = (np.sort(sid, axis=1) - true_d).max(axis=1) / scale
    return {
        "ids_invalid": float(bad.sum() + dup.sum()),
        "dist_err": float(dist_err.max()),
        "rank_excess": float(max(excess.max(), 0.0)),
    }


def drive(cfg, traffic, *, seed, seconds, prof, t_start, devices,
          control=False):
    """One run of a serving cell: set-up, the open-loop window of
    ``seconds`` at the traffic's rate, a wait for every request due in it,
    then the check. Returns (ctx, numbers, control numbers or None)."""
    key = data.base_key(seed)
    due, qid = data.arrivals(traffic["rate_qps"], seconds,
                             traffic["lead_s"], seed, traffic["pool"])
    L, pool, stack = setup(key, cfg, traffic, traced=prof is not None,
                           max_traces=len(due) + 1024)
    rp = Replay(stack.scheduler, pool, due, qid, traffic)
    marks = {}

    def on_window():
        if prof is not None:
            prof.mark()
        marks["hist0"] = stack.batch_hist()

    cells.settle_heap()
    if prof is not None:
        prof.start()
    t_first = time.perf_counter() + 0.05
    t0 = t_first + traffic["lead_s"]
    rp.run(t0, on_window=on_window)
    sleep_until(t0 + seconds)
    hist1 = stack.batch_hist()
    if prof is not None:
        prof.stop()
    rp.wait(t0 + seconds + 60.0)
    t_end = time.perf_counter()
    ws = window_stats(rp, t0, seconds, t_end)
    closed = stack.close()
    peak = cells.peak_bytes(devices)
    spans = stack.tracer.drain()
    rp.scheduler = None
    del stack
    cells.release_heap()
    mono0 = t0 + time.monotonic() - time.perf_counter()   # spans' clock
    ctx = {
        "kind": "serve",
        "setup_s": t0 - t_start,
        "window_s": seconds,
        "t0": t0,
        "latency_s": ws["latency_s"],
        "lag_s": ws["lag_s"],
        "completed_in_window": ws["completed_in_window"],
        "attempted": ws["n_due"],
        "failed": ws["n_failed"] + (0 if closed else 1),
        "memory_peak_bytes": peak,
        "spans": [s for s in spans
                  if mono0 <= s["root"]["t_start"] < mono0 + seconds],
        "batches": (hist1[0] - marks["hist0"][0],
                    hist1[1] - marks["hist0"][1]),
    }
    nums, low = check(key, L, cfg, pool, rp, ws["in_window"], seed,
                      control=control)
    nums["requests_unanswered"] = float(ws["n_unanswered"])
    return ctx, nums, low

