"""Mining cells: the hard-pair miner's closed loop of k-NN bursts.

The driver of the traffic kind ``mine`` (``drive``). With a front end
attached, ``mining/miner.py`` (``HardPairMiner._neighborhoods``) submits one
burst of ``query_batch`` anchors, each a request for its k =
``k_neighbors`` + 1 nearest rows under the scheduler's ``mining`` class,
waits for every answer, and only then submits the next burst. One client
thread replays that loop here against a serving cell's stack
(``serve.setup``), with the engine's ``k_top`` the miner's k: bursts of
``burst`` distinct anchors drawn from the seed out of the query pool,
back to back, from a lead-in of ``lead_s`` until the window closes. The
burst in flight at the close is waited for.

A closed loop keeps at most one burst outstanding, so nothing queues
without bound and nothing is refused, however fast the program is:
``completed_in_window`` counts every request that completed inside the
window, whatever burst it belongs to, so ``qps`` reads what one chip
completes. There is no schedule, so there is no lag behind one
(``lag_s`` is empty). The check is ``serve.check``'s, over the requests
of every burst that overlaps the window.
"""

from __future__ import annotations

import concurrent.futures
import sys
import threading
import time

import numpy as np

from harness import cells, data, serve

MAX_TRACES = 65536     # the newest traces kept: > 11 s at 5,900 requests/s


class Bursts:
    """The client thread: bursts of ``burst`` distinct queries, each burst
    submitted whole and then waited for, until ``stop``. Per request its
    query id, submit and done times, outcome (0 none, 1 ok, 2 failed) and
    answer, as ``serve.Replay`` keeps them."""

    def __init__(self, scheduler, pool: np.ndarray, traffic: dict,
                 seed: int):
        self.scheduler, self.pool = scheduler, pool
        self.burst, self.k = traffic["burst"], traffic["k_top"]
        self.priority = traffic["priority"]
        self.rng = np.random.default_rng(
            [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 5])
        self.stop = threading.Event()
        self._bursts: list = []
        self.results: dict = {}
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _done(self, rec, j, i, fut):
        t = time.perf_counter()
        exc = fut.exception()
        with self._lock:
            rec["t_done"][j] = t
            if exc is None:
                rec["outcome"][j] = 1
                self.results[i] = fut.result()
            else:
                rec["outcome"][j] = 2

    def _run(self):
        from repro.serve.scheduler import RejectedError
        n = self.burst
        while not self.stop.is_set():
            base = len(self._bursts) * n
            rec = {"qid": self.rng.choice(len(self.pool), n, replace=False),
                   "t_submit": np.full(n, np.nan),
                   "t_done": np.full(n, np.nan),
                   "outcome": np.zeros(n, np.int8)}
            self._bursts.append(rec)
            futs = []
            for j, q in enumerate(rec["qid"]):
                rec["t_submit"][j] = time.perf_counter()
                try:
                    fut = self.scheduler.submit(self.pool[q], k_top=self.k,
                                                priority=self.priority)
                except RejectedError:
                    rec["outcome"][j] = 2
                    rec["t_done"][j] = rec["t_submit"][j]
                    continue
                fut.add_done_callback(
                    lambda f, rec=rec, j=j, i=base + j: self._done(
                        rec, j, i, f))
                futs.append(fut)
            concurrent.futures.wait(futs, timeout=60.0)

    def start(self):
        self._thread.start()

    def join(self, timeout: float) -> bool:
        self.stop.set()
        self._thread.join(timeout)
        return not self._thread.is_alive()

    def flat(self):
        """Every request of every burst, in submit order: (burst of each,
        query ids, submit times, done times, outcomes)."""
        with self._lock:
            recs = list(self._bursts)
            cols = [np.concatenate([r[c] for r in recs]) for c in
                    ("qid", "t_submit", "t_done", "outcome")]
        which = np.repeat(np.arange(len(recs)), self.burst)
        return (which, *cols)


def window_stats(cl: Bursts, t0: float, seconds: float, t_end: float):
    """Host-clock figures of the window: the requests of the bursts that
    overlap it, and every request completed inside it."""
    which, qid, t_sub, t_done, outcome = cl.flat()
    cl.qid, cl.outcome = qid, outcome          # as serve.check reads them
    t_close = t0 + seconds
    late = np.where(np.isnan(t_done), np.inf, t_done)
    last = np.full(which.max() + 1, -np.inf)
    np.maximum.at(last, which, late)
    inw = last[which] >= t0
    ok = outcome[inw] == 1
    done_in = (outcome == 1) & (t_done >= t0) & (t_done <= t_close)
    open_at_close = inw & (t_sub < t_close) & ~(late <= t_close)
    return {
        "n_requests": int(inw.sum()),
        "n_bursts": int(len(np.unique(which[inw]))),
        "n_failed": int((outcome[inw] == 2).sum()),
        "n_unanswered": int((outcome[inw] == 0).sum()),
        "latency_s": np.where(ok, t_done[inw], t_end) - t_sub[inw],
        "completed_in_window": int(done_in.sum()),
        "open_at_close": int(open_at_close.sum()),
        "drained_s": float(np.max(late[inw]) - t_close),
        "in_window": np.flatnonzero(inw),
    }


def drive(cfg, traffic, *, seed, seconds, prof, t_start, devices,
          control=False):
    """One run of a mining cell: set-up, the closed loop from the lead-in
    to the window's close, a wait for the burst in flight, then the check.
    Returns (ctx, numbers, control numbers or None)."""
    key = data.base_key(seed)
    cfg = dict(cfg, k_top=traffic["k_top"])     # the miner's engine
    L, pool, stack = serve.setup(key, cfg, traffic, traced=prof is not None,
                                 max_traces=MAX_TRACES)
    client = Bursts(stack.scheduler, pool, traffic, seed)
    cells.settle_heap()
    if prof is not None:
        prof.start()
    t0 = time.perf_counter() + traffic["lead_s"]
    client.start()
    serve.sleep_until(t0)
    if prof is not None:
        prof.mark()
    hist0 = stack.batch_hist()
    serve.sleep_until(t0 + seconds)
    client.stop.set()
    hist1 = stack.batch_hist()
    if prof is not None:
        prof.stop()
    joined = client.join(timeout=60.0)
    t_end = time.perf_counter()
    ws = window_stats(client, t0, seconds, t_end)
    closed = stack.close()
    peak = cells.peak_bytes(devices)
    spans = stack.tracer.drain()
    client.scheduler = None
    del stack
    cells.release_heap()
    print(f"mine: {ws['n_bursts']} bursts of {traffic['burst']} overlap "
          f"the window, {ws['open_at_close']} requests open at its close, "
          f"the last done {ws['drained_s']!r} s after it", file=sys.stderr)
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
    }
    nums, low = serve.check(key, L, cfg, pool, client, ws["in_window"], seed,
                            control=control)
    nums["requests_unanswered"] = float(ws["n_unanswered"])
    return ctx, nums, low
