"""The mining driver's closed loop and its window arithmetic, with a
stand-in scheduler: one burst outstanding at a time, and every request
completed inside the window counted, whatever burst it belongs to."""

import queue
import threading
import time
from concurrent.futures import Future

import numpy as np

import tiny  # noqa: F401  puts the harness on the path
from harness import mine

MIX = {"burst": 4, "k_top": 3, "priority": "mining"}


def _burst(t_submit, t_done, outcome):
    n = len(t_done)
    return {"qid": np.arange(n), "t_submit": np.full(n, float(t_submit)),
            "t_done": np.asarray(t_done, float),
            "outcome": np.asarray(outcome, np.int8)}


def test_the_window_counts_every_request_completed_in_it():
    cl = mine.Bursts(None, np.zeros((16, 2)), MIX, seed=1)
    cl._bursts = [
        _burst(0.0, [1, 2, 3, 4], [1, 1, 1, 1]),          # before the window
        _burst(4.5, [4.8, 5.2, 5.5, 6.0], [1, 1, 1, 1]),  # straddles its start
        _burst(6.0, [7, 8, np.nan, 16], [1, 2, 0, 1]),    # runs past its close
    ]
    ws = mine.window_stats(cl, t0=5.0, seconds=10.0, t_end=20.0)
    assert ws["n_bursts"] == 2 and ws["n_requests"] == 8
    assert list(ws["in_window"]) == list(range(4, 12))
    assert ws["completed_in_window"] == 4       # 5.2, 5.5, 6.0 and 7
    assert ws["n_failed"] == 1 and ws["n_unanswered"] == 1
    assert ws["open_at_close"] == 2             # unanswered, and done at 16
    assert np.isinf(ws["drained_s"])
    assert list(cl.outcome) == [1] * 8 + [1, 2, 0, 1]


class _Server:
    """A stand-in scheduler: answers each request about half a millisecond
    after the one before, refuses every fifth, and keeps the most requests
    it ever held at once."""

    def __init__(self):
        from repro.serve.scheduler import RejectedError
        self.refused = RejectedError
        self.lock = threading.Lock()
        self.open = self.most = self.n = 0
        self.todo = queue.Queue()
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def submit(self, row, k_top, priority):
        assert priority == "mining" and k_top == MIX["k_top"]
        with self.lock:
            self.n += 1
            if self.n % 5 == 0:
                raise self.refused("full")
            self.open += 1
            self.most = max(self.most, self.open)
        fut = Future()
        self.todo.put(fut)
        return fut

    def _serve(self):
        while (fut := self.todo.get()) is not None:
            time.sleep(0.0005)
            with self.lock:
                self.open -= 1
            fut.set_running_or_notify_cancel()
            k = MIX["k_top"]
            fut.set_result((np.zeros(k, np.float32), np.arange(k)))


def test_the_client_keeps_one_burst_outstanding():
    server = _Server()
    cl = mine.Bursts(server, np.zeros((16, 2)), MIX, seed=2 ** 31 + 5)
    cl.start()
    time.sleep(0.2)
    assert cl.join(timeout=10.0)
    server.todo.put(None)
    which, qid, t_sub, t_done, outcome = cl.flat()
    assert which.max() >= 3                     # several bursts ran
    assert server.most <= MIX["burst"]
    for b in range(which.max() + 1):
        assert len(set(qid[which == b])) == MIX["burst"]
    assert np.all(outcome > 0) and np.all(t_done >= t_sub)
    assert np.sum(outcome == 2) == server.n // 5
    assert len(cl.results) == np.sum(outcome == 1)
