"""The program's stages in the JAX profiler's trace, on the CPU.

The trainer annotates every iteration of ``train_dml_distributed``
(``train``, ``train.batch`` with its per-worker ``train.draw`` and
``train.gather`` and its ``train.stack``, ``train.step``, ``train.log``)
and the serving stack mirrors the sampled ``obs`` spans that stay on one
thread (``batch``, ``engine``, ``cache_lookup``, ``pad``,
``device_topk``). Each test records a trace into ``tmp_path`` and reads
the host plane with ``jax.profiler.ProfileData``.
"""

import contextlib
import glob
import os
import subprocess
import sys
from collections import Counter

import numpy as np

import jax

from repro.core import dml
from repro.core.ps import sync
from repro.core.ps.trainer import DMLTrainConfig, train_dml_distributed
from repro.data.pairs import IndexPairSource, sample_pair_indices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS, LOG_EVERY = 12, 5
LOGGED = [t for t in range(STEPS) if t % LOG_EVERY == 0 or t == STEPS - 1]
TRAIN_SPANS = ("train", "train.batch", "train.draw", "train.gather",
               "train.stack", "train.step", "train.log")
SERVE_SPANS = ("request", "queue", "batch", "engine", "cache_lookup", "pad",
               "device_topk")


@contextlib.contextmanager
def profiled(trace_dir):
    """The JAX profiler around the block (Python tracer off, as the
    benchmark runs it), stopped whatever the block raises."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_events(trace_dir, names):
    """{name: [(start, end)]} of the host plane's events named in
    ``names``, over every host thread."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = {n: [] for n in names}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in out:
                    out[e.name].append((e.start_ns,
                                        e.start_ns + e.duration_ns))
    return out


def inside(child, parents):
    s, e = child
    return any(ps <= s and e <= pe for ps, pe in parents)


def train_tiny(n_workers: int):
    """A dozen steps of Eq. 4 from an on-device store (``log_every`` 5)."""
    rng = np.random.RandomState(0)
    labels = rng.randint(0, 4, 256)
    feats = jax.numpy.asarray(rng.randn(256, 16).astype(np.float32))
    pool = sample_pair_indices(labels, 400, 400, seed=0)
    cfg = DMLTrainConfig(
        dml=dml.DMLConfig(feat_dim=16, proj_dim=8),
        ps=sync.PSConfig(n_workers=n_workers, sync="bsp"),
        batch_size=32, steps=STEPS, lr=1e-2, log_every=LOG_EVERY)
    return train_dml_distributed(cfg, IndexPairSource(feats, pool))


def check_trainer_spans(ev, n_workers):
    count = Counter({n: len(v) for n, v in ev.items()})
    assert count["train"] == count["train.batch"] == STEPS
    assert count["train.step"] == count["train.stack"] == STEPS
    assert count["train.log"] == len(LOGGED)
    assert count["train.draw"] == count["train.gather"] == STEPS * n_workers
    for name in ("train.draw", "train.gather", "train.stack"):
        assert all(inside(c, ev["train.batch"]) for c in ev[name]), name
    for name in ("train.batch", "train.step", "train.log"):
        assert all(inside(c, ev["train"]) for c in ev[name]), name
    # the stages of one step follow each other: batch, then step, then log
    batch, step = sorted(ev["train.batch"]), sorted(ev["train.step"])
    assert all(b[1] <= s[0] for b, s in zip(batch, step))


def test_trainer_stages_one_worker(tmp_path):
    with profiled(tmp_path):
        _, history = train_tiny(n_workers=1)
    assert [h["step"] for h in history] == LOGGED
    check_trainer_spans(host_events(tmp_path, TRAIN_SPANS), 1)


def test_trainer_stages_two_workers(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests",
                                      "_profiler_spans_check.py"),
         str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    check_trainer_spans(host_events(tmp_path, TRAIN_SPANS), 2)


def test_no_stage_is_written_without_the_profiler(tmp_path):
    train_tiny(n_workers=1)                 # annotations with nobody to hear
    with profiled(tmp_path):
        jax.numpy.ones(3).block_until_ready()
    assert not any(host_events(tmp_path, TRAIN_SPANS).values())


def serve_some(sample_rate, n=12):
    """``n`` requests through a scheduler over a small exact index, each
    waited for, so that every batch holds one request and pads to 4."""
    from repro.obs import Tracer
    from repro.serve import ExactIndex, RequestScheduler, RetrievalEngine
    rng = np.random.RandomState(0)
    L = jax.numpy.asarray(0.3 * rng.randn(8, 16), jax.numpy.float32)
    G = jax.numpy.asarray(rng.randn(64, 16), jax.numpy.float32)
    tracer = Tracer(sample_rate=sample_rate)
    eng = RetrievalEngine(ExactIndex.build(L, G), k_top=5, buckets=(4,),
                          tracer=tracer)
    eng.search(np.zeros((1, 16), np.float32))            # compile first
    sched = RequestScheduler(eng, max_batch=4, max_wait_ms=0.0)
    try:
        for q in rng.randn(n, 16).astype(np.float32):
            sched.submit(q).result(timeout=30)
    finally:
        assert sched.close(timeout=30)
    return tracer


def test_serving_spans_on_one_thread_are_mirrored(tmp_path):
    with profiled(tmp_path):
        tracer = serve_some(sample_rate=1.0)
    assert len(tracer.drain()) == 12
    ev = host_events(tmp_path, SERVE_SPANS)
    # the cross-thread spans are never mirrored
    assert ev["queue"] == [] and ev["request"] == []
    for name in ("batch", "engine", "cache_lookup", "pad", "device_topk"):
        assert len(ev[name]) == 12, name
    assert all(inside(c, ev["batch"]) for c in ev["engine"])
    for name in ("cache_lookup", "pad", "device_topk"):
        assert all(inside(c, ev["engine"]) for c in ev[name]), name


def test_unsampled_serving_writes_no_span(tmp_path):
    with profiled(tmp_path):
        tracer = serve_some(sample_rate=0.0)
    assert tracer.drain() == []
    assert not any(host_events(tmp_path, SERVE_SPANS).values())


def test_importing_obs_does_not_import_jax():
    code = ("import sys, repro.obs\n"
            "bad = [m for m in sys.modules if m.split('.')[0] == 'jax']\n"
            "assert not bad, bad[:5]\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=60)
    assert proc.returncode == 0, proc.stderr
