"""Training cells: Eq. 4 through ``train_dml_distributed``, timed by its hook.

The driver of the traffic kind ``train`` (``drive``).

One call of the trainer is the whole run: its first steps are set-up (the
step compiles at step 0) and feed the correctness check, and the steps
after ``window_start`` are the measured window. ``step_hook`` is the seam:
the trainer calls it at every logged step with the merged factor, after
the step's metrics were read back (so the device is drained there). The
hook keeps the factor after the first step and at the window's start, marks
the window's ends, and ends the run by raising ``WindowClosed`` once
``--seconds`` have passed.

The check follows the contract for training cells, on what the trainer
lets a caller see: its loss at the logged steps of the set-up (steps 0 and
``window_start``), its first gradient as the optimizer got it, worked out
from the factor after one step (SGD: ``(L0 - L1) / lr``), and the change of
its factor over the set-up steps; each against the plain reference run on
the same batches from the same seed. The batches are identified by the
rows they hold: each batch row is matched to its row of the benchmark's
own feature store through a fingerprint, exactly, and each pair's label
is checked against the store's labels.
"""

from __future__ import annotations

import math
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells, data, reference

HIGHEST = jax.lax.Precision.HIGHEST


class WindowClosed(Exception):
    """Raised from the step hook once the measured window is over."""


class RecordedSource:
    """A pair source that hands the program's own batches through, and
    fingerprints the rows of the first ``n_record`` batches of each worker
    (eight random projections of each row) so that the check can name the
    store rows each one holds. After those
    it is a plain pass-through generator."""

    def __init__(self, source, n_record: int, probe):
        self.source, self.n_record, self.probe = source, n_record, probe
        self.records: list = []          # per worker: list of (fx, fy, sim)

    def worker_streams(self, n_workers: int, batch_size: int, seed: int):
        streams = self.source.worker_streams(n_workers, batch_size, seed)
        self.records = [[] for _ in streams]
        return [self._tap(s, rec) for s, rec in zip(streams, self.records)]

    def _tap(self, stream, rec):
        for _ in range(self.n_record):
            b = next(stream)
            rec.append((fingerprint(b["xs"], self.probe),
                        fingerprint(b["ys"], self.probe), b["sim"]))
            yield b
        yield from stream


@jax.jit
def fingerprint(x, probe):
    return jax.lax.dot_general(x, probe, (((1,), (0,)), ((), ())),
                               precision=HIGHEST)


def nearest_rows(fp_rows, fp_store_sorted, order):
    """The store row whose fingerprint is nearest each row's, searched
    among the store rows whose first column lies within a rounding
    tolerance of the row's (the store's fingerprints are summed in
    another order than a batch's, so they agree only to rounding)."""
    key = fp_rows[:, 0]
    tol = 1e-3 * (np.abs(fp_rows).max(axis=1) + 1.0)
    col = fp_store_sorted[:, 0]
    lo = np.searchsorted(col, key - tol)
    hi = np.searchsorted(col, key + tol, side="right")
    best = np.full(len(key), -1, np.int64)
    best_err = np.full(len(key), np.inf)
    for j in range(int((hi - lo).max(initial=0))):
        cand = np.minimum(lo + j, len(col) - 1)
        err = np.abs(fp_store_sorted[cand] - fp_rows).max(axis=1)
        better = (lo + j < hi) & (err < best_err)
        best[better] = order[cand[better]]
        best_err[better] = err[better]
    return best


class Hook:
    """The trainer's ``step_hook``: keeps the factor after the first step
    and at the window's start, times the window and closes it."""

    def __init__(self, seconds: float, window_start: int, on_start=None,
                 on_stop=None):
        self.seconds, self.window_start = seconds, window_start
        self.on_start, self.on_stop = on_start, on_stop
        self.L_first = self.L_start = None
        self.t0 = self.t1 = None
        self.step0 = self.step1 = None

    def __call__(self, t, L):
        if t == 0:
            self.L_first = L
        if t == self.window_start:
            self.L_start = L
            if self.on_start is not None:
                self.on_start()
            self.t0, self.step0 = time.perf_counter(), t
        elif t > self.window_start and self.t0 is not None:
            now = time.perf_counter()
            if now - self.t0 >= self.seconds:
                self.t1, self.step1 = now, t
                if self.on_stop is not None:
                    self.on_stop()
                raise WindowClosed


def trainer_history(tb) -> list:
    """The trainer's per-logged-step records, read from its frame in the
    traceback of ``WindowClosed`` (the trainer returns them only when it
    runs to its last step)."""
    found = None
    while tb is not None:
        h = tb.tb_frame.f_locals.get("history")
        if (isinstance(h, list) and h and isinstance(h[0], dict)
                and "loss" in h[0] and "step" in h[0]):
            found = h
        tb = tb.tb_next
    if found is None:
        raise RuntimeError("the trainer's loss history was not found")
    return list(found)


def build(cfg: dict, seed: int):
    """Make the cell's store, pair pool and trainer config from the seed,
    as the configuration states them."""
    from repro.core import dml
    from repro.core.ps import sync
    from repro.core.ps.trainer import DMLTrainConfig
    from repro.data.pairs import IndexPairSource

    key = data.base_key(seed)
    feats, labels = data.make_rows(
        key, stream=data.TRAIN, rows=cfg["n_samples"],
        n_classes=cfg["n_classes"], feat_dim=cfg["feat_dim"],
        sparsity=cfg["sparsity"], noise=cfg["noise"])
    labels = np.asarray(labels)
    pool = data.pair_pool(labels, cfg["n_similar"], cfg["n_dissimilar"],
                          seed)
    probe = jax.random.normal(jax.random.fold_in(key, 99),
                              (cfg["feat_dim"], 8), jnp.float32)
    dml_cfg = dml.DMLConfig(feat_dim=cfg["feat_dim"],
                            proj_dim=cfg["proj_dim"], lam=cfg["lam"],
                            margin=cfg["margin"])
    ps_seed = int(seed) % (2 ** 31)
    tcfg = DMLTrainConfig(
        dml=dml_cfg,
        ps=sync.PSConfig(n_workers=cfg["n_workers"], sync=cfg["sync"],
                         seed=ps_seed),
        batch_size=cfg["batch_size"], steps=10 ** 9, lr=cfg["lr"],
        log_every=cfg["log_every"])
    return dict(feats=feats, labels=labels, probe=probe, tcfg=tcfg,
                ps_seed=ps_seed, source=IndexPairSource(feats, pool))


def run_trainer(env: dict, hook: Hook, n_record: int):
    """Run the trainer until the hook closes the window. Returns (history,
    recorded source)."""
    from repro.core.ps.trainer import train_dml_distributed

    src = RecordedSource(env["source"], n_record, env["probe"])
    try:
        _, history = train_dml_distributed(env["tcfg"], src, step_hook=hook)
    except WindowClosed as e:
        history = trainer_history(e.__traceback__)
        traceback.clear_frames(e.__traceback__)
    return history, src


def identify(env: dict, src, n_steps: int):
    """The store rows of each recorded step's batch, all workers' batches
    concatenated: ([(a, b, sim)] per step, numbers). Each batch row is
    matched to the store row with the nearest fingerprint, and the match
    is then made exact: the matched store rows, gathered in the batch's
    own shape, must give the batch's fingerprints bit for bit. ``sim`` is
    the store's own label of each pair; the numbers count batch rows that
    are no store row and pairs whose label the program got wrong."""
    feats, labels, probe = env["feats"], env["labels"], env["probe"]
    fp_store = np.asarray(fingerprint(feats, probe), np.float64)
    order = np.argsort(fp_store[:, 0], kind="stable")
    fp_sorted = fp_store[order]

    def rows_of(fp):
        fp = np.asarray(fp)
        idx = nearest_rows(fp.astype(np.float64), fp_sorted, order)
        again = np.asarray(fingerprint(feats[jnp.asarray(np.maximum(idx, 0))],
                                       probe))
        return np.where((idx >= 0) & np.all(again == fp, axis=1), idx, -1)

    steps, missing, mislabelled = [], 0, 0
    for t in range(n_steps):
        a_all, b_all, s_all = [], [], []
        for rec in src.records:
            fx, fy, sim = rec[t]
            a, b = rows_of(fx), rows_of(fy)
            missing += int((a < 0).sum() + (b < 0).sum())
            ok = (a >= 0) & (b >= 0)
            truth = (labels[a] == labels[b]).astype(np.int32)
            mislabelled += int(((np.asarray(sim) != truth) & ok).sum())
            a_all.append(a)
            b_all.append(b)
            s_all.append(truth)
        steps.append((np.concatenate(a_all), np.concatenate(b_all),
                      np.concatenate(s_all)))
    return steps, {"rows_not_in_store": float(missing),
                   "pairs_mislabelled": float(mislabelled)}


def program_seen(cfg: dict, history: list, hook: Hook, check_step: int):
    """What the check compares of the program: (loss at each logged step
    up to ``check_step``, factor after the first step, factor after
    ``check_step``), the factors on one device."""
    logged = range(0, check_step + 1, cfg["log_every"])
    by_step = {h["step"]: h["loss"] for h in history}
    dev = jax.devices()[0]
    return ({t: by_step[t] for t in logged},
            jax.device_put(hook.L_first, dev),
            jax.device_put(hook.L_start, dev))


def check(env: dict, cfg: dict, seen, steps, check_step: int, *,
          control: bool = False):
    """The compared numbers of a training cell (see the module doc) for
    ``seen`` = (losses by logged step, L after step 0, L after
    ``check_step``); with ``control``, also those of the control, the
    reference computed in bfloat16 in the program's place. Returns
    (numbers, control numbers or None)."""
    L0 = reference.init_factor(env["ps_seed"], cfg["proj_dim"],
                               cfg["feat_dim"])
    kw = dict(lr=cfg["lr"], lam=cfg["lam"], margin=cfg["margin"])
    steps = steps[:check_step + 1]
    ref = reference.train(L0, env["feats"], steps, **kw)
    nums = compare(ref, seen, L0, cfg["lr"])
    if not control:
        return nums, None
    losses, first, last = reference.train(L0, env["feats"], steps,
                                          dtype=jnp.bfloat16, **kw)
    low = ({t: losses[t] for t in seen[0]}, first, last)
    return nums, compare(ref, low, L0, cfg["lr"])


def compare(ref, seen, L0, lr: float) -> dict:
    """Gaps of ``seen`` from the reference ``ref`` = (losses, L after step
    0, L after the last step): the worst loss gap over the logged steps,
    and the gaps of the first gradient's norm and of the change's norm,
    each as a share of the reference's."""
    r_losses, r_first, r_last = ref
    losses, first, last = seen
    f32 = jnp.float32

    def norm(x):
        return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(f32)))))

    def gap(a, b):
        return abs(norm(a) - norm(b)) / norm(b)

    return {
        "loss_gap": max(abs(v - r_losses[t]) / abs(r_losses[t])
                        for t, v in losses.items()),
        "grad_norm_gap": gap((L0 - first.astype(f32)) / lr,
                             (L0 - r_first.astype(f32)) / lr),
        "change_norm_gap": gap(last.astype(f32) - L0,
                               r_last.astype(f32) - L0),
    }


def drive(cfg, traffic, *, seed, seconds, prof, t_start, devices,
          control=False):
    """One run of a training cell: the trainer from the seed, its first
    ``window_start`` steps as set-up, ``seconds`` of steps as the window,
    then the check of the set-up steps. Returns (ctx, numbers, control
    numbers or None)."""
    start = traffic["window_start"]
    env = build(cfg, seed)

    def on_start():
        cells.settle_heap()
        if prof is not None:
            prof.start()
            prof.mark()

    hook = Hook(seconds, start, on_start=on_start,
                on_stop=prof.stop if prof is not None else None)
    history, src = run_trainer(env, hook, n_record=start + 1)
    peak = cells.peak_bytes(devices)
    cells.release_heap()
    steps = hook.step1 - hook.step0
    in_window = [h for h in history if h["step"] > hook.step0]
    ctx = {
        "kind": "train",
        "setup_s": hook.t0 - t_start,
        "window_s": hook.t1 - hook.t0,
        "steps": steps,
        "pairs": steps * cfg["batch_size"] * cfg["n_workers"],
        "attempted": steps,
        "failed": sum(not math.isfinite(h["loss"]) for h in in_window),
        "memory_peak_bytes": peak,
    }
    steps, nums = identify(env, src, start + 1)
    low = None
    if not any(nums.values()):
        seen = program_seen(cfg, history, hook, start)
        prog, low = check(env, cfg, seen, steps, start, control=control)
        nums.update(prog)
    return ctx, nums, low

