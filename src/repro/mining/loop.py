"""The closed loop: train -> refresh the serving index -> mine -> train.

``ClosedLoopTrainer`` alternates PS training steps with serving-index
refreshes. The index always serves neighborhoods under a *recent* metric:
every refresh pushes the current merged L into the index
(``MutableIndex.swap_metric`` for mutable bases — the PR-3 trainer->server
hot swap — or a from-scratch rebuild for frozen bases), then re-mines the
hard-pair pool with ``HardPairMiner`` and swaps it into the
``MinedPairSource`` feeding the workers. This is the first subsystem that
exercises training and serving in one process: the same index answering
retrieval traffic is the constraint producer for the trainer.

Refresh is governed by an explicit staleness policy: every
``refresh_every`` steps, and/or when the objective plateaus (relative
improvement of the recent loss window below ``plateau_tol``). Mining
against a stale metric is not wrong — it is the *asynchronous PS
tradeoff from the paper applied to data*: bounded staleness buys
throughput (no rebuild per step), and the history records exactly how
stale each training step's pairs were (``staleness`` = steps since the
pool's metric was current).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

import jax
import numpy as np

from repro.core import dml
from repro.core.ps import sync, trainer
from repro.core.ps.trainer import DMLTrainConfig
from repro.mining.miner import HardPairMiner, MinerConfig
from repro.mining.stream import CurriculumSchedule, MinedPairSource
from repro.optim import Optimizer, sgd
from repro.serve import (ExactIndex, IVFIndex, MutableIndex,
                         RetrievalEngine)


@dataclasses.dataclass(frozen=True)
class ClosedLoopConfig:
    """Everything above the per-step training math.

    train: the inner DMLTrainConfig (steps, batch, lr, sync model).
    miner / schedule: hard-pair filter knobs + curriculum.
    index: which serving backend mines — "mutable-exact" / "mutable-ivf"
      (refreshed via swap_metric) or "exact" / "ivf" (frozen: refresh
      rebuilds from scratch — correct but pays projection + clustering
      every time; the mutable path is why PR 3 exists).
    index_kwargs: forwarded to the base build (n_clusters, nprobe, ...).
    refresh_every: refresh the index + pool every R steps (0 disables
      periodic refresh — then only plateau triggers fire).
    plateau_window: trailing loss steps inspected for a plateau (0
      disables plateau-triggered refresh).
    plateau_tol: relative improvement of the window's older half over
      its newer half below which the objective counts as plateaued.
    min_refresh_gap: floor between refreshes, so a flat stretch does not
      refresh every step.
    mine_queries: anchors mined per refresh.
    """

    train: DMLTrainConfig
    miner: MinerConfig = MinerConfig()
    schedule: CurriculumSchedule = CurriculumSchedule()
    index: str = "mutable-exact"
    index_kwargs: Optional[dict] = None
    refresh_every: int = 100
    plateau_window: int = 0
    plateau_tol: float = 1e-3
    min_refresh_gap: int = 10
    mine_queries: int = 1024

    def __post_init__(self):
        if self.index not in ("mutable-exact", "mutable-ivf", "exact",
                              "ivf"):
            raise ValueError(f"unknown index kind {self.index!r}")
        if self.refresh_every == 0 and self.plateau_window == 0:
            raise ValueError("no staleness policy: set refresh_every > 0 "
                             "and/or plateau_window > 0")
        if self.mine_queries < 1:
            raise ValueError(f"mine_queries must be >= 1, got "
                             f"{self.mine_queries}")


class ClosedLoopTrainer:
    """Alternates PS training with serving-index refresh + re-mining."""

    def __init__(self, cfg: ClosedLoopConfig, features, labels, *,
                 opt: Optional[Optimizer] = None, mesh=None,
                 engine: Optional[RetrievalEngine] = None,
                 router=None, tenant: Optional[str] = None,
                 shadow_probe: int = 8):
        """Build the serving stack and the mined source (no training yet).

        ``engine`` lets a caller share an existing serving engine (its
        index must be over ``features`` with row ids 0..n-1); by default
        the trainer stands up its own index of ``cfg.index`` kind under
        the *initial* L — the first refresh replaces that metric.

        ``router`` + ``tenant`` close the loop through the multi-tenant
        front end (serve/tenant.py): each metric-swapping refresh also
        registers the fresh L as the tenant's *shadow arm*, mirrors
        ``shadow_probe`` seeded anchor queries through it (so the arm
        carries overlap/latency evidence, visible in the registry and
        the refresh record), then promotes it live — the serving
        tenant's metric tracks training without ever serving a view the
        shadow machinery didn't build.
        """
        self.cfg = cfg
        if (router is None) != (tenant is None):
            raise ValueError("pass router and tenant together (or "
                             "neither)")
        self.router = router
        self.tenant = tenant
        self.shadow_probe = shadow_probe
        if router is not None:
            router.tenant(tenant)   # unknown tenant fails here, not at
            if router.d_in != np.asarray(features).shape[1]:   # refresh
                raise ValueError(
                    f"router gallery d_in={router.d_in} != feature "
                    f"dim {np.asarray(features).shape[1]}")
        self.features = np.asarray(features, np.float32)
        self.labels = np.asarray(labels)
        self.opt = opt or sgd(cfg.train.lr)
        self.mesh = mesh or sync.make_worker_mesh(cfg.train.ps.n_workers,
                                                  cfg.train.ps.axis)
        self.rng = jax.random.PRNGKey(cfg.train.ps.seed)
        self.L0 = dml.init_params(cfg.train.dml, self.rng)
        if engine is None:
            index = self._build_index(np.asarray(self.L0))
            engine = RetrievalEngine(index,
                                     k_top=cfg.miner.k_neighbors + 1)
        self.engine = engine
        self.miner = HardPairMiner(engine, self.features, self.labels,
                                   cfg.miner)
        self.source = MinedPairSource(self.features, self.labels,
                                      cfg.schedule)
        self.n_refreshes = 0
        self.refreshes = []          # per-refresh mining stats records
        # obs: the loop records into the engine's registry/tracer so the
        # closed loop and the serving path share one snapshot; refreshes
        # are rare control-plane transitions, so their traces bypass
        # sampling (force=True)
        self.registry = getattr(engine, "registry", None)
        self.tracer = getattr(engine, "tracer", None)
        if self.registry is not None:
            self._c_refresh = self.registry.counter(
                "loop_refreshes_total", "index refresh + re-mine cycles")
            self._g_staleness = self.registry.gauge(
                "loop_staleness_steps",
                "training steps since the pair pool's metric was current")
            self._g_mined_frac = self.registry.gauge(
                "loop_mined_frac",
                "curriculum fraction of mined pairs in the current batch")
            self._g_pool = self.registry.gauge(
                "loop_pool_size", "pairs in the live mined pool")
            self._g_neg_yield = self.registry.gauge(
                "loop_neg_yield", "hard-negative yield of the last mine")
            self._g_pos_yield = self.registry.gauge(
                "loop_pos_yield", "hard-positive yield of the last mine")

    def _build_index(self, L):
        kw = dict(self.cfg.index_kwargs or {})
        if self.cfg.index.startswith("mutable"):
            return MutableIndex.build(L, self.features,
                                      base=self.cfg.index.split("-")[1],
                                      retain_raw=True, **kw)
        if self.cfg.index == "ivf":
            return IVFIndex.build(L, np.asarray(self.features), **kw)
        return ExactIndex.build(L, np.asarray(self.features), **kw)

    # -- refresh -------------------------------------------------------------

    def refresh(self, L, step: int, swap: bool = True) -> dict:
        """Push L into the index, re-mine, swap the pool. Returns stats.
        ``swap=False`` only re-mines (used for the initial pool, whose
        metric the index was just built with)."""
        trace = (self.tracer.start_trace("refresh", force=True)
                 if self.tracer is not None else None)
        if trace is not None:
            trace.root.set_attrs(step=step, swap=swap)
        if swap:
            L = np.asarray(L, np.float32)
            index = self.engine.index
            if isinstance(index, MutableIndex):
                sp = (trace.span("swap_metric") if trace is not None
                      else None)
                index.swap_metric(L)  # version bump -> engine cache flush
                if sp is not None:
                    sp.set_attrs(rows=index.size).end()
            else:
                # frozen base: rebuild off to the side and repoint the
                # engine (the engine's LRU flushes on the identity change)
                sp = trace.span("rebuild") if trace is not None else None
                self.engine.index = self._build_index(L)
                if sp is not None:
                    sp.set_attrs(kind=self.cfg.index,
                                 rows=self.engine.index.size).end()
        shadow_stats = None
        if swap and self.router is not None:
            # A/B the fresh metric through the tenant's shadow arm:
            # mirror a few seeded anchors for overlap/latency evidence,
            # then promote — the router's deterministic build makes the
            # promoted view identical to a fresh rebuild under L
            p_sp = trace.span("promote") if trace is not None else None
            arm = self.router.register_shadow(self.tenant,
                                              np.asarray(L, np.float32),
                                              sample_rate=1.0)
            probe_rng = np.random.RandomState(
                self.cfg.train.ps.seed + self.n_refreshes)
            probes = probe_rng.randint(
                0, len(self.features),
                size=min(self.shadow_probe, len(self.features)))
            for qid in probes:
                self.router.search(self.tenant, self.features[qid])
            shadow_stats = arm.stats()
            self.router.promote(self.tenant)
            if p_sp is not None:
                p_sp.set_attrs(tenant=self.tenant,
                               n_mirrored=shadow_stats["n_mirrored"],
                               overlap_at_k=shadow_stats["overlap_at_k"]
                               ).end()
        m_sp = trace.span("mine") if trace is not None else None
        result = self.miner.mine(n_queries=self.cfg.mine_queries,
                                 seed=self.cfg.train.ps.seed
                                 + self.n_refreshes)
        if m_sp is not None:
            m_sp.set_attrs(n_queries=self.cfg.mine_queries,
                           n_pairs=result.stats["n_pairs"],
                           neg_yield=result.stats["neg_yield"]).end()
        self.source.set_pool(result)
        self.n_refreshes += 1
        if self.registry is not None:
            self._c_refresh.inc()
            self._g_pool.set(self.source.pool_size)
            self._g_neg_yield.set(result.stats["neg_yield"])
            self._g_pos_yield.set(result.stats["pos_yield"])
            self.registry.event("loop_refresh", step=step,
                                refresh=self.n_refreshes,
                                n_pairs=result.stats["n_pairs"],
                                index_version=result.stats["index_version"])
        if trace is not None:
            self.tracer.finish(trace)
        rec = {"step": step, "refresh": self.n_refreshes, **result.stats}
        if shadow_stats is not None:
            rec["shadow"] = shadow_stats
            rec["promoted_tenant"] = self.tenant
        self.refreshes.append(rec)
        return rec

    def _plateaued(self, trace) -> bool:
        w = self.cfg.plateau_window
        if w == 0 or len(trace) < w:
            return False
        recent = np.asarray(trace[-w:], np.float64)
        old = recent[:w // 2].mean()
        new = recent[w // 2:].mean()
        return (old - new) < self.cfg.plateau_tol * max(abs(old), 1e-12)

    # -- training ------------------------------------------------------------

    def run(self, step_hook=None):
        """Train for ``cfg.train.steps`` with interleaved refreshes.

        Returns (L_merged, history): history["steps"] mirrors
        ``train_dml_distributed`` records plus ``staleness`` (steps since
        the pairs' metric was current) and ``mined_frac``;
        history["refreshes"] holds one mining-stats record per refresh
        (hard-pair yield, engine QPS, index version); history["summary"]
        has the run-level roll-up (refresh count, mean staleness at use,
        total mined pairs). ``step_hook(step, L)`` behaves as in
        ``train_dml_distributed``.
        """
        tcfg = self.cfg.train
        # initial pool under L0: the curriculum starts uniform, but the
        # pool must exist before the ramp's first mined batch (no metric
        # swap — the index was just built with L0)
        self.refresh(self.L0, step=0, swap=False)
        last_refresh = 0
        staleness_sum = 0
        trace = []
        history = []
        # the trainer's own loop; a due refresh runs between its steps,
        # before the next batch is drawn and before the next step donates
        # ``state``
        steps = trainer._train_steps(tcfg, self.source, self.opt,
                                     self.mesh, self.rng)
        with contextlib.closing(steps):
            for t, state, metrics in steps:
                loss = float(metrics["loss"])
                trace.append(loss)
                staleness_sum += t - last_refresh
                if self.registry is not None:   # per-step staleness gauges
                    self._g_staleness.set(t - last_refresh)
                    self._g_mined_frac.set(self.cfg.schedule.mined_frac(t))
                    self._g_pool.set(self.source.pool_size)
                if t % tcfg.log_every == 0 or t == tcfg.steps - 1:
                    rec = {"step": t,
                           **{k: float(v) for k, v in metrics.items()},
                           "staleness": t - last_refresh,
                           "mined_frac": self.cfg.schedule.mined_frac(t),
                           "pool_size": self.source.pool_size}
                    if step_hook is not None:
                        out = step_hook(t, sync.worker_mean(state.params))
                        if out is not None:
                            rec["hook"] = out
                    history.append(rec)
                nxt = t + 1
                if nxt < tcfg.steps and self._due(nxt, last_refresh, trace):
                    self.refresh(sync.worker_mean(state.params), step=nxt)
                    last_refresh = nxt
                    trace = []       # plateau window restarts post-refresh
        L = sync.worker_mean(state.params)
        summary = {
            "n_refreshes": self.n_refreshes,
            "mean_staleness": staleness_sum / max(tcfg.steps, 1),
            "total_mined_pairs": int(sum(r["n_pairs"]
                                         for r in self.refreshes)),
            "neg_yield": float(np.mean([r["neg_yield"]
                                        for r in self.refreshes])),
            "pos_yield": float(np.mean([r["pos_yield"]
                                        for r in self.refreshes])),
            "engine": self.engine.stats(),
        }
        return L, {"steps": history, "refreshes": self.refreshes,
                   "summary": summary}

    def _due(self, t: int, last_refresh: int, trace) -> bool:
        gap = t - last_refresh
        if self.cfg.refresh_every and gap >= self.cfg.refresh_every:
            return True
        return gap >= self.cfg.min_refresh_gap and self._plateaued(trace)
