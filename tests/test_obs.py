"""Unified observability layer (obs/): metric instruments, registry
export, deterministic tracing, and the instrumentation threaded through
engine -> scheduler -> index lifecycle.

Everything time-dependent runs on FakeClock, so durations, histogram
contents, and span windows are asserted *exactly* — no sleeps, no
approx-latency flakiness.
"""

import json
import math
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from repro.obs import (DEFAULT_LATENCY_BUCKETS, MetricsRegistry, NULL_SPAN,
                       Tracer, index_memory, log_buckets, merge_snapshots,
                       parse_label_key, percentile, span_names)
from repro.serve import (ExactIndex, FakeClock, IVFIndex, MutableIndex,
                         RequestScheduler, RetrievalEngine, load_index,
                         save_index)


# ---------------------------------------------------------------------------
# percentile: THE deduped implementation (satellite: the old
# sorted[int(n * q) - 1] underflowed to the minimum at small n)


class TestPercentile:
    def test_small_n_high_percentile_is_not_the_minimum(self):
        # regression: with n=2, int(2 * 0.99) - 1 == 0 -> the *minimum*
        # was reported as p99. Interpolation must stay near the max.
        assert percentile([10.0, 20.0], 99.0) == pytest.approx(19.9)
        assert percentile([10.0, 20.0], 50.0) == pytest.approx(15.0)

    def test_single_sample_every_q(self):
        for q in (0.0, 50.0, 99.0, 100.0):
            assert percentile([7.5], q) == 7.5

    def test_empty_is_nan(self):
        assert math.isnan(percentile([], 99.0))
        out = percentile([], (50.0, 99.0))
        assert len(out) == 2 and all(math.isnan(v) for v in out)

    def test_matches_numpy_and_sequence_q(self):
        rng = np.random.RandomState(0)
        vals = rng.randn(101).tolist()
        assert percentile(vals, 90.0) == pytest.approx(
            float(np.percentile(vals, 90.0)))
        p50, p99 = percentile(vals, (50.0, 99.0))
        assert p50 == pytest.approx(float(np.percentile(vals, 50.0)))
        assert p99 == pytest.approx(float(np.percentile(vals, 99.0)))


class TestLogBuckets:
    def test_default_spans_serving_range(self):
        b = log_buckets()
        assert b == DEFAULT_LATENCY_BUCKETS
        assert b[0] == pytest.approx(1e-4)
        assert b[-1] == pytest.approx(60.0, rel=0.5)
        assert list(b) == sorted(set(b))

    def test_bad_range_raises(self):
        with pytest.raises(ValueError):
            log_buckets(lo=0.0)
        with pytest.raises(ValueError):
            log_buckets(lo=1.0, hi=0.5)


# ---------------------------------------------------------------------------
# instruments + registry


class TestInstruments:
    def test_counter_exact_and_monotone(self):
        reg = MetricsRegistry(clock=FakeClock())
        c = reg.counter("reqs_total", labelnames=("cls",))
        c.inc(cls="a")
        c.inc(2.5, cls="a")
        c.inc(cls="b")
        assert c.value(cls="a") == 3.5
        assert c.value(cls="b") == 1.0
        assert c.total() == 4.5
        with pytest.raises(ValueError):
            c.inc(-1.0, cls="a")
        with pytest.raises(ValueError):
            c.inc(cls="a", extra="nope")     # undeclared label

    def test_gauge_set_inc(self):
        reg = MetricsRegistry(clock=FakeClock())
        g = reg.gauge("depth")
        g.set(4)
        g.inc(-1.5)
        assert g.value() == 2.5

    def test_histogram_exact_bucket_placement(self):
        reg = MetricsRegistry(clock=FakeClock())
        h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 2.5, 100.0):
            h.observe(v)
        # bisect_left: a boundary value lands in its own bucket
        assert h.counts() == [2, 0, 1, 1]
        assert h.count() == 4
        assert h.sum() == 0.5 + 1.0 + 2.5 + 100.0
        # upper-bound percentile readout; overflow bucket reads inf
        assert h.percentile(50.0) == 1.0
        assert h.percentile(100.0) == float("inf")
        assert math.isnan(reg.histogram("empty",
                                        buckets=(1.0,)).percentile(50.0))

    def test_registry_get_or_create_idempotent(self):
        reg = MetricsRegistry(clock=FakeClock())
        c1 = reg.counter("x_total", labelnames=("cls",))
        assert reg.counter("x_total", labelnames=("cls",)) is c1
        with pytest.raises(ValueError):
            reg.gauge("x_total")                       # kind collision
        with pytest.raises(ValueError):
            reg.counter("x_total", labelnames=("other",))
        h = reg.histogram("h", buckets=(1.0, 2.0))
        assert reg.histogram("h") is h                 # buckets omitted ok
        with pytest.raises(ValueError):
            reg.histogram("h", buckets=(1.0, 3.0))

    def test_label_key_round_trip(self):
        reg = MetricsRegistry(clock=FakeClock())
        c = reg.counter("y_total", labelnames=("cls", "outcome"))
        c.inc(cls="interactive", outcome="completed")
        (key,) = c.label_keys()
        assert parse_label_key(key) == {"cls": "interactive",
                                        "outcome": "completed"}

    def test_threaded_increments_are_exact(self):
        # satellite: the engine's old bare-attribute counters lost
        # increments under concurrent read-modify-write; the registry
        # lock makes totals exact, not approximate
        reg = MetricsRegistry(clock=FakeClock())
        c = reg.counter("stress_total")
        h = reg.histogram("stress_lat", buckets=(1.0,))
        n_threads, n_each = 8, 1000

        def work():
            for _ in range(n_each):
                c.inc()
                h.observe(0.5)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == n_threads * n_each
        assert h.counts() == [n_threads * n_each, 0]


class TestRegistryExport:
    def _reg(self):
        clock = FakeClock(t0=100.0)
        reg = MetricsRegistry(clock=clock)
        reg.counter("a_total", "help a", labelnames=("cls",)).inc(
            3, cls="x")
        reg.gauge("g").set(7)
        reg.histogram("h", buckets=(1.0, 2.0)).observe(1.5)
        reg.event("boot", detail="ok")
        return reg, clock

    def test_snapshot_schema_and_collectors(self):
        reg, clock = self._reg()
        reg.register_collector(lambda: reg.gauge("derived").set(42))
        snap = reg.snapshot()
        assert set(snap) == {"t", "counters", "gauges", "histograms",
                             "events"}
        assert snap["t"] == 100.0
        assert snap["counters"]["a_total"]["values"] == {"cls=x": 3.0}
        assert snap["gauges"]["derived"]["values"][""] == 42.0
        cell = snap["histograms"]["h"]["values"][""]
        assert cell == {"counts": [0, 1, 0], "sum": 1.5, "count": 1}
        (ev,) = snap["events"]
        assert ev["event"] == "boot" and ev["detail"] == "ok"
        assert ev["t"] == 100.0

    def test_events_bounded_oldest_dropped(self):
        reg = MetricsRegistry(clock=FakeClock(), max_events=4)
        for i in range(6):
            reg.event("e", i=i)
        evs = reg.events("e")
        assert [e["i"] for e in evs] == [2, 3, 4, 5]

    def test_merge_counters_add_gauges_later_wins(self):
        reg_a, _ = self._reg()
        reg_b, _ = self._reg()
        reg_b.gauge("g").set(9)
        merged = merge_snapshots(reg_a.snapshot(), reg_b.snapshot())
        assert merged["counters"]["a_total"]["values"]["cls=x"] == 6.0
        assert merged["gauges"]["g"]["values"][""] == 9.0
        cell = merged["histograms"]["h"]["values"][""]
        assert cell == {"counts": [0, 2, 0], "sum": 3.0, "count": 2}
        assert [e["event"] for e in merged["events"]] == ["boot", "boot"]

    def test_merge_bucket_mismatch_raises(self):
        reg_a, _ = self._reg()
        other = MetricsRegistry(clock=FakeClock())
        other.histogram("h", buckets=(1.0, 3.0)).observe(0.5)
        with pytest.raises(ValueError):
            merge_snapshots(reg_a.snapshot(), other.snapshot())

    def test_exposition_cumulative_buckets(self):
        reg, _ = self._reg()
        text = reg.exposition()
        assert "# TYPE a_total counter" in text
        assert 'a_total{cls="x"} 3' in text
        assert "# TYPE h histogram" in text
        assert 'h_bucket{le="1"} 0' in text
        assert 'h_bucket{le="2"} 1' in text
        assert 'h_bucket{le="+Inf"} 1' in text      # cumulative
        assert "h_sum 1.5" in text and "h_count 1" in text

    def test_write_snapshot_round_trips(self, tmp_path):
        reg, _ = self._reg()
        path = tmp_path / "snap.json"
        written = reg.write_snapshot(str(path))
        assert json.loads(path.read_text()) == written


# ---------------------------------------------------------------------------
# tracing


class TestTracer:
    def test_fake_clock_exact_span_windows(self):
        clock = FakeClock()
        tracer = Tracer(clock=clock, sample_rate=1.0)
        tr = tracer.start_trace()
        assert tr.sampled and tr.root.t_start == 0.0
        clock.advance(1.0)
        sp = tr.span("queue").set_attrs(cls="interactive")
        clock.advance(0.5)
        sp.end()
        sp.end()                             # idempotent: first end wins
        clock.advance(0.25)
        assert sp.t_start == 1.0 and sp.t_end == 1.5
        assert sp.duration == 0.5
        tracer.finish(tr)
        (d,) = tracer.drain()
        assert d["trace_id"] == tr.trace_id
        assert span_names(d) == ["request", "queue"]
        assert d["root"]["t_end"] == 1.75
        assert d["root"]["children"][0]["attrs"] == {"cls": "interactive"}

    def test_deterministic_sampling_every_fourth(self):
        tracer = Tracer(clock=FakeClock(), sample_rate=0.25)
        sampled = [tracer.start_trace().sampled for _ in range(8)]
        assert sampled == [False, False, False, True] * 2
        assert tracer.n_minted == 8 and tracer.n_sampled == 2

    def test_rate_edges_and_validation(self):
        assert not any(Tracer(clock=FakeClock(),
                              sample_rate=0.0).start_trace().sampled
                       for _ in range(3))
        t1 = Tracer(clock=FakeClock(), sample_rate=1.0)
        assert all(t1.start_trace().sampled for _ in range(3))
        with pytest.raises(ValueError):
            Tracer(clock=FakeClock(), sample_rate=1.5)

    def test_force_bypasses_sampling(self):
        tracer = Tracer(clock=FakeClock(), sample_rate=0.0)
        tr = tracer.start_trace("refresh", force=True)
        assert tr.sampled and tr.root.name == "refresh"

    def test_unsampled_spans_are_null_and_free(self):
        tracer = Tracer(clock=FakeClock(), sample_rate=0.0)
        tr = tracer.start_trace()
        sp = tr.span("anything")
        assert sp is NULL_SPAN
        assert sp.child("x").set_attrs(a=1).end() is NULL_SPAN
        tracer.finish(tr)                    # dropped, not buffered
        assert tracer.drain() == []

    def test_mirror_holds_one_annotation_per_span_until_it_ends(
            self, monkeypatch):
        import repro.obs.trace as obs_trace
        log = []

        class Annotation:                    # stands in for the profiler's
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                log.append(("enter", self.name))

            def __exit__(self, *exc):
                log.append(("exit", self.name))

        monkeypatch.setattr(obs_trace, "_annotation", Annotation)
        tracer = Tracer(clock=FakeClock(), sample_rate=1.0)
        tr = tracer.start_trace()
        q = tr.span("queue")                 # cross-thread: not mirrored
        b = tr.span("batch", mirror=True)
        e = tr.span("engine", parent=b)      # a mirror's child mirrors
        with e.child("pad"):
            pass
        e.end()
        e.end()                              # one exit, at the first end
        b.end()
        q.end()
        tracer.finish(tr)
        assert log == [("enter", "batch"), ("enter", "engine"),
                       ("enter", "pad"), ("exit", "pad"),
                       ("exit", "engine"), ("exit", "batch")]
        assert span_names(tracer.drain()[0]) == ["request", "queue",
                                                 "batch", "engine", "pad"]
        tracer.start_trace().span("x").child("y").end()
        assert Tracer(clock=FakeClock()).start_trace().span(
            "batch", mirror=True) is NULL_SPAN  # unsampled: nothing opens
        assert len(log) == 6

    def test_trace_ids_unique_and_ring_bounded(self):
        tracer = Tracer(clock=FakeClock(), sample_rate=1.0, max_traces=4)
        ids = set()
        for _ in range(10):
            tr = tracer.start_trace()
            ids.add(tr.trace_id)
            tracer.finish(tr)
        assert len(ids) == 10
        assert len(tracer.drain()) == 4      # oldest evicted

    def test_write_jsonl(self, tmp_path):
        tracer = Tracer(clock=FakeClock(), sample_rate=1.0)
        for _ in range(3):
            tracer.finish(tracer.start_trace())
        path = tmp_path / "traces.jsonl"
        assert tracer.write_jsonl(str(path), append=False) == 3
        lines = path.read_text().splitlines()
        assert len(lines) == 3
        assert all("trace_id" in json.loads(ln) for ln in lines)


# ---------------------------------------------------------------------------
# engine instrumentation (FakeClock-exact: the stub index advances the
# clock inside topk, so measured device time is known to the bit)

_DT = 1.0 / 128.0       # exactly representable: sums stay exact


class _StubIndex:
    """MetricIndex test double whose topk advances a FakeClock by a
    known amount — device time becomes deterministic."""

    def __init__(self, clock, d=4, size=100, dt=_DT):
        self.L = np.zeros((2, d), np.float32)
        self.version = 0
        self.size = size
        self.n_shards = 1
        self.scan_impl = "xla"
        self.nprobe = 3
        self._clock = clock
        self._dt = dt

    def topk(self, queries, k_top, backend="xla", **kw):
        self._clock.advance(self._dt)
        n = queries.shape[0]
        dists = np.zeros((n, k_top), np.float32)
        idxs = np.tile(np.arange(k_top, dtype=np.int32), (n, 1))
        return dists, idxs


class TestEngineObs:
    def test_busy_time_and_histogram_exact(self):
        clock = FakeClock()
        eng = RetrievalEngine(_StubIndex(clock), k_top=5, cache_size=0,
                              buckets=(8,), clock=clock)
        q = np.zeros((3, 4), np.float32)
        eng.search(q)
        eng.search(q)
        assert eng.busy_s == 2 * _DT
        assert eng.n_requests == 2
        assert eng.n_queries == 6 and eng.n_device_queries == 6
        h = eng.registry.histogram("engine_search_seconds")
        assert h.count() == 2 and h.sum() == 2 * _DT

    def test_search_span_tree_and_attrs(self):
        clock = FakeClock()
        eng = RetrievalEngine(_StubIndex(clock), k_top=5, cache_size=16,
                              buckets=(8,), clock=clock)
        tracer = Tracer(clock=clock, sample_rate=1.0)
        q = np.ones((3, 4), np.float32)

        tr = tracer.start_trace()
        eng.search(q, span=tr.root)          # miss -> full device path
        tracer.finish(tr)
        (d,) = tracer.drain()
        assert span_names(d) == ["request", "cache_lookup", "pad",
                                 "device_topk"]
        lookup, pad, topk = d["root"]["children"]
        assert lookup["attrs"] == {"hit": False, "rows": 3}
        assert pad["attrs"] == {"rows": 3, "bucket": 8}
        assert topk["attrs"] == {"batch": 8, "k": 5, "scan_impl": "xla",
                                 "nprobe": 3, "rerank_depth": None}
        assert topk["t_end"] - topk["t_start"] == _DT

        tr2 = tracer.start_trace()
        eng.search(q, span=tr2.root)         # repeat -> full cache hit
        tracer.finish(tr2)
        (d2,) = tracer.drain()
        assert span_names(d2) == ["request", "cache_lookup"]
        assert d2["root"]["children"][0]["attrs"] == {"hit": True,
                                                      "rows": 3}

    def test_concurrent_search_counters_exact(self):
        # the data-race satellite at the engine level: concurrent
        # callers must never lose a counter increment
        clock = FakeClock()
        eng = RetrievalEngine(_StubIndex(clock), k_top=5, cache_size=0,
                              buckets=(8,), clock=clock)
        n_threads, n_each, rows = 8, 50, 2

        def work():
            q = np.zeros((rows, 4), np.float32)
            for _ in range(n_each):
                eng.search(q)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert eng.n_requests == n_threads * n_each
        assert eng.n_queries == n_threads * n_each * rows
        assert eng.n_device_queries == n_threads * n_each * rows

    def test_stats_is_a_view_over_the_registry(self):
        rng = np.random.RandomState(0)
        L = jnp.asarray(0.3 * rng.randn(8, 16), jnp.float32)
        G = jnp.asarray(rng.randn(64, 16), jnp.float32)
        eng = RetrievalEngine(ExactIndex.build(L, G), k_top=5)
        q = rng.randn(4, 16).astype(np.float32)
        eng.search(q)
        eng.search(q)
        st = eng.stats()
        reg = eng.registry
        assert st["n_requests"] == 2
        assert st["n_queries"] == reg.counter(
            "engine_queries_total").value() == 8
        assert st["cache_hits"] == reg.counter(
            "engine_cache_hits_total").value() == 4
        assert st["cache_misses"] == 4
        assert st["busy_s"] == reg.histogram(
            "engine_search_seconds").sum() > 0

    def test_memory_gauges_follow_index_swap(self):
        rng = np.random.RandomState(0)
        L = jnp.asarray(0.3 * rng.randn(8, 16), jnp.float32)
        G = jnp.asarray(rng.randn(64, 16), jnp.float32)
        eng = RetrievalEngine(ExactIndex.build(L, G), k_top=5)
        snap = eng.registry.snapshot()
        mem = snap["gauges"]["index_memory_bytes"]["values"]
        expect = index_memory(eng.index)
        assert mem["component=gallery"] == expect["gallery"] > 0
        assert mem["component=delta"] == 0
        # swap to an index with no resident arrays: bytes must zero out,
        # not dangle at the old backend's values
        eng.index = _StubIndex(FakeClock())
        mem2 = eng.registry.snapshot()["gauges"][
            "index_memory_bytes"]["values"]
        assert all(v == 0 for v in mem2.values())


class TestIndexMemory:
    def _build(self):
        rng = np.random.RandomState(0)
        L = jnp.asarray(0.3 * rng.randn(8, 16), jnp.float32)
        G = jnp.asarray(rng.randn(200, 16), jnp.float32)
        return L, G, rng

    def test_exact_components(self):
        L, G, _ = self._build()
        idx = ExactIndex.build(L, G)
        mem = index_memory(idx)
        assert mem["gallery"] == idx.gp.nbytes + idx.gn.nbytes
        assert "codes" not in mem and "delta" not in mem

    def test_ivf_has_centroids(self):
        L, G, _ = self._build()
        ivf = IVFIndex.build(L, G, n_clusters=8, seed=0)
        mem = index_memory(ivf)
        assert mem["centroids"] == ivf.centroids.nbytes
        assert mem["gallery"] > 0

    def test_mutable_adds_delta_and_host_store(self):
        L, G, rng = self._build()
        mut = MutableIndex.build(L, G, retain_raw=True,
                                 auto_compact_delta=0, auto_compact_dead=0)
        base_mem = index_memory(mut.base)
        mut.upsert(rng.randn(10, 16).astype(np.float32))
        mem = index_memory(mut)
        assert mem["delta"] > 0
        assert mem["host_store"] > 0
        assert mem["gallery"] == base_mem["gallery"]


# ---------------------------------------------------------------------------
# lifecycle events (mutable index + snapshot persistence)


class TestLifecycleEvents:
    def _mut(self, reg):
        rng = np.random.RandomState(0)
        L = jnp.asarray(0.3 * rng.randn(8, 16), jnp.float32)
        G = jnp.asarray(rng.randn(200, 16), jnp.float32)
        mut = MutableIndex.build(L, G, retain_raw=True,
                                 auto_compact_delta=0, auto_compact_dead=0)
        mut.registry = reg
        return mut, rng

    def test_compaction_event(self):
        reg = MetricsRegistry(clock=FakeClock())
        mut, rng = self._mut(reg)
        mut.upsert(rng.randn(10, 16).astype(np.float32))
        mut.delete(np.arange(5))
        assert mut.compact()
        (ev,) = reg.events("index_compaction")
        assert ev["delta_rows"] == 10 and ev["tombstones"] == 5
        assert ev["size"] == mut.size
        assert reg.counter("index_lifecycle_total",
                           labelnames=("event",)).value(
                               event="compaction") == 1

    def test_swap_metric_event(self):
        reg = MetricsRegistry(clock=FakeClock())
        mut, rng = self._mut(reg)
        L2 = jnp.asarray(0.3 * rng.randn(8, 16), jnp.float32)
        mut.swap_metric(L2)
        (ev,) = reg.events("index_swap_metric")
        assert ev["rows"] == mut.size

    def test_snapshot_save_load_events(self, tmp_path):
        reg = MetricsRegistry(clock=FakeClock())
        mut, _ = self._mut(reg)
        save_index(mut, str(tmp_path))
        (ev,) = reg.events("index_snapshot_save")
        assert ev["size"] == mut.size
        reg2 = MetricsRegistry(clock=FakeClock())
        load_index(str(tmp_path), registry=reg2)
        (ev2,) = reg2.events("index_snapshot_load")
        assert ev2["size"] == mut.size


# ---------------------------------------------------------------------------
# end-to-end: trace-id propagation scheduler -> engine, sampling knob


class TestSchedulerTracing:
    def _stack(self, sample_rate):
        rng = np.random.RandomState(0)
        L = jnp.asarray(0.3 * rng.randn(8, 16), jnp.float32)
        G = jnp.asarray(rng.randn(128, 16), jnp.float32)
        eng = RetrievalEngine(ExactIndex.build(L, G), k_top=5,
                              buckets=(8,))
        eng.tracer.sample_rate = sample_rate
        sched = RequestScheduler(eng, max_wait_ms=1.0, degrade=False)
        return eng, sched, rng

    def test_trace_covers_submit_to_device_topk(self):
        eng, sched, rng = self._stack(sample_rate=1.0)
        futs = [sched.submit(rng.randn(16).astype(np.float32))
                for _ in range(5)]
        for f in futs:
            f.result(timeout=30)
        sched.close()
        traces = eng.tracer.drain()
        assert len(traces) == 5
        assert len({t["trace_id"] for t in traces}) == 5
        for t in traces:
            names = span_names(t)
            assert names[:2] == ["request", "queue"]
            assert t["root"]["attrs"]["outcome"] == "completed"
            assert t["root"]["attrs"]["cls"] == "interactive"
        # the batch's carrier rider records the full engine path — the
        # ISSUE's acceptance span set
        full = [t for t in traces
                if {"batch", "engine", "device_topk"} <=
                set(span_names(t))]
        assert full, "no trace covers batch -> engine -> device_topk"
        # spans nest: every child window sits inside its parent's
        t = full[0]

        def check(span):
            for c in span["children"]:
                assert span["t_start"] <= c["t_start"]
                assert c["t_end"] <= span["t_end"]
                check(c)

        check(t["root"])

    def test_sampling_rate_honored_end_to_end(self):
        eng, sched, rng = self._stack(sample_rate=0.5)
        futs = [sched.submit(rng.randn(16).astype(np.float32))
                for _ in range(6)]
        for f in futs:
            f.result(timeout=30)
        sched.close()
        assert eng.tracer.n_minted == 6
        assert eng.tracer.n_sampled == 3
        assert len(eng.tracer.drain()) == 3

    def test_zero_rate_mints_nothing(self):
        eng, sched, rng = self._stack(sample_rate=0.0)
        sched.submit(rng.randn(16).astype(np.float32)).result(timeout=30)
        sched.close()
        assert eng.tracer.n_minted == 0      # perf guard: no mint at all
        assert eng.tracer.drain() == []

    def test_registry_snapshot_spans_the_whole_stack(self):
        # the ISSUE's acceptance snapshot: one snapshot from a scheduler
        # run holds front-end, engine, and index figures together
        eng, sched, rng = self._stack(sample_rate=1.0)
        futs = [sched.submit(rng.randn(16).astype(np.float32))
                for _ in range(4)]
        for f in futs:
            f.result(timeout=30)
        sched.close()
        snap = eng.registry.snapshot()
        assert snap["counters"]["engine_requests_total"]["values"]
        assert snap["counters"]["frontend_requests_total"]["values"][
            "cls=interactive,outcome=completed"] == 4.0
        assert "cls=interactive" in snap["histograms"][
            "frontend_latency_seconds"]["values"]
        assert "cls=interactive" in snap["gauges"][
            "frontend_queue_depth"]["values"]
        assert "" in snap["gauges"]["frontend_degradation_level"]["values"]
        assert snap["gauges"]["index_memory_bytes"]["values"][
            "component=gallery"] > 0


class TestMetricsReport:
    def test_render_smoke(self):
        from repro.launch.metrics_report import render
        rng = np.random.RandomState(0)
        L = jnp.asarray(0.3 * rng.randn(8, 16), jnp.float32)
        G = jnp.asarray(rng.randn(64, 16), jnp.float32)
        eng = RetrievalEngine(ExactIndex.build(L, G), k_top=5)
        q = rng.randn(4, 16).astype(np.float32)
        eng.search(q)
        eng.search(q)
        eng.registry.event("index_compaction", size=64)
        text = render(eng.registry.snapshot())
        assert "== serving ==" in text
        assert "hit rate" in text
        assert "== index memory ==" in text
        assert "index_compaction" in text

    def test_render_reads_busy_time_from_the_search_histogram(self):
        from repro.launch.metrics_report import render
        clock = FakeClock()
        eng = RetrievalEngine(_StubIndex(clock), k_top=5, cache_size=0,
                              buckets=(8,), clock=clock)
        for _ in range(4):
            eng.search(np.zeros((2, 4), np.float32))
        assert "engine_busy_seconds_total" not in eng.registry.snapshot()[
            "counters"]
        text = render(eng.registry.snapshot())
        busy = eng.registry.histogram("engine_search_seconds").sum()
        assert busy == eng.busy_s == 4 * _DT
        assert f"(8 on device, {busy:.3f}s busy, {8 / busy:.0f} qps)" in text
