"""The reader of the program's stages in a traced run (``harness/
host_spans.py``): its arithmetic on hand-made intervals, and the whole
reading on a small trace of the trainer recorded on a TPU v5e."""

import os

import pytest

from harness import host_spans, spec, trace
from tiny import ROOT

XPLANE = os.path.join(ROOT, "tests", "bench_chip", "data",
                      "v5e_trainer.xplane.pb")
MS = 1_000_000                                  # the trace counts ns


def test_idle_inside_a_span_is_clipped_to_the_window_and_recorded_end():
    busy = trace.union([(10, 20), (40, 60)])
    # the span [0, 50) meets idle [0, 10) and [20, 40), but the window
    # opens at 5; the span [55, 100) meets idle [60, 100), cut at 80
    spans = [(0, 50), (55, 100)]
    assert host_spans.idle_inside(busy, spans, 5, 80) == 5 + 20 + 20
    assert host_spans.idle_inside(busy, [], 0, 100) == 0.0
    # overlapping spans are counted once
    assert host_spans.idle_inside(busy, [(0, 30), (25, 40)], 0, 100) == 30


def test_idle_share_follows_each_device_over_what_it_recorded():
    ops = {0: [(0, 10)], 1: [(0, 50), (60, 100)]}
    ends = {0: [10], 1: [50, 100]}        # device 0's trace stops at 10
    spans = [(0, 100)]
    # device 0: recorded [0, 10), all busy -> 0%; device 1: idle [50, 60)
    # inside the span -> 10% of 100
    assert host_spans.idle_share_in(ops, ends, spans, 0, 100) == \
        pytest.approx(5.0)
    assert host_spans.idle_share_in(ops, ends, [(50, 55)], 0, 100) == \
        pytest.approx(2.5)
    # a device that recorded nothing of the window counts 0
    assert host_spans.idle_share_in({0: [], 1: [(0, 100)]},
                                    {0: [], 1: [100]}, spans, 0, 100) == 0.0


def hand_made(window=(100, 200)):
    host = {"train.batch": [(90, 110), (120, 130), (150, 170), (200, 210)],
            "train.step": [(110, 120), (130, 150), (170, 200)],
            "backend_compile_and_load": [(50, 60), (140, 145)],
            "backend_compile": [(180, 190)]}
    return host_spans.HostSpans(window, host, {0: [(100, 200)]},
                                {0: [200]})


def test_span_means_count_the_events_that_start_in_the_window():
    hs = hand_made()
    # (120, 130) and (150, 170); (90, 110) starts before, (200, 210) at
    # the window's end
    assert hs.mean_ms("train.batch") == pytest.approx(15 / MS)
    assert hs.mean_ms("train.step") == pytest.approx(20 / MS)
    assert host_spans.durations_in([(1, 3), (5, 9)], 0, 5) == [2]


def test_a_missing_span_reads_none_and_compiles_read_zero():
    hs = hand_made()
    assert hs.mean_ms("train.log") is None
    assert hs.idle_in("train.log", [0]) is None
    assert hs.count(host_spans.COMPILES) == 2
    assert hs.count(["train.log"]) == 0
    no_window = hand_made(window=None)
    assert no_window.mean_ms("train.batch") is None
    assert no_window.idle_in("train.batch", [0]) is None


NEW = ("batch_ms.train", "dispatch_ms.train", "log_ms.train",
       "idle_in_batch.train", "idle_in_dispatch.train",
       "idle_in_engine.serve", "compiles.train", "compiles.serve")


@pytest.mark.parametrize("name", NEW)
def test_readers_are_silent_without_a_trace(name, tmp_path, monkeypatch):
    read = spec.reader(name)
    kind = name.rsplit(".", 1)[1]
    assert read({"kind": kind, "trace": None}) is None
    # a traced run whose trace file is not where the profile writes it
    monkeypatch.setattr(host_spans, "TRACE_DIR", str(tmp_path))
    assert read({"kind": kind, "trace": {"devices": {0: {}}}}) is None


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(XPLANE):
        pytest.fail(f"missing recorded trace {XPLANE}")
    return host_spans.HostSpans.from_file(XPLANE)


def test_recorded_window_holds_two_steps_and_one_logged(recorded):
    lo, hi = recorded.window
    count = {n: len(host_spans.durations_in(v, lo, hi))
             for n, v in recorded.host.items()}
    assert count == {"train": 1, "train.batch": 2, "train.draw": 2,
                     "train.gather": 2, "train.stack": 2, "train.step": 2,
                     "train.log": 1, "backend_compile_and_load": 1}
    # the compile nests inside the logged step's train.log that paid for it
    (cs, ce), = recorded.host["backend_compile_and_load"]
    (ls, le), = recorded.host["train.log"]
    assert ls <= cs and ce <= le
    assert recorded.count(host_spans.COMPILES) == 1


def test_recorded_stages_agree_with_the_device_trace(recorded):
    lo, hi = recorded.window
    red = trace.reduce(XPLANE, [0])
    idle = 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    parts = [recorded.idle_in(n, [0])
             for n in ("train.batch", "train.step", "train.log")]
    assert all(p > 0 for p in parts)
    assert sum(parts) <= idle + 1e-9
    per_step_ms = (hi - lo) / MS / 2
    assert 0 < (recorded.mean_ms("train.batch")
                + recorded.mean_ms("train.step")) <= per_step_ms
    # the idle gaps are named by the program's stages where they cover them
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "train.log"
    assert {n for n, _ in gaps} & {"train.stack", "train.step"}


def test_readers_read_the_recorded_trace(recorded, tmp_path, monkeypatch):
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(open(XPLANE, "rb").read())
    monkeypatch.setattr(host_spans, "TRACE_DIR", str(tmp_path))
    ctx = {"kind": "train", "trace": {"devices": {0: {}}}}
    got = {n: spec.reader(n)(ctx) for n in NEW}
    assert got["batch_ms.train"] == pytest.approx(
        recorded.mean_ms("train.batch"))
    assert got["idle_in_dispatch.train"] == pytest.approx(
        recorded.idle_in("train.step", [0]))
    assert got["compiles.train"] == 1
    assert got["idle_in_engine.serve"] is None        # a training run
    assert got["compiles.serve"] is None
    assert all(got[n] is not None for n in NEW if n.endswith(".train"))
