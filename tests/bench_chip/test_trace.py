"""The trace reduction: interval arithmetic on hand-made intervals, and
the whole reduction on a small trace recorded on a TPU v5e."""

import os

import numpy as np
import pytest

from harness import trace
from tiny import ROOT

XPLANE = os.path.join(ROOT, "tests", "bench_chip", "data", "v5e_tiny.xplane.pb")


def test_union_merges_overlaps_and_touching_intervals():
    got = trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 12)])
    np.testing.assert_array_equal(got, [[0, 4], [5, 7], [10, 12]])
    assert trace.union([]).shape == (0, 2)


def test_covered_and_clip_stay_inside_the_window():
    merged = trace.union([(0, 4), (5, 7), (10, 12)])
    assert trace.covered(merged, 2, 11) == 2 + 2 + 1
    assert trace.clip([(0, 4, "a"), (5, 7, "b"), (9, 20, "c")], 3, 10) == [
        (3, 4, "a"), (5, 7, "b"), (9, 10, "c")]


def test_gaps_are_the_longest_and_named_by_the_innermost_annotation():
    us = 1000                                       # the trace counts ns
    merged = trace.union([(10 * us, 20 * us), (50 * us, 60 * us),
                          (60 * us + 500, 90 * us)])
    labels = [(0, 100 * us, "outer"), (20 * us, 50 * us, "draw_batch"),
              (90 * us, 100 * us, "log_sync"), (60 * us, 61 * us, "tiny")]
    gaps = trace._gaps(merged, 0, 100 * us, labels, 3)
    # the 500 ns seam between two ops is no gap
    assert sorted(gaps, key=lambda g: (-g[1], g[0])) == [
        ("draw_batch", 30 * us), ("log_sync", 10 * us), ("outer", 10 * us)]


@pytest.mark.parametrize("op,want", [
    ("%all-reduce.3 = f32[1000,21504]{1,0:T(8,128)} all-reduce(%p), "
     "replica_groups={{0,1,2,3}}", True),
    ("%all-reduce-start = f32[1000]{0} all-reduce-start(%g)", True),
    ("%fusion.2 = f32[1000,21504]{1,0} fusion(%all-reduce.3, %p), "
     "kind=kLoop", False),
    ("%copy.1 = f32[1000,21504]{1,0:T(8,128)} copy(%args_0_.1)", False),
])
def test_a_collective_is_named_by_its_instruction_not_its_operands(op, want):
    assert trace.is_collective(op) is want


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(XPLANE):
        pytest.fail(f"missing recorded trace {XPLANE}")
    return trace.reduce(XPLANE, [0])


def test_recorded_window_holds_three_runs_of_the_program(recorded):
    dev = recorded["devices"][0]
    runs = [c for n, (_, c) in dev["modules_ns"].items() if "tiny_step" in n]
    assert runs == [3], dev["modules_ns"]
    # each run: a prefetch copy's start and end, then the two fusions
    assert sorted(dev["op_counts"].values()) == [3, 3, 3, 3]
    assert sum(dev["ops_ns"].values()) >= dev["busy_ns"] > 0


def test_recorded_busy_and_gaps_add_up_to_the_window(recorded):
    assert 0.2 <= recorded["window_s"] < 0.25         # four 50 ms sleeps
    gaps = recorded["breakdown"]["idle_gaps"]
    idle = sum(t for _, t in gaps)
    assert recorded["busy_s"] + idle == pytest.approx(recorded["window_s"],
                                                      abs=1e-5)
    assert 0 < recorded["busy_s"] < 0.005


def test_recorded_gaps_are_named_by_the_host_sleep(recorded):
    gaps = recorded["breakdown"]["idle_gaps"]
    assert [n for n, _ in gaps] == ["host_sleep"] * 4, gaps
    assert all(0.045 <= t < 0.06 for _, t in gaps)
    ops = recorded["breakdown"]["device_ops"]
    assert ops and all(t > 0 for _, t in ops)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)


def test_a_device_trace_that_stops_early_is_measured_over_what_it_recorded():
    # device 0 stops at 1.4 s of a 10 s window; device 1 idles its last
    # 0.2 s, which is no cut; a lone device is never cut
    ends = trace.recorded_ends({0: [0.5, 1.4], 1: [3.0, 9.8]}, 0.0, 10.0)
    assert ends == {0: 1.4, 1: 10.0}
    assert trace.recorded_ends({0: [1.0]}, 0.0, 10.0) == {0: 10.0}
    assert trace.recorded_ends({0: [], 1: [12.0]}, 0.0, 10.0) == {0: 0.0,
                                                                  1: 10.0}
