"""A broken timed path must come out not correct: each fault a cell can
have, planted under a tiny run that skips the look for a chip, and the
control (one precision below the stated one) read against the limits."""

import pytest

import calibrate
from harness import spec
from test_cells import four_workers
from tiny import run_tiny, tiny_cell

FAULTS = [("imnet1m.train", "unchanged"), ("imnet1m.train", "half"),
          ("imnet63k.train", "unchanged"), ("imnet63k.train", "half"),
          ("imnet1m.serve", "altered"), ("imnet1m.serve", "half")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_a_planted_fault_fails_the_check(cell, fault):
    with calibrate.fault(fault):
        result, checks = run_tiny(cell)
    assert not result["correct"], checks


def test_leaving_out_the_exchange_fails_the_check():
    out = four_workers("noexchange")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["imnet1m.train", "imnet63k.train",
                                  "imnet1m.serve"])
def test_the_control_fails_the_check(cell):
    _, cfg, traffic, limits = tiny_cell(cell)
    seed = 2 ** 31 + 21
    if traffic["kind"] == "train":
        out = calibrate.train_numbers(cfg, traffic, seed, control=True)
    else:
        out = calibrate.serve_numbers(cfg, traffic, seed, 1.0, control=True)
    assert spec.judge(out["program"], limits)[0], out["program"]
    ok, checks = spec.judge(out["control"], limits)
    assert not ok, checks
