"""A broken timed path must come out not correct: each fault a cell can
have (its limits file's ``faults``), planted under a tiny run that skips
the look for a chip, and the control (one precision below the stated one)
read against the limits."""

import pytest

import calibrate
from harness import spec
from test_cells import four_workers
from tiny import benchmark_cells, run_tiny, tiny_cell

FAULTS = [(cell, fault) for cell in benchmark_cells(chips=1)
          for fault in spec.cell(spec.benchmark(), cell)[3].get("faults", [])]


def test_every_cell_names_its_faults():
    for cell in benchmark_cells():
        assert spec.cell(spec.benchmark(), cell)[3].get("faults"), cell


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_a_planted_fault_fails_the_check(cell, fault):
    _, _, traffic, _ = tiny_cell(cell)
    with calibrate.fault(fault, spec.driver(traffic["kind"])):
        result, checks = run_tiny(cell)
    assert not result["correct"], checks


def test_leaving_out_the_exchange_fails_the_check():
    out = four_workers("noexchange")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", benchmark_cells(chips=1))
def test_the_control_fails_the_check(cell):
    work, cfg, traffic, limits = tiny_cell(cell)
    out = calibrate.readings(work, cfg, traffic, 2 ** 31 + 21, 1.0,
                             control=True)
    assert out["program"]["failed"] == 0
    assert spec.judge(out["program"], limits)[0], out["program"]
    ok, checks = spec.judge(out["control"], limits)
    assert not ok, checks
