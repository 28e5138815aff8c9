"""BENCHMARK.json and the files it names: the harness finds every
configuration, traffic mix, limit and metric by name."""

import os
import re

import pytest

from harness import spec
from tiny import benchmark_cells, stand_in

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|"
                   r"head|expansion|experts_per_tok")


def test_keys_and_names(benchmark_json):
    b = benchmark_json
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    for p in b["paths"]:
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(x["name"] for x in b["workloads"])) == len(b["workloads"])
    metric_names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_configs_are_files_of_their_own_and_cut_no_width(benchmark_json):
    files = set()
    for c in benchmark_json["configs"]:
        cfg = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert c["file"] not in files
        files.add(c["file"])
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert not any(WIDTH.search(k) for k in c["reduced"])
        used = [w for w in benchmark_json["workloads"]
                if w["config"] == c["name"]]
        assert used, f"config {c['name']} has no cell"


def test_every_cell_finds_its_pieces(benchmark_json):
    pairs = set()
    for w in benchmark_json["workloads"]:
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        work, cfg, traffic, limits = spec.cell(benchmark_json, w["name"])
        assert hasattr(spec.driver(traffic["kind"]), "drive")
        assert limits.get("numbers"), f"no limits for {w['name']}"
        e2e = spec.metrics_for(benchmark_json, w["name"], False)
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert spec.metrics_for(benchmark_json, w["name"], True)


def test_an_unknown_kind_fails_with_the_kinds_found(benchmark_json):
    with pytest.raises(KeyError) as err:
        spec.driver("no_such_kind")
    for w in benchmark_json["workloads"]:
        kind = spec.cell(benchmark_json, w["name"])[2]["kind"]
        assert repr(kind) in str(err.value) and kind in spec.kinds()
    for bad in ("spec", "../run", "cells"):      # modules, but no drivers
        with pytest.raises(KeyError):
            spec.driver(bad)


@pytest.mark.parametrize("cell", benchmark_cells())
def test_every_cell_has_a_cpu_stand_in(cell, benchmark_json):
    work = next(w for w in benchmark_json["workloads"] if w["name"] == cell)
    cfg, traffic = stand_in("configs", work["config"], cell), stand_in(
        "traffic", work["traffic"], cell)
    _, full, mix, _ = spec.cell(benchmark_json, cell)
    assert set(cfg) <= set(full) and set(traffic) <= set(mix), cell


def test_four_chip_cells_are_at_most_half(benchmark_json):
    four = [w for w in benchmark_json["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(benchmark_json["workloads"]) // 2)


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader(benchmark_json, kind):
    for m in benchmark_json[kind]:
        read = spec.reader(m["name"])
        if m["name"] != "setup_s":
            # a reader with nothing of its kind to read stays silent
            assert read({"kind": "neither", "trace": None}) is None


def test_bounds(benchmark_json):
    for m in benchmark_json["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in benchmark_json["per_layer"]:
        assert "bound" not in m and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in benchmark_json["end_to_end"]}
