"""BENCHMARK.json resolves, piece by piece, and keeps to its format."""
from __future__ import annotations

import json
import re

import pytest

from bench import cells

BENCH = cells.load_benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e


def test_names_units_and_text_fields():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    names = [e["name"] for e in entries]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [e["name"] for e in BENCH[kind]]
        assert len(ns) == len(set(ns)), kind
    for n in names:
        assert NAME.fullmatch(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for e in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200
        for k in c["reduced"]:
            assert NAME.fullmatch(k), k
    for w in BENCH["workloads"]:
        assert NAME.fullmatch(w["config"]) and NAME.fullmatch(w["traffic"])
        assert w["chips"] in (1, 4)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_every_piece(name):
    cell = cells.resolve(BENCH, name)
    assert cell.config["chips"] == cell.chips
    pr, pc = cell.config["grid"]
    assert pr * pc == cell.chips
    cells.client(cell.traffic["client"]).Traffic       # noqa: B018
    e2e = [m["name"] for m in cell.end_to_end]
    assert sorted(e2e) == sorted(["setup_s", cell.traffic["rate_metric"]])
    assert cell.per_layer, name
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m["name"]).read)
        assert m["moves"] in e2e


def test_every_config_is_used_and_states_its_cuts():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(BENCH["paths"][0] + "/configs/")
        files.add(c["file"])
        with open(cells.ROOT / c["file"]) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
    assert len(files) == len(BENCH["configs"])


def test_every_config_names_a_generator_that_resolves():
    for c in BENCH["configs"]:
        with open(cells.ROOT / c["file"]) as f:
            conf = json.load(f)
        assert callable(cells.generator(conf["generator"]).graph), c["name"]


def test_a_config_reads_only_what_the_harness_honours():
    """A configuration states no choice that the run does not make: the
    tile capacity is worked out (``graph.tile_cap``), values are float32
    ones, duplicate edges are always dropped and every seed relabels."""
    for c in BENCH["configs"]:
        with open(cells.ROOT / c["file"]) as f:
            conf = json.load(f)
        for key in ("tile_cap", "values", "drop_duplicate_edges",
                    "relabel_by_run_seed"):
            assert key not in conf, (c["name"], key)


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        cells.resolve(BENCH, "no.such.cell")
