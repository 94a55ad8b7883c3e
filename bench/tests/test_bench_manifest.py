"""BENCHMARK.json keeps to the benchmark's contract, and every file it
implies is there."""

import json
import os
import re

import pytest

from bench import cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(cell.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    for kind, keys in KEYS.items():
        names = [e["name"] for e in manifest[kind]]
        assert len(names) == len(set(names)), kind
        for e in manifest[kind]:
            extra = {"workloads"} if kind in ("end_to_end",
                                              "per_layer") else set()
            assert keys <= set(e) <= keys | extra, e
            assert NAME.match(e["name"]), e["name"]
            for text in ("why", "layer", "source"):
                if text in e:
                    assert 1 <= len(e[text]) <= 200 and "\n" not in e[text] \
                        and "\t" not in e[text], e
    all_metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in all_metrics}) == len(all_metrics)
    for m in all_metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


def test_metrics_name_their_cells_and_what_they_move(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in manifest["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= cells, m
        assert m["moves"] in e2e, m
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved), m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["unit"] == "%" and ("mfu" in m["name"]
                                 or m["name"].endswith("_roofline")):
            assert m["source"] == "device_trace"


def test_every_cell_reports_enough(manifest):
    bench = cell.Bench(cell.ROOT)
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        e2e = [m["name"] for m in bench.metrics(w, "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert bench.metrics(w, "per_layer"), w["name"]
        assert w["name"] == f"{w['config']}.{w['traffic']}"


def test_every_named_file_is_there(manifest):
    bench = cell.Bench(cell.ROOT)
    for path in manifest["paths"]:
        assert os.path.isdir(os.path.join(cell.ROOT, path))
    for c in manifest["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in
                                          manifest["paths"]))
        assert bench.config(c["name"])["name"] == c["name"]
        assert callable(bench.config_module(c["name"]).run)
    for w in manifest["workloads"]:
        assert bench.traffic(w)
        for m in bench.metrics(w, "per_layer"):
            assert callable(bench.reader(w, m["name"]).read)
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word


def test_reduced_keys_are_the_files_own(manifest):
    """``reduced`` names the keys each configuration's file lists as cut,
    each of them a key of the file, none of them a width."""
    bench = cell.Bench(cell.ROOT)
    widths = re.compile(r"(_dim|_rank|_size|_factor|d_model|num_heads)$")
    for c in manifest["configs"]:
        cut = bench.config(c["name"]).get("reduced", {})
        assert sorted(c["reduced"]) == sorted(cut), c["name"]
        for key in c["reduced"]:
            assert key in bench.config(c["name"]), key
            assert not widths.search(key), key
