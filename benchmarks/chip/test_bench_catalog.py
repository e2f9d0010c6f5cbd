"""BENCHMARK.json against its contract, and every name it gives found as a
file of its own: a cell, a configuration, a traffic mix or a metric is
added by adding files.  The checks read ``BENCHMARK.json`` through
``catalog`` when they run, so they hold for a copy that adds a cell."""
from __future__ import annotations

import json
import re

import pytest

import catalog

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = catalog.benchmark()
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_keys_and_limits():
    bench = catalog.benchmark()
    assert set(bench) == KEYS["top"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[kind]:
            extra = set(entry) - KEYS[kind] - (
                {"workloads"} if kind in ("end_to_end", "per_layer")
                else set())
            assert set(entry) >= KEYS[kind] and not extra, entry
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            assert m["workloads"] and set(m["workloads"]) <= cells, m
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                         "device_trace")
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_names_and_units():
    bench = catalog.benchmark()
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[kind]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append(entry["name"])
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in bench["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_found_by_name(cell):
    bench = catalog.benchmark()
    w = catalog.workload(cell, bench)
    assert cell == f"{w['config']}.{w['traffic']}"
    cfg = catalog.config(w["config"])
    assert cfg["name"] == w["config"]
    (entry,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    assert entry["file"] == f"benchmarks/chip/configs/{w['config']}.json"
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    traffic = catalog.traffic(w["traffic"])
    assert traffic["steps_per_dispatch"] >= 1
    mod = catalog.config_module(w["config"])
    for fn in ("program", "init_params", "flops_per_unit",
               "units_per_example", "example_batch", "reference_loss"):
        assert callable(getattr(mod, fn))
    assert set(mod.TEST_SIZE) <= set(cfg)
    assert set(catalog.limits(cell)) == {"loss_gap", "step1_change",
                                         "step3_change"}
    e2e = {m["name"] for m in catalog.cell_metrics(cell, bench,
                                                   "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert f"train_{mod.UNIT}_per_s" in e2e
    assert catalog.cell_metrics(cell, bench, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_readers(metric):
    bench = catalog.benchmark()
    (m,) = [x for x in bench["per_layer"] if x["name"] == metric]
    assert callable(catalog.metric_reader(metric).read)
    assert m["layer"] and "\n" not in m["layer"]
    assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for cell in m["workloads"]:
        e2e = {x["name"] for x in catalog.cell_metrics(cell, bench,
                                                       "end_to_end")}
        assert m["moves"] in e2e, (metric, cell)


def test_layers_share_one_name_each():
    layers = {m["layer"] for m in catalog.benchmark()["per_layer"]}
    assert layers <= {"device", "gradient step", "gossip kernels"}


def test_command_stays_in_paths():
    bench = catalog.benchmark()
    cmd = bench["command"]
    assert cmd[0] == "python3" and len(cmd) <= 32
    assert any(cmd[1].startswith(p + "/") for p in bench["paths"])
    for p in bench["paths"]:
        assert not p.startswith("/") and ".." not in p
        assert (catalog.ROOT / p).is_dir()
