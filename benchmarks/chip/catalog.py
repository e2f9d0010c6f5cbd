"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

A cell ``<config>.<traffic>`` is one entry of ``workloads``; its pieces are
files of their own, so a later change adds a cell, a configuration, a
traffic mix or a per-layer metric by adding files:

  configs/<config>.json   the sizes as run (the entry's ``file``)
  configs/<config>.py     the program's build of it (``program(cfg,
                          traffic, batch)``, training on the harness's
                          ``batch(key)``), its data, its work counts, its
                          unit of work (``UNIT``: ``train_<UNIT>_per_s``
                          is its rate), the sizes the CPU tests run it at
                          (``TEST_SIZE``) and its plain reference
  traffic/<traffic>.json  the schedule, batch and dispatch parameters
  limits/<cell>.json      the limits of the numbers ``correct`` compares
  metrics/<metric>.py     the reader of one per-layer metric: ``read(r,
                          facts)`` over the reduced trace (device time per
                          op, per replay scope and per ``model.<name>``
                          scope, ``trace_reduce.reduce``) and the run's
                          facts, None where it finds nothing to read

and its entries in ``BENCHMARK.json``; a new cell is appended to the
``workloads`` of the metrics it reports.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def workload(name: str, bench: dict) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def config_module(name: str):
    return load_module(HERE / "configs" / f"{name}.py",
                       "bench_config_" + name.replace("-", "_")
                       .replace(".", "_"))


def traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def limits(cell: str) -> dict:
    return json.loads((HERE / "limits" / f"{cell}.json").read_text())


def metric_reader(name: str):
    return load_module(HERE / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


def cell_metrics(cell: str, bench: dict, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics the cell reports: those
    that list it, or list no cells at all."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]

