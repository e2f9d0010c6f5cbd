"""Shared pieces of the benchmark's CPU tests: a cell at a size a test run
holds, built from the committed configuration and traffic files."""
from __future__ import annotations

import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
for p in (str(HERE), str(HERE.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

import catalog  # noqa: E402


def tiny(cell: str) -> tuple[dict, dict]:
    """(config, traffic) of ``cell`` at test size: the configuration at
    the ``TEST_SIZE`` its module gives, the traffic at a few rounds of
    small batches."""
    entry = catalog.workload(cell, catalog.benchmark())
    mod = catalog.config_module(entry["config"])
    cfg = dict(catalog.config(entry["config"]), **mod.TEST_SIZE)
    traffic = dict(catalog.traffic(entry["traffic"]), rounds=16, batch=2)
    traffic["steps_per_dispatch"] = min(traffic["steps_per_dispatch"], 8)
    return cfg, traffic


@pytest.fixture
def tiny_cell():
    return tiny
