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

# widths cut so that a whole run takes seconds on a CPU; the blocks
# (pre-activation, projection where the width changes) stay the config's
TINY = {
    "resnet18-cifar10": dict(stage_sizes=[1, 1], width=8, norm_groups=4,
                             image_size=8),
}


def tiny(cell: str) -> tuple[dict, dict]:
    """(config, traffic) of ``cell`` at test size."""
    entry = catalog.workload(cell, catalog.benchmark())
    cfg = dict(catalog.config(entry["config"]), **TINY[entry["config"]])
    traffic = dict(catalog.traffic(entry["traffic"]), rounds=16, batch=2)
    traffic["steps_per_dispatch"] = min(traffic["steps_per_dispatch"], 8)
    return cfg, traffic


@pytest.fixture
def tiny_cell():
    return tiny
