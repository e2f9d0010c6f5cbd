"""The check that decides ``correct``, shown to fail.

Each test drives a whole run of a cell at test size on the CPU (past the
harness's look for a chip) with the timed path broken underneath, and sees
``correct`` come out false; a sound run comes out true, and the control --
the plain reference computed in bfloat16, put in the program's place --
fails the cell's limits.  Every lookup goes through ``catalog`` when the
test runs, so a cell that a configuration adds by files alone is driven
the same way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import bench
import catalog
import reference
from repro.core import engine

CELLS = [w["name"] for w in catalog.benchmark()["workloads"]]


def run(cell, tiny_cell, seed=9):
    cfg, traffic = tiny_cell(cell)
    return bench.run_cell(cell, catalog.benchmark(), seed, 0.2, False,
                          jax.devices(), cfg=cfg, traffic=traffic,
                          limits=catalog.limits(cell), backend="ref")


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tiny_cell):
    out = run(cell, tiny_cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_state_left_unchanged_is_caught(cell, tiny_cell, monkeypatch):
    step = bench.EngineStream.__call__

    def stuck(self, state, placed):
        _, trace = step(self, jax.tree.map(jnp.copy, state), placed)
        return state, trace
    monkeypatch.setattr(bench.EngineStream, "__call__", stuck)
    assert not run(cell, tiny_cell)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_batch_is_caught(cell, tiny_cell, monkeypatch):
    """The program trains on the first half of each batch it is handed;
    the reference draws its own, whole."""
    draw = bench.Cell.batch

    def halved(self, key):
        return {k: v[: v.shape[0] // 2] for k, v in draw(self, key).items()}
    monkeypatch.setattr(bench.Cell, "batch", halved)
    assert not run(cell, tiny_cell)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_gossip_answer_is_caught(cell, tiny_cell, monkeypatch):
    gossip = engine.gossip_event_stacked

    def altered(x, x_tilde, *args, **kw):
        ox, ot = gossip(x, x_tilde, *args, **kw)
        return ox.at[0, 0].add(1.0), ot
    monkeypatch.setattr(engine, "gossip_event_stacked", altered)
    assert not run(cell, tiny_cell)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_control_fails_the_limits(cell, tiny_cell):
    cfg, traffic = tiny_cell(cell)
    entry = catalog.workload(cell, catalog.benchmark())
    c = bench.Cell(cell, cfg, traffic,
                   catalog.config_module(entry["config"]), "ref")
    r = c.start(12)
    c.first_steps(r)
    ref = c.reference(r)
    limits = catalog.limits(cell)
    assert reference.verdict(c.compare(r, ref), limits)
    control = reference.numbers(*c.reference(r, jnp.bfloat16), *ref,
                                c.marks())
    assert not reference.verdict(control, limits), control


def test_replay_without_gossip_kernel_is_refused(tiny_cell):
    """On the Pallas backend the compiled replay has to hold the gossip
    kernel's custom call; the jnp backend's replay holds none."""
    cfg, traffic = tiny_cell(CELLS[0])
    c = bench.Cell(CELLS[0], cfg, traffic,
                   catalog.config_module(cfg["name"]), "ref")
    c.stream.backend = "pallas"
    with pytest.raises(RuntimeError, match="a2cid2_gossip"):
        c.start(3)
