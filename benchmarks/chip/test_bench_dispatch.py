"""Dispatch shapes that do not depend on the seed: every seed of a cell
runs one executable, cut from the stream without changing the replay."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
import catalog
import traffic as T
from repro.core.simulator import SimState

BENCH = catalog.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def dispatches(cell: str, seed: int) -> list[dict]:
    """The cell's dispatches at its own traffic, from a stand-in state
    (the stream reads only the workers' clocks)."""
    entry = catalog.workload(cell, BENCH)
    traffic = catalog.traffic(entry["traffic"])
    c = bench.Cell(cell, catalog.config(entry["config"]), traffic,
                   catalog.config_module(entry["config"]), "ref")
    w = traffic["workers"]
    state = SimState(None, None, np.zeros(w, np.float32), None)
    return c.stream.dispatches(state, T.schedule(traffic, seed,
                                                 traffic["rounds"]))


@pytest.mark.parametrize("cell", CELLS)
def test_two_seeds_give_one_dispatch_shape(cell):
    traffic = catalog.traffic(catalog.workload(cell, BENCH)["traffic"])
    a, b = dispatches(cell, 3), dispatches(cell, 2**31 + 11)
    assert len(a) == len(b) > bench.FIRST_STEPS
    for ca, cb in zip(a, b):
        for k in bench.EngineStream.KEYS:
            assert ca[k].shape == cb[k].shape and ca[k].dtype == cb[k].dtype
        assert np.array_equal(ca["is_grad"], cb["is_grad"])
        # every step sweeps the whole bank; on a ring of more than four
        # a maximal matching's size (the pairs it exchanges) varies
        assert ca["grad_ticks"] == cb["grad_ticks"]
    assert {c["partners"].shape for c in a} == {
        (traffic["steps_per_dispatch"], traffic["workers"])}


def test_seeds_differ_in_events_not_in_work():
    traffic = catalog.traffic("ring16.b64")
    a, b = (T.schedule(traffic, s, 40) for s in (1, 2))
    assert np.array_equal(a["counts"], b["counts"])
    assert not np.array_equal(a["event_times"], b["event_times"])


def test_chunked_replay_equals_whole_stream(tiny_cell):
    cfg, traffic = tiny_cell(CELLS[0])
    cell = bench.Cell("t", cfg, traffic,
                      catalog.config_module(cfg["name"]), "ref")
    k_n = 4
    run = cell.start(5)
    whole = run["chunks"][:k_n]
    arrays = (whole[0]["prologue"],
              *(np.concatenate([c[k] for c in whole])
                for k in ("partners", "dt_next", "is_grad", "grad_scale")),
              np.arange(k_n * cell.steps, dtype=np.int32),
              whole[-1]["t_final"])
    ref_state, ref_tr = cell.sim.run_coalesced(
        cell.sim.init(run["x0"], cell.workers, jnp.copy(run["k_sim"])),
        tuple(jnp.asarray(a) for a in arrays))
    losses = []
    for k in range(k_n):
        losses.append(np.asarray(cell.dispatch(run, k).loss))
    np.testing.assert_array_equal(np.asarray(run["state"].x),
                                  np.asarray(ref_state.x))
    np.testing.assert_array_equal(np.asarray(run["state"].x_tilde),
                                  np.asarray(ref_state.x_tilde))
    np.testing.assert_array_equal(np.concatenate(losses),
                                  np.asarray(ref_tr.loss))
    np.testing.assert_array_equal(np.asarray(run["state"].t_last),
                                  whole[-1]["t_final"])


def test_window_compiles_nothing(tiny_cell):
    cfg, traffic = tiny_cell(CELLS[0])
    counter = bench.CompileCounter()
    cell = bench.Cell("t", cfg, traffic,
                      catalog.config_module(cfg["name"]), "ref")
    run = cell.start(6)
    cell.first_steps(run)
    jax.block_until_ready(run["state"])
    before = counter.count
    win = cell.window(run, 0.5)
    assert win["dispatches"] > 1 and win["failed"] == 0
    assert counter.count == before
