"""The reduction from a trace to the per-layer metrics, and the counting
functions the metrics divide by."""
from __future__ import annotations

import gzip
import json

import jax
import jax.numpy as jnp
import pytest

import catalog
import readers
import trace_reduce
from peaks import peaks

MS = 1_000_000  # ns


def record(*ops_per_chip, host=None):
    return {"host": host or [["window", 0, 100 * MS],
                             ["dispatch", 0, 2 * MS],
                             ["wait", 2 * MS, 98 * MS]],
            "devices": [{"name": f"/device:TPU:{i}", "ops": ops}
                        for i, ops in enumerate(ops_per_chip)]}


def test_busy_is_the_union_of_op_intervals():
    r = trace_reduce.reduce(record([
        ["fusion.1", 10 * MS, 20 * MS, "op"],
        ["fusion.2", 20 * MS, 20 * MS, "op"],       # overlaps the first
        ["a2cid2_gossip", 50 * MS, 10 * MS, "gossip"],
        ["fusion.3", 95 * MS, 20 * MS, "op"],       # runs past the window
        ["fusion.0", -30 * MS, 20 * MS, "op"],      # ends before it
    ]))
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.045)
    assert readers.idle_share(r, {}) == pytest.approx(55.0)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["wait", pytest.approx(0.035)]
    assert sum(g[1] for g in gaps) == pytest.approx(0.055)


def test_gossip_time_is_summed_per_chip_and_averaged():
    r = trace_reduce.reduce(
        record([["a2cid2_gossip", 0, 4 * MS, "gossip"],
                ["a2cid2_gossip", 10 * MS, 6 * MS, "gossip"]],
               [["a2cid2_gossip", 0, 2 * MS, "gossip"]]))
    assert r["gossip_s"] == pytest.approx((0.010 + 0.002) / 2)
    assert r["gossip_calls"] == pytest.approx(1.5)
    assert r["chips"] == 2


def test_kinds_from_names():
    assert trace_reduce._kind("a2cid2_gossip", "") == "gossip"
    assert trace_reduce._kind("custom-call.3", "kernel a2cid2_gossip") \
        == "gossip"
    assert trace_reduce._kind("fusion.12", "") == "op"


def test_gossip_bytes_are_two_bank_passes():
    """16 bytes per f32 parameter per worker per stacked call: x and x~
    each read once and written once, whatever implements the kernel."""
    cfg = catalog.config("resnet18-cifar10")
    r = {"gossip_calls": 1, "gossip_s": 1.0}
    facts = {"workers_per_chip": 16, "parameters": cfg["parameters"],
             "peak_hbm": 1.0}
    assert readers.gossip_roofline(r, facts) == pytest.approx(
        100 * 16 * 16 * 11_171_274)
    assert 16 * 16 * cfg["parameters"] == pytest.approx(2.86e9, rel=1e-3)
    assert readers.gossip_roofline({"gossip_calls": 0, "gossip_s": 0},
                                   facts) is None


def test_resnet_flops_match_xla_count():
    """From shapes, within a few % of XLA's count for the program's own
    forward and backward pass on the CPU (which adds the elementwise
    work)."""
    from repro.models.resnet import ResNetConfig, init_resnet, resnet_loss

    cfg = catalog.config("resnet18-cifar10")
    mod = catalog.config_module("resnet18-cifar10")
    rc = ResNetConfig("r", tuple(cfg["stage_sizes"]), cfg["width"],
                      cfg["num_classes"], cfg["norm_groups"])
    p = jax.eval_shape(lambda k: init_resnet(k, rc), jax.random.PRNGKey(0))
    batch = {"images": jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32),
             "labels": jax.ShapeDtypeStruct((1,), jnp.int32)}
    cost = jax.jit(jax.grad(lambda p, b: resnet_loss(p, rc, b)[0])).lower(
        p, batch).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert mod.flops_per_unit(cfg, {}) == pytest.approx(cost["flops"],
                                                        rel=0.03)
    assert cost["flops"] == pytest.approx(2.93e9, rel=0.01)


def test_mfu_arithmetic():
    r = {"window_s": 2.0}
    facts = {"traced_units": 8192, "flops_per_unit": 2.888e9, "chips": 1,
             "peak_flops": 197e12}
    assert readers.mfu(r, facts) == pytest.approx(
        100 * 8192 * 2.888e9 / 2.0 / 197e12)


def test_unknown_device_kind_raises():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")


def test_loops_are_not_counted_twice():
    r = trace_reduce.reduce(record([
        ["%while.1", 10 * MS, 50 * MS, "op"],
        ["%fusion.1", 10 * MS, 20 * MS, "op"],
        ["%a2cid2_gossip.2", 40 * MS, 10 * MS, "gossip"],
    ]))
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert "%while.1" not in names and len(names) == 2
    assert r["busy_s"] == pytest.approx(0.050)


def test_recorded_chip_trace():
    """0.6 s of a traced window of a gossip-training replay on one TPU
    v5e (a 4-worker ring of a 128,404,224-parameter LM, 8 x 256 tokens a
    worker), op names cut at their HLO text's " = "."""
    path = catalog.HERE / "testdata" / "trace_nano_lm_ring4.json.gz"
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    r = trace_reduce.reduce(rec)
    # busy time on a 1 us grid, counted without the interval arithmetic
    import numpy as np
    grid = np.zeros(600_000, bool)
    for _, start, dur, _ in rec["devices"][0]["ops"]:
        grid[max(start // 1000, 0):max((start + dur + 999) // 1000, 0)] = 1
    assert r["busy_s"] == pytest.approx(grid.mean() * 0.6, abs=2e-4)
    assert r["gossip_calls"] == 3
    facts = {"workers_per_chip": 4, "parameters": 128_404_224,
             "peak_hbm": peaks("TPU v5 lite")["hbm_bytes_per_s"]}
    assert 50 < readers.gossip_roofline(r, facts) < 100
    assert readers.idle_share(r, {}) < 1.0
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert "%a2cid2_gossip.2" in names
    assert not any(n.startswith(("%while", "%conditional")) for n in names)


def traced_run(monkeypatch, tiny_cell, ops):
    """A whole --trace 1 run of the cell at test size on the CPU, its trace
    replaced by one chip's ``ops`` over the traced window, read against
    the v5e's peaks."""
    import contextlib

    import bench

    cell = catalog.benchmark()["workloads"][0]["name"]
    cfg, traffic = tiny_cell(cell)
    monkeypatch.setattr(jax.profiler, "trace",
                        lambda d: contextlib.nullcontext())
    monkeypatch.setattr(trace_reduce, "extract", lambda d: record(ops))
    monkeypatch.setattr(bench, "peaks", lambda kind: peaks("TPU v5 lite"))
    return bench.run_cell(cell, catalog.benchmark(), 5, 0.2, True,
                          jax.devices(), cfg=cfg, traffic=traffic,
                          backend="ref")


def test_traced_run_reports_every_listed_metric(monkeypatch, tiny_cell):
    out = traced_run(monkeypatch, tiny_cell, [
        ["%fusion.1", 0, 60 * MS, "op"],
        ["%a2cid2_gossip.2", 60 * MS, 30 * MS, "gossip"]])
    listed = {m["name"] for m in catalog.benchmark()["per_layer"]}
    assert set(out["metrics"]) == listed
    assert out["device"]["busy_s"] == pytest.approx(0.09)
    assert out["breakdown"]["device_ops"][0] == ["%fusion.1",
                                                 pytest.approx(0.06)]


def test_traced_run_without_gossip_ops_fails(monkeypatch, tiny_cell):
    """A cell that lists a gossip roofline and whose trace shows no gossip
    kernel (renamed, or routed around) fails, rather than dropping it."""
    with pytest.raises(RuntimeError, match="gossip_roofline"):
        traced_run(monkeypatch, tiny_cell,
                   [["%fusion.1", 0, 60 * MS, "op"]])
