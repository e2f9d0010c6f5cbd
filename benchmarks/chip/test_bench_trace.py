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
import scopes
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


def _recorded():
    path = catalog.HERE / "testdata" / "trace_nano_lm_ring4.json.gz"
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_chip_trace_reduces_as_before():
    """Without scope maps ``reduce`` gives every key it gave before op
    times were added, with the same values to the last digit
    (``trace_nano_lm_ring4.reduced.json`` holds them), and so the same
    readings; it adds the time and calls of every leaf op."""
    rec = _recorded()
    before = json.loads((catalog.HERE / "testdata" /
                         "trace_nano_lm_ring4.reduced.json").read_text())
    r = trace_reduce.reduce(rec)
    assert set(r) == set(before) | {"op_s", "op_calls"}
    assert json.loads(json.dumps({k: r[k] for k in before})) == before
    facts = {"workers_per_chip": 4, "parameters": 128_404_224,
             "peak_hbm": 819e9, "traced_units": 8 * 256 * 4 * 3,
             "flops_per_unit": 6 * 128_404_224, "chips": 1,
             "peak_flops": 197e12}
    for read in (readers.idle_share, readers.mfu, readers.gossip_roofline):
        assert read(r, facts) == read(before, facts)
    # every leaf op in the window, not only the top ten
    (_, lo, dur), = [s for s in rec["host"] if s[0] == "window"]
    leaves = trace_reduce._leaves([o for o in rec["devices"][0]["ops"]
                                   if o[1] + o[2] > lo and o[1] < lo + dur])
    assert len(r["op_s"]) > 10 and set(r["op_s"]) == {o[0] for o in leaves}
    assert sum(r["op_s"].values()) == pytest.approx(
        sum(o[2] for o in leaves) * 1e-9, rel=1e-12)
    assert sum(r["op_calls"].values()) == len(leaves)
    assert r["op_calls"]["%a2cid2_gossip.2"] == r["gossip_calls"] == 3
    top = sorted(r["op_s"].items(), key=lambda kv: -kv[1])[:10]
    assert r["breakdown"]["device_ops"] == [list(kv) for kv in top]


def test_op_time_and_calls_averaged_over_chips():
    r = trace_reduce.reduce(
        record([["%fusion.1", 0, 4 * MS, "op"],
                ["%fusion.1", 10 * MS, 6 * MS, "op"],
                ["%copy.2", 20 * MS, 1 * MS, "op"]],
               [["%fusion.1", 0, 2 * MS, "op"]]))
    assert r["op_s"] == {"%fusion.1": pytest.approx(0.006),
                         "%copy.2": pytest.approx(0.0005)}
    assert r["op_calls"] == {"%fusion.1": 1.5, "%copy.2": 0.5}
    assert "scope_s" not in r and "model_s" not in r


def scoped_ops(maps) -> list:
    """One op named by an instruction of each replay scope and each model
    scope of the compiled replay, 2 ms each, back to back, then one 30 ms
    gossip kernel call: every scope reader finds something to read."""
    picked = {}
    for m in (maps.replay, maps.model):
        for instr, scope in sorted(m.items()):
            picked.setdefault(scope, instr)
    ops = [[f"%{instr}", i * 2 * MS, 2 * MS, "op"]
           for i, instr in enumerate(picked.values())]
    return ops + [["%a2cid2_gossip.2", len(ops) * 2 * MS, 30 * MS,
                   "gossip"]]


def traced_run(monkeypatch, tiny_cell, ops, cell=None):
    """A whole --trace 1 run of the cell (the first, by default) at test
    size on the CPU, its trace replaced by one chip's ``ops`` over the
    traced window, read against the v5e's peaks.  ``ops`` may be a
    function of the compiled replay's scope maps."""
    import contextlib

    import bench

    cell = cell or catalog.benchmark()["workloads"][0]["name"]
    cfg, traffic = tiny_cell(cell)
    compiled = {}
    compile_ = bench.EngineStream.compile

    def compile(self, state, placed):
        compile_(self, state, placed)
        compiled["maps"] = scopes.maps(self.hlo)

    def extract(trace_dir):
        return record(ops(compiled["maps"]) if callable(ops) else ops)
    monkeypatch.setattr(bench.EngineStream, "compile", compile)
    monkeypatch.setattr(jax.profiler, "trace",
                        lambda d: contextlib.nullcontext())
    monkeypatch.setattr(trace_reduce, "extract", extract)
    monkeypatch.setattr(bench, "peaks", lambda kind: peaks("TPU v5 lite"))
    return bench.run_cell(cell, catalog.benchmark(), 5, 0.2, True,
                          jax.devices(), cfg=cfg, traffic=traffic,
                          backend="ref")


def check_traced_run(cell, monkeypatch, tiny_cell):
    """A traced run of ``cell`` reports exactly the per-layer metrics the
    cell lists, each read from the trace."""
    traced = []

    def ops(maps):
        traced[:] = scoped_ops(maps)
        return traced
    out = traced_run(monkeypatch, tiny_cell, ops, cell)
    listed = {m["name"] for m in catalog.cell_metrics(
        cell, catalog.benchmark(), "per_layer")}
    assert set(out["metrics"]) == listed
    assert all(m["value"] > 0 for m in out["metrics"].values()), \
        out["metrics"]
    assert out["device"]["busy_s"] == pytest.approx(
        sum(o[2] for o in traced) * 1e-9)
    assert out["breakdown"]["device_ops"][0] == ["%a2cid2_gossip.2",
                                                 pytest.approx(0.03)]
    return out


def test_traced_run_reports_every_listed_metric(monkeypatch, tiny_cell):
    """Each cell against the per-layer metrics it lists, the replay's
    scope readings among them."""
    for cell in [w["name"] for w in catalog.benchmark()["workloads"]]:
        out = check_traced_run(cell, monkeypatch, tiny_cell)
        if cell == "resnet18-cifar10.ring16.b64":
            assert {"grad_mfu.resnet", "bank_ms_per_tick.resnet",
                    "record_ms_per_tick.resnet"} <= set(out["metrics"])


def test_traced_run_without_gossip_ops_fails(monkeypatch, tiny_cell):
    """A cell that lists a gossip roofline and whose trace shows no gossip
    kernel (renamed, or routed around) fails, rather than dropping it."""
    with pytest.raises(RuntimeError, match=r"\['gossip_roofline"):
        traced_run(monkeypatch, tiny_cell,
                   lambda maps: scoped_ops(maps)[:-1])
