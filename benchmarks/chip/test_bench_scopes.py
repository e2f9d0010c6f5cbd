"""The replay's named scopes, from the compiled HLO to device time per
phase of the training step, and the scope readings."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import pytest

import catalog
import scope_profile
import scopes
import trace_reduce
from peaks import peaks
from repro.analysis import tracing
from repro.core import (Simulator, make_schedule, params_from_graph,
                        ring_graph)

MS = 1_000_000  # ns
REPLAY = "jit(_run_coalesced_impl)/while/body/closed_call/cond"

HLO = f"""HloModule jit__run_coalesced_impl

%fused_computation.7 (param_0.1: f32[4,256]) -> f32[4,256] {{
  %param_0.1 = f32[4,256]{{1,0}} parameter(0)
  %constant.3 = f32[] constant(2), metadata={{op_name="jit(f)/while/body"}}
  %mul.1 = f32[4,256]{{1,0}} multiply(%param_0.1, %param_0.1), metadata={{op_name="{REPLAY}/branch_1_fun/replay.update/mul"}}
  ROOT %sub.2 = f32[4,256]{{1,0}} subtract(%mul.1, %param_0.1), metadata={{op_name="{REPLAY}/branch_1_fun/replay.mix/sub"}}
}}

ENTRY %main.9 (Arg_0.1: f32[4,256]) -> f32[4,256] {{
  %Arg_0.1 = f32[4,256]{{1,0}} parameter(0)
  %dot.3 = f32[4,256]{{1,0}} dot(%Arg_0.1, %Arg_0.1), metadata={{op_name="{REPLAY}/branch_1_fun/replay.grad/replay.unpack/vmap(jvp(loss))/replay.grad/vmap(jvp())/dot_general"}}
  %dot.4 = f32[4,256]{{1,0}} dot(%dot.3, %Arg_0.1), metadata={{op_name="{REPLAY}/branch_1_fun/replay.grad/vmap(transpose(jvp()))/dot_general"}}
  %fusion.7 = f32[4,256]{{1,0}} fusion(%dot.4), kind=kLoop, calls=%fused_computation.7, metadata={{op_name="{REPLAY}/branch_1_fun/replay.mix/sub"}}
  %copy.2 = f32[4,256]{{1,0}} copy(%fusion.7)
  ROOT %add.5 = f32[4,256]{{1,0}} add(%copy.2, %copy.2), metadata={{op_name="{REPLAY}/branch_0_fun/replay.gossips/add"}}
}}
"""


def test_scope_names_match_the_program():
    assert scopes.SCOPES == tracing.SCOPES


def test_scope_map_on_fixed_hlo():
    """The last token wins, ``transpose(`` past a grad token is the
    backward pass, and an instruction without a token is unscoped."""
    m = scopes.scope_map(HLO)
    assert m["dot.3"] == "replay.grad.fwd"
    assert m["dot.4"] == "replay.grad.bwd"
    assert m["fusion.7"] == m["sub.2"] == "replay.mix"
    assert m["mul.1"] == "replay.update"
    assert m["copy.2"] == m["Arg_0.1"] == m["constant.3"] == "unscoped"
    assert m["add.5"] == "unscoped"      # "replay.gossips" is no scope
    # merged metadata: the last name's stack past its token decides
    assert scopes.scope_of("a/replay.grad/jvp()/x;"
                           "b/replay.grad/transpose(jvp())/y") \
        == "replay.grad.bwd"
    assert scopes.scope_of("a/replay.grad/transpose(jvp())/x;"
                           "b/replay.grad/jvp()/y") == "replay.grad.fwd"


def test_fused_scopes_on_fixed_hlo():
    f = scopes.fused_scopes(HLO)
    assert f == {"fusion.7": {"replay.update", "replay.mix"}}


# the model marks its parts inside replay.grad, as JAX names them under
# vmap and differentiation
MODEL_HLO = HLO.replace(
    "vmap(jvp())/dot_general",
    "vmap(jvp(model.block))/model.attn/dot_general").replace(
    "vmap(transpose(jvp()))/dot_general",
    "vmap(transpose(jvp(model.block)))/dot_general")


def test_model_scopes_on_fixed_hlo():
    """The innermost ``model.`` token names the model scope, ``transpose(``
    in its name makes it the backward pass, and the replay scopes stay as
    they were without the tokens."""
    assert MODEL_HLO != HLO
    m = scopes.maps(MODEL_HLO)
    assert m.replay == scopes.scope_map(HLO)
    assert m.model == {"dot.3": "model.attn.fwd", "dot.4": "model.block.bwd"}
    assert scopes.model_map(HLO) == {}
    assert scopes.model_scope_of("a/mymodel.x/y") is None
    assert scopes.model_scope_of(
        "a/transpose(jvp(model.mlp))/x;b/jvp(model.head)/y") \
        == "model.head.fwd"
    assert scopes.model_scope_of(
        "a/jvp(model.mlp)/x;b/transpose(jvp(model.mlp-2.up))/y") \
        == "model.mlp-2.up.bwd"


def test_model_seconds_leave_replay_seconds_as_they_were():
    rec = _record([["%dot.3", 0, 3 * MS, "op"],
                   ["%dot.4", 3 * MS, 5 * MS, "op"],
                   ["%fusion.7", 8 * MS, 1 * MS, "op"]])
    r = trace_reduce.reduce(rec, scopes.maps(MODEL_HLO))
    assert r["model_s"] == {"model.attn.fwd": pytest.approx(0.003),
                            "model.block.bwd": pytest.approx(0.005)}
    plain = trace_reduce.reduce(rec, scopes.maps(HLO))
    assert plain["model_s"] == {}
    assert r["scope_s"] == plain["scope_s"] == scopes.scope_seconds(
        rec, scopes.scope_map(HLO))
    assert r["scope_s"]["replay.grad.fwd"] == pytest.approx(0.003)
    assert r["scope_s"]["replay.grad.bwd"] == pytest.approx(0.005)


def _grad_fn(p, key, wid):
    x = jax.random.normal(key, (4, 8))

    def loss(p):
        with jax.named_scope("model.mlp"):
            h = jnp.tanh(x @ p["w1"])
        return jnp.mean((h @ p["w2"]) ** 2)
    return jax.value_and_grad(loss)(p)


def _compiled_replay(body: str) -> str:
    n = 4
    graph = ring_graph(n)
    sim = Simulator(_grad_fn, params_from_graph(graph), 0.1, backend="ref",
                    robust_clip=1.0 if body == "channel" else None)
    p0 = {"w1": jnp.full((8, 16), 0.1), "w2": jnp.full((16, 2), 0.1)}
    state = sim.init(p0, n, jax.random.PRNGKey(0))
    sched = make_schedule(graph, 4, seed=0)
    if body == "plain":
        fn, args = sim.schedule_executable(state, sched)
    elif body == "channel":
        arrays, horizon = sim.channel_coalesced_arrays(state, sched)
        fn, args = Simulator._run_channel_jit, (sim, state, arrays,
                                                horizon, None)
    else:
        fn, args = sim.worlds_executable([state, state], [sched, sched])
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("body", ["plain", "channel", "worlds"])
def test_replay_bodies_carry_every_scope(body):
    """Every scope reaches the compiled replay's op_name metadata, and the
    model's matmuls fall under the forward or the backward pass; the
    model's own scope splits the same way, and leaves the replay's map as
    it is."""
    text = _compiled_replay(body)
    for name in tracing.SCOPES:
        assert f"/{name}/" in text, name
    m = scopes.maps(text)
    instrs = [line.split(" = ")[0].split()[-1].lstrip("%")
              for line in text.splitlines() if " dot(" in line]
    assert {m.replay[i] for i in instrs} == {"replay.grad.fwd",
                                             "replay.grad.bwd"}
    assert {m.model[i] for i in instrs if i in m.model} == {
        "model.mlp.fwd", "model.mlp.bwd"}
    assert all(m.replay[i] == "replay.grad." + s.rsplit(".", 1)[1]
               for i, s in m.model.items())


def _record(*ops_per_chip):
    return {"host": [["window", 0, 100 * MS], ["wait", 2 * MS, 98 * MS]],
            "devices": [{"name": f"/device:TPU:{i}", "ops": ops}
                        for i, ops in enumerate(ops_per_chip)]}


MAP = {"fusion.1": "replay.grad.fwd", "fusion.2": "replay.grad.bwd",
       "fusion.3": "replay.update", "fusion.4": "replay.record",
       "a2cid2_gossip.2": "replay.gossip", "while.1": "unscoped",
       "copy.1": "unscoped", "fusion.5": "replay.mix",
       "fusion.6": "replay.unpack", "fusion.8": "replay.pack"}


def test_scope_seconds_sums_leaves_per_chip():
    rec = _record(
        [["%while.1", 0, 90 * MS, "op"],              # holds the rest
         ["%fusion.1", 0, 30 * MS, "op"],
         ["%fusion.2", 30 * MS, 20 * MS, "op"],
         ["%fusion.3", 50 * MS, 4 * MS, "op"],
         ["%fusion.4", 54 * MS, 2 * MS, "op"],
         ["%a2cid2_gossip.2", 60 * MS, 10 * MS, "gossip"],
         ["%copy.9", 70 * MS, 6 * MS, "op"],          # not in the map
         ["%fusion.5", 200 * MS, 5 * MS, "op"]],      # after the window
        [["%fusion.1", 0, 10 * MS, "op"]])
    s = scopes.scope_seconds(rec, MAP)
    assert set(s) == set(MAP.values())
    assert s["replay.grad.fwd"] == pytest.approx((0.030 + 0.010) / 2)
    assert s["replay.grad.bwd"] == pytest.approx(0.010)
    assert s["replay.update"] == pytest.approx(0.002)
    assert s["replay.gossip"] == pytest.approx(0.005)
    assert s["unscoped"] == pytest.approx(0.003)      # the loop not counted
    assert s["replay.mix"] == s["replay.pack"] == 0.0
    red = trace_reduce.reduce(rec)
    assert sum(s.values()) == pytest.approx(
        sum(v for _, v in red["breakdown"]["device_ops"]))
    named = scopes.scoped_ops(red["breakdown"]["device_ops"], MAP,
                              {"fusion.1": {"replay.grad.fwd"}})
    assert named[0] == ["%fusion.1 [replay.grad.fwd]", pytest.approx(0.02),
                        ["replay.grad.fwd"]]
    assert ["%copy.9 [unscoped]", pytest.approx(0.003), []] in named


FACTS = {"chips": 1, "grad_ticks": 10, "traced_units": 640,
         "flops_per_unit": 1e9, "peak_flops": 1e14}
SCOPE_S = {"replay.grad.fwd": 0.01, "replay.grad.bwd": 0.03,
           "replay.unpack": 0.001, "replay.pack": 0.002,
           "replay.update": 0.003, "replay.mix": 0.004,
           "replay.record": 0.005, "replay.gossip": 0.02, "unscoped": 0.0}


@pytest.mark.parametrize("reader, value", [
    (scopes.grad_mfu, 100 * 640 * 1e9 / 0.04 / 1e14),
    (scopes.bank_ms_per_tick, 1e3 * 0.010 / 10),
    (scopes.record_ms_per_tick, 1e3 * 0.005 / 10),
])
def test_scope_readers(reader, value):
    assert reader(SCOPE_S, FACTS) == pytest.approx(value)
    # a program without scopes: every op unscoped
    assert reader({"unscoped": 0.5}, FACTS) is None
    assert reader(SCOPE_S, dict(FACTS, grad_ticks=0, traced_units=0)) \
        is None


def test_profile_at_test_size(monkeypatch, tiny_cell):
    """The profiling script end to end at test size on the CPU, its
    trace replaced by one chip's ops over the traced window."""
    cell = catalog.benchmark()["workloads"][0]["name"]
    cfg, traffic = tiny_cell(cell)
    ops = [["%fusion.1", 0, 30 * MS, "op"],
           ["%fusion.2", 30 * MS, 30 * MS, "op"],
           ["%fusion.4", 60 * MS, 5 * MS, "op"],
           ["%a2cid2_gossip.2", 65 * MS, 10 * MS, "gossip"]]
    seen, real = [], scopes.scope_map

    def scope_map(text):
        seen.append(real(text))
        return MAP
    monkeypatch.setattr(scopes, "scope_map", scope_map)
    monkeypatch.setattr(jax.profiler, "trace",
                        lambda d: contextlib.nullcontext())
    monkeypatch.setattr(trace_reduce, "extract", lambda d: _record(ops))
    monkeypatch.setattr(scope_profile, "peaks",
                        lambda kind: peaks("TPU v5 lite"))
    out = list(scope_profile.profile(cell, [5, 6], 0.2, jax.devices(),
                                     cfg=cfg, traffic=traffic,
                                     backend="ref"))
    assert [o["seed"] for o in out] == [5, 6]
    assert len(seen) == 1               # one executable for every seed
    # replicas are flat vectors: bank <-> pytree is the unpack inside
    # grad_fn, and packing the flat gradient is a no-op
    assert set(seen[0].values()) >= (set(tracing.SCOPES) | set(scopes.GRAD)) \
        - {"replay.grad", "replay.pack"}
    o = out[0]
    assert o["grad_ticks"] > 0
    assert o["scope_s"]["replay.grad.bwd"] == pytest.approx(0.03)
    assert o["record_ms_per_tick"] == pytest.approx(5 / o["grad_ticks"])
    assert o["bank_ms_per_tick"] == 0.0
    assert o["grad_mfu"] > 0
    assert o["device_ops"][0][0] == "%fusion.1 [replay.grad.fwd]"
    assert set(o["gc"]) == {"untraced", "traced"}
    rate = o["train_images_per_s"]
    assert rate["untraced"] > 0 and rate["traced"] > 0
