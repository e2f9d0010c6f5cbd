"""Sharded giant-world replay (DESIGN.md §16).

The contracts under test:

  * pinning (lag 0) — ``run_worlds(..., mesh=...)`` splits the worker
    axis over a device mesh and serves cross-shard partner reads through
    the permute ring, yet the final state is BITWISE the single-device
    engine replay on topology, channel, and defense worlds, on both
    kernel backends (traces allclose: the loss/consensus metrics cross
    shards via psum and reassociate, but never feed the state);
  * pinning (lag > 0) — a positive staleness floor on boundary reads is
    EXACTLY the per-event delay reference: the single-device replay of
    ``world.shard_lag_schedule(sched, NS, L)``;
  * one trace — every world batch on one (mesh, lag) shares ONE compiled
    scan (jit-cache size grows by exactly one across distinct batches);
  * ragged fallback — a worker axis the mesh cannot split evenly warns
    and falls back to the single-device flavors, bitwise;
  * host compiler — ``events.shard_partition`` serves every cross read
    the row its reader asked for, at the slot the schedule resolved.

Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for the
full matrix (CI's forced-multi-device job); on a single device the
multi-shard cases skip and the n_shards=1 mesh path still pins.
"""
import os
import sys

# Standalone (this file alone, jax not yet imported anywhere) force an
# 8-device host so the full cross-shard matrix runs.  Inside the full
# suite another module has already imported jax — leave the platform
# alone (tier-1 stays on its native device count; the multi-device
# cases skip) and let CI's forced-multi-device job set the env itself.
if "jax" not in sys.modules:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AdaptiveDefense, ByzantineEdges, ChannelModel,
                        DelayProcess, Simulator, World, params_from_graph,
                        ring_graph)
from repro.core.events import shard_lag_stale, shard_partition
from repro.core.telemetry import Telemetry, cross_shard_reads
from repro.core.world import shard_cross_reads, shard_lag_schedule
from repro.launch.mesh import make_replay_mesh
from repro.launch.mesh_replay import MeshReplay, sharded_twin

N, D, ROUNDS = 16, 24, 6
NDEV = jax.local_device_count()
NS = min(8, NDEV)
multi = pytest.mark.skipif(NDEV < 2, reason="needs >= 2 devices "
                           "(XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8)")

BACKENDS = ["ref", "pallas_interpret"]


def _quad_grad_fn(b):
    def grad_fn(x, key, wid):
        g = (x - b[wid]).astype(x.dtype)
        g = g + (0.05 * jax.random.normal(key, x.shape)).astype(x.dtype)
        return 0.5 * jnp.sum(g ** 2), g
    return grad_fn


def _make_sim(backend="ref", **kw):
    g = ring_graph(N)
    b = jax.random.normal(jax.random.PRNGKey(1), (N, D))
    return Simulator(_quad_grad_fn(b), params_from_graph(g, True),
                     gamma=0.05, backend=backend, **kw)


def _states(sim, count):
    return [sim.init(jnp.zeros(D), N, jax.random.PRNGKey(2))
            for _ in range(count)]


def _mesh(n=None):
    return MeshReplay(make_replay_mesh(NS if n is None else n))


def _assert_ulp_pinned(a, b, maxulp=0):
    """``a`` equals ``b`` bitwise (maxulp 0), or within ``maxulp`` ulps of
    ``b``'s largest magnitude (a rounding difference upstream of a
    cancelling subtraction is many ulps of the small result)."""
    a, b = np.asarray(a), np.asarray(b)
    if maxulp == 0:
        np.testing.assert_array_equal(a, b)
        return
    tol = maxulp * np.spacing(np.max(np.abs(b)).astype(b.dtype))
    err = np.max(np.abs(a.astype(np.float64) - b.astype(np.float64)))
    assert err <= tol, f"max |a - b| = {err} > {maxulp} ulp ({tol})"


def _assert_state_pinned(f0, f1, maxulp=0):
    for a, c in zip(jax.tree.leaves(f0.x), jax.tree.leaves(f1.x)):
        _assert_ulp_pinned(a, c, maxulp)
    for a, c in zip(jax.tree.leaves(f0.x_tilde),
                    jax.tree.leaves(f1.x_tilde)):
        _assert_ulp_pinned(a, c, maxulp)


def _pin_mesh(sim, worlds, seeds, mr, maxulp=0, **kw):
    """mesh= replay of a batch equals the single-device replay: states
    bitwise (or within ``maxulp`` ulps), traces allclose (metrics psum
    across shards)."""
    scheds = [w.compile(ROUNDS, seed=s) for w, s in zip(worlds, seeds)]
    states = _states(sim, len(scheds))
    f0, t0 = sim.run_worlds(states, scheds, **kw)
    f1, t1 = sim.run_worlds(states, scheds, mesh=mr, **kw)
    _assert_state_pinned(f0, f1, maxulp)
    np.testing.assert_allclose(np.asarray(t0.loss), np.asarray(t1.loss),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(t0.consensus),
                               np.asarray(t1.consensus), rtol=1e-6)
    return f1, t1


# ------------------------------------------------------------ lag-0 pinning

@multi
@pytest.mark.parametrize("backend", BACKENDS)
def test_topology_worlds_pin(backend):
    ring = ring_graph(N)
    sim = _make_sim(backend)
    _pin_mesh(sim, [World(topology=ring), World(topology=ring)], [0, 1],
              _mesh())


@multi
@pytest.mark.parametrize("backend", BACKENDS)
def test_channel_worlds_pin(backend):
    """Delay + Byzantine + drop channels: the publisher-resolved permute
    ring serves the SAME snapshots the single-device ring read."""
    ring = ring_graph(N)
    sim = _make_sim(backend)
    _pin_mesh(sim, [
        World(topology=ring, channel=ChannelModel(
            delay=DelayProcess(horizon=2, prob=0.7))),
        World(topology=ring, channel=ChannelModel(
            adversary=ByzantineEdges(ring.edges[:2], "scale", scale=40.0,
                                     prob=0.6),
            drop_prob=0.1)),
    ], [1, 3], _mesh())


@multi
def test_defense_worlds_pin():
    """Self-healing defense: trust rows shard with the readers, the
    gathered tau sort and the psum'd integer counters are exact, so the
    defense trace pins bitwise too."""
    ring = ring_graph(N)
    sim = _make_sim(robust_rule="trim")
    byz = World(topology=ring, channel=ChannelModel(
        adversary=ByzantineEdges(ring.edges[:3], "scale", scale=60.0,
                                 prob=0.5)))
    scheds = [byz.compile(ROUNDS, seed=s) for s in (0, 1)]
    states = _states(sim, 2)
    dfs = [AdaptiveDefense(), AdaptiveDefense()]
    f0, t0 = sim.run_worlds(states, scheds, defenses=dfs)
    f1, t1 = sim.run_worlds(states, scheds, defenses=dfs, mesh=_mesh())
    _assert_state_pinned(f0, f1)
    np.testing.assert_array_equal(np.asarray(t0.defense.tau),
                                  np.asarray(t1.defense.tau))
    np.testing.assert_array_equal(np.asarray(t0.defense.rejections),
                                  np.asarray(t1.defense.rejections))
    np.testing.assert_array_equal(np.asarray(t0.defense.quarantined),
                                  np.asarray(t1.defense.quarantined))


def test_single_shard_mesh_pins():
    """An n_shards=1 mesh (always constructible) runs the sharded twins
    with an empty boundary and still pins — the degenerate case every
    device count can exercise.  The pin is ulp-level (within 2 ulp of the
    bank's scale), not bitwise: XLA:CPU contracts multiply-adds into FMAs
    inside fused loops, and the shard_map body and the single-device scan
    fuse differently.  With fusion off, or ``--xla_cpu_max_isa=SSE4_2``,
    the pin is bitwise (DESIGN.md §16)."""
    ring = ring_graph(N)
    sim = _make_sim()
    _pin_mesh(sim, [World(topology=ring, channel=ChannelModel(
        delay=DelayProcess(horizon=2, prob=0.5)))], [2], _mesh(1),
        maxulp=2)


@multi
def test_run_schedule_mesh_lift():
    """run_schedule(mesh=) lifts to a B=1 worlds replay and squeezes —
    the batched-equals-serial precedent (signed zeros aside)."""
    ring = ring_graph(N)
    sim = _make_sim()
    sch = World(topology=ring).compile(ROUNDS, seed=0)
    st = _states(sim, 1)[0]
    f0, t0 = sim.run_schedule(st, sch)
    f1, t1 = sim.run_schedule(st, sch, mesh=_mesh())
    assert t1.loss.shape == (ROUNDS,)
    for a, c in zip(jax.tree.leaves(f0.x), jax.tree.leaves(f1.x)):
        np.testing.assert_array_equal(np.abs(np.asarray(a)),
                                      np.abs(np.asarray(c)))


# ----------------------------------------------------------- lag>0 pinning

@multi
@pytest.mark.parametrize("lag", [1, 2])
def test_lagged_ring_equals_delay_reference(lag):
    """MeshReplay(lag=L) IS a ChannelModel(delay=...) on the boundary:
    bitwise the single-device replay of shard_lag_schedule(sched, NS, L)."""
    ring = ring_graph(N)
    sim = _make_sim()
    w = World(topology=ring, channel=ChannelModel(
        delay=DelayProcess(horizon=3, prob=0.5)))
    scheds = [w.compile(ROUNDS, seed=7)]
    states = _states(sim, 1)
    f1, _ = sim.run_worlds(states, scheds,
                           mesh=MeshReplay(make_replay_mesh(NS), lag=lag))
    refs = [shard_lag_schedule(s, NS, lag) for s in scheds]
    f0, _ = sim.run_worlds(states, refs)
    _assert_state_pinned(f0, f1)


@multi
def test_lagged_plain_world():
    """lag > 0 engages the ring even on a delay-free schedule (boundary
    reads become stale) and still matches the rewritten-extras reference."""
    ring = ring_graph(N)
    sim = _make_sim()
    scheds = [World(topology=ring).compile(ROUNDS, seed=3)]
    states = _states(sim, 1)
    f1, _ = sim.run_worlds(states, scheds,
                           mesh=MeshReplay(make_replay_mesh(NS), lag=2))
    f0, _ = sim.run_worlds(states,
                           [shard_lag_schedule(s, NS, 2) for s in scheds])
    _assert_state_pinned(f0, f1)


# --------------------------------------------------- trace & dispatch cost

@multi
def test_one_trace_per_mesh():
    """One trace, one dispatch: a whole world batch costs a single
    compiled scan, and every batch whose stacked stream (and permute-
    ring pool) keeps its shape rides that SAME trace — different
    matchings, keys, and states never retrace.  (A batch that changes
    the stream length or the data-dependent pool width legitimately
    costs a new trace — that is shape polymorphism, not cache misses.)"""
    ring = ring_graph(N)
    sim = _make_sim()
    mr = _mesh()
    fn = sharded_twin("channel", donate=False)
    scheds_a = [World(topology=ring).compile(ROUNDS, seed=s)
                for s in (4, 5)]
    scheds_b = [World(topology=ring).compile(ROUNDS, seed=s)
                for s in (6, 7)]
    # precondition: the two batches stack to identical stream shapes
    _, args_a = sim.worlds_executable(_states(sim, 2), scheds_a, mesh=mr)
    _, args_b = sim.worlds_executable(_states(sim, 2), scheds_b, mesh=mr)
    shp = lambda args: [getattr(l, "shape", None)
                        for l in jax.tree.leaves(args)]
    assert shp(args_a) == shp(args_b)
    base = fn._cache_size()
    sim.run_worlds(_states(sim, 2), scheds_a, mesh=mr)
    assert fn._cache_size() == base + 1      # one trace for the batch
    sim.run_worlds(_states(sim, 2), scheds_a, mesh=mr)   # fresh replay
    sim.run_worlds(_states(sim, 2), scheds_b, mesh=mr)   # fresh batch
    assert fn._cache_size() == base + 1      # ...and no more


# ----------------------------------------------------------- ragged fallback

def test_ragged_worker_axis_falls_back():
    """n % n_shards != 0 cannot shard; warn and replay single-device,
    bitwise."""
    n_odd = 15
    g = ring_graph(n_odd)
    b = jax.random.normal(jax.random.PRNGKey(1), (n_odd, D))
    sim = Simulator(_quad_grad_fn(b), params_from_graph(g, True),
                    gamma=0.05)
    scheds = [World(topology=g).compile(ROUNDS, seed=0)]
    states = [sim.init(jnp.zeros(D), n_odd, jax.random.PRNGKey(2))]
    f0, _ = sim.run_worlds(states, scheds)
    mr = MeshReplay(make_replay_mesh(min(2, NDEV)))
    if mr.n_shards == 1:  # 15 % 1 == 0: force a ragged shard count
        pytest.skip("needs a >1-shard mesh to be ragged")
    with pytest.warns(RuntimeWarning, match="not divisible"):
        f1, _ = sim.run_worlds(states, scheds, mesh=mr)
    _assert_state_pinned(f0, f1)


def test_engine_false_mesh_raises():
    ring = ring_graph(N)
    sim = _make_sim()
    scheds = [World(topology=ring).compile(ROUNDS, seed=0)]
    with pytest.raises(ValueError, match="flat-buffer engine"):
        sim.run_worlds(_states(sim, 1), scheds, engine=False,
                       mesh=_mesh(1))


# ------------------------------------------------------------- telemetry

@multi
def test_cross_shard_byte_split():
    """bytes split into intra vs cross: cross = boundary reads x the
    flat row width; intra + cross = applied bytes of the unsharded
    accounting (total conserved)."""
    ring = ring_graph(N)
    sim = _make_sim()
    tel = Telemetry(bytes_moved=True)
    w = World(topology=ring, channel=ChannelModel(drop_prob=0.2))
    scheds = [w.compile(ROUNDS, seed=5)]
    states = _states(sim, 1)
    _, t0 = sim.run_worlds(states, scheds, telemetry=tel)
    _, t1 = sim.run_worlds(states, scheds, telemetry=tel, mesh=_mesh())
    tt0, tt1 = t0.telemetry, t1.telemetry
    assert tt0.cross_reads is None and tt0.bytes_cross is None
    assert tt1.cross_reads is not None
    survived = (np.asarray(tt1.scheduled) - np.asarray(tt1.dropped)) \
        * float(tt1.row_bytes)
    np.testing.assert_array_equal(
        np.asarray(tt1.bytes_intra) + np.asarray(tt1.bytes_cross), survived)
    np.testing.assert_array_equal(np.asarray(tt0.bytes_moved),
                                  np.asarray(tt1.bytes_moved))
    np.testing.assert_array_equal(
        np.asarray(tt1.bytes_cross),
        np.asarray(tt1.cross_reads, np.float64) * tt1.row_bytes)
    # the exact count from the schedule, independent of the replay
    want = np.stack([cross_shard_reads(s.partners, s.event_mask, NS)
                     for s in scheds])
    np.testing.assert_array_equal(np.asarray(tt1.cross_reads), want)


def test_telemetry_none_stays_noop():
    """telemetry=None under mesh= adds no columns and changes nothing."""
    ring = ring_graph(N)
    sim = _make_sim()
    scheds = [World(topology=ring).compile(ROUNDS, seed=0)]
    _, tr = sim.run_worlds(_states(sim, 1), scheds, mesh=_mesh(1))
    assert tr.telemetry is None


# ------------------------------------------------------- host-side compiler

def test_shard_partition_serves_requested_rows():
    """Every cross read's (hop, pool_pos) lands on the row and slot its
    reader asked for; intra reads keep a local involution."""
    rng = np.random.default_rng(0)
    S, B, n, ns, h = 5, 2, 16, 4, 3
    ws = n // ns
    partners = np.tile(np.arange(n, dtype=np.int32), (S, B, 1))
    for s in range(S):
        for bi in range(B):
            perm = rng.permutation(n)
            for k in range(0, n, 2):
                i, j = perm[k], perm[k + 1]
                partners[s, bi, i], partners[s, bi, j] = j, i
    src_slot = rng.integers(0, h + 1, (S, B, n)).astype(np.int32)
    plan = shard_partition(partners, src_slot, ns, h)
    assert plan.shard_size == ws
    rdr = np.arange(n)
    for s in range(S):
        for bi in range(B):
            for i in range(n):
                p = partners[s, bi, i]
                if p == i:
                    assert not plan.is_cross[s, bi, i]
                    assert plan.local_partner[s, bi, i] == i % ws
                elif p // ws == i // ws:
                    assert not plan.is_cross[s, bi, i]
                    assert plan.local_partner[s, bi, i] == p % ws
                else:
                    assert plan.is_cross[s, bi, i]
                    hop = plan.hop[s, bi, i]
                    assert hop == (i // ws - p // ws) % ns
                    k = plan.pool_pos[s, bi, i]
                    assert plan.pub_row[s, p // ws, bi, k] == p % ws
                    assert plan.pub_slot[s, p // ws, bi, k] \
                        == src_slot[s, bi, i]
    np.testing.assert_array_equal(
        plan.cross_reads,
        (plan.is_cross & (partners != rdr)).sum(axis=-1))
    # intra restriction is an involution per shard
    lp = plan.local_partner
    for s in range(S):
        for bi in range(B):
            for u in range(ns):
                blk = lp[s, bi, u * ws:(u + 1) * ws]
                intra = ~plan.is_cross[s, bi, u * ws:(u + 1) * ws]
                got = blk[blk[np.arange(ws)]][intra]
                np.testing.assert_array_equal(got, np.arange(ws)[intra])


def test_shard_lag_stale_floors_cross_only():
    S, B, n, ns = 4, 1, 8, 2
    partners = np.tile(np.arange(n, dtype=np.int32), (S, B, 1))
    partners[:, 0, 0], partners[:, 0, 4] = 4, 0      # cross pair
    partners[:, 0, 1], partners[:, 0, 2] = 2, 1      # intra pair
    stale = np.zeros((S, B, n), np.int32)
    stale[:, 0, 1] = 2
    step_round = np.array([0, 1, 2, 3])
    out = shard_lag_stale(partners, stale, step_round, ns, lag=2)
    np.testing.assert_array_equal(out[:, 0, 0], [0, 1, 2, 2])  # floored
    np.testing.assert_array_equal(out[:, 0, 1], [2, 2, 2, 2])  # untouched
    np.testing.assert_array_equal(out[:, 0, 3], [0, 0, 0, 0])  # idle


def test_shard_lag_schedule_rewrites_extras():
    ring = ring_graph(N)
    sch = World(topology=ring).compile(ROUNDS, seed=0)
    out = shard_lag_schedule(sch, 4, 2)
    assert out is not sch
    from repro.core.channel import STALE_KEY
    st = out.extras_dict()[STALE_KEY]
    assert (st >= 0).all() and st.max() <= 2
    assert shard_lag_schedule(sch, 1, 2) is sch
    assert shard_lag_schedule(sch, 4, 0) is sch


def test_cross_shard_reads_counts():
    ring = ring_graph(N)
    sch = World(topology=ring).compile(ROUNDS, seed=0)
    c2 = shard_cross_reads(sch, 2)
    assert c2.shape == (ROUNDS,) and c2.dtype == np.int64
    assert (shard_cross_reads(sch, 1) == 0).all()
    # a ring of N has exactly 2 boundary edges per shard cut; every
    # matched boundary edge contributes 2 directed reads
    assert (c2 >= 0).all()


# ------------------------------------------------------------- mesh plumbing

def test_make_replay_mesh_host_aware():
    m = make_replay_mesh()
    assert m.axis_names == ("worker",)
    assert m.shape["worker"] == NDEV
    assert make_replay_mesh(1).shape["worker"] == 1
    with pytest.raises(ValueError, match="local devices"):
        make_replay_mesh(NDEV + 1)
    with pytest.raises(ValueError, match="local devices"):
        make_replay_mesh(0)


def test_replay_mesh_rules():
    from repro.launch.mesh import rules_for
    from repro import sharding
    assert rules_for(make_replay_mesh(1)) == dict(sharding.REPLAY_RULES)


def test_mesh_replay_validation():
    m = make_replay_mesh(1)
    with pytest.raises(ValueError, match="lag"):
        MeshReplay(m, lag=-1)
    with pytest.raises(ValueError, match="axis"):
        MeshReplay(m, axis="data")
    mr = MeshReplay(m, lag=3)
    assert mr.n_shards == 1
    assert hash(mr) == hash(MeshReplay(m, lag=3))
