"""Batched many-worlds replay (DESIGN.md §11).

The contracts under test:

  * pinning — ``Simulator.run_worlds`` replays every world of a batch
    bit-for-bit identically to its serial per-world replay (within 2 ulp
    of the bank's scale where XLA fuses the two programs differently; see
    DESIGN.md §11), per flavor
    (engine vs per-event reference, plain vs channel), across ragged
    stream lengths, channel worlds with DISTINCT staleness horizons, and
    both kernel backends (jnp oracle + interpret-mode Pallas);
  * alignment — ``events.stack_streams`` pads each round to the per-round
    max batch count with identity groups, so ``is_grad``/``grad_pos`` are
    shared across the batch and padding is an exact replay no-op;
  * per-world dynamics — baseline (eta 0) and accelerated worlds share
    ONE batched dispatch via the dynamic (B,) parameter arrays and still
    pin to their serial static-scalar replays;
  * sweep API — ``WorldSweep`` builds/validates/serializes grids and
    compiles them host-side, one schedule per (world, seed) point;
  * donation — ``Simulator(donate=True)`` consumes the input state
    (buffers reused for the scan carries) and produces the same replay.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ByzantineEdges, ChannelModel, DelayProcess,
                        Simulator, World, WorldSweep, build_graph,
                        coalesce_schedule, params_from_graph, ring_graph,
                        stack_schedules, stack_streams)

N, D, ROUNDS = 8, 24, 7

BACKENDS = ["ref", "pallas_interpret"]


def _quad_grad_fn(b):
    def grad_fn(x, key, wid):
        g = (x - b[wid]).astype(x.dtype)
        g = g + (0.05 * jax.random.normal(key, x.shape)).astype(x.dtype)
        return 0.5 * jnp.sum(g ** 2), g
    return grad_fn


def _make_sim(backend="ref", robust_clip=None, robust_rule="trim",
              donate=False, accelerated=True):
    g = ring_graph(N)
    b = jax.random.normal(jax.random.PRNGKey(1), (N, D))
    return Simulator(_quad_grad_fn(b), params_from_graph(g, accelerated),
                     gamma=0.05, backend=backend, robust_clip=robust_clip,
                     robust_rule=robust_rule, donate=donate)


def _states(sim, count):
    return [sim.init(jnp.zeros(D), N, jax.random.PRNGKey(2))
            for _ in range(count)]


def _assert_ulp_pinned(a, b, maxulp=0):
    """``a`` equals ``b`` bitwise (maxulp 0), or within ``maxulp`` ulps of
    ``b``'s largest magnitude.  The scale is the array's, not each
    element's: a 1-ulp rounding difference upstream of a cancelling
    subtraction is many ulps of the small result."""
    a, b = np.asarray(a), np.asarray(b)
    if maxulp == 0:
        np.testing.assert_array_equal(a, b)
        return
    tol = maxulp * np.spacing(np.max(np.abs(b)).astype(b.dtype))
    err = np.max(np.abs(a.astype(np.float64) - b.astype(np.float64)))
    assert err <= tol, f"max |a - b| = {err} > {maxulp} ulp ({tol})"


def _assert_world_pinned(sim, fin, tr, i, serial_fin, serial_tr, maxulp=0):
    """World i of a batched replay equals its serial replay (states AND
    per-round traces): bit-for-bit, or within ``maxulp`` ulps."""
    for bl, sl in zip(jax.tree.leaves(fin.x), jax.tree.leaves(serial_fin.x)):
        _assert_ulp_pinned(bl[i], sl, maxulp)
    for bl, sl in zip(jax.tree.leaves(fin.x_tilde),
                      jax.tree.leaves(serial_fin.x_tilde)):
        _assert_ulp_pinned(bl[i], sl, maxulp)
    np.testing.assert_array_equal(np.asarray(fin.t_last[i]),
                                  np.asarray(serial_fin.t_last))
    _assert_ulp_pinned(tr.loss[i], serial_tr.loss, maxulp)
    _assert_ulp_pinned(tr.consensus[i], serial_tr.consensus, maxulp)


def _pin_batch(sim, worlds_params_seeds, engine, maxulp=0):
    """Run the batch through run_worlds and pin every world to its serial
    replay on the same flavor."""
    scheds = [w.compile(ROUNDS, seed=s) for w, _, s in worlds_params_seeds]
    plist = [p for _, p, _ in worlds_params_seeds]
    states = _states(sim, len(scheds))
    fin, tr = sim.run_worlds(states, scheds, params=plist, engine=engine)
    assert tr.consensus.shape == (len(scheds), ROUNDS)
    for i, (st, sch, p) in enumerate(zip(states, scheds, plist)):
        serial = dataclasses.replace(sim, params=p)
        sfin, str_ = serial.run_schedule(st, sch, engine=engine)
        _assert_world_pinned(sim, fin, tr, i, sfin, str_, maxulp)


# ---------------------------------------------------------------- pinning

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("engine", [True, False])
def test_batched_equals_serial_ragged_mixed_params(backend, engine):
    """Ragged stream lengths (comms_per_grad grid + a different topology)
    and mixed baseline/accelerated params, one batch, every world pinned.

    The engine pin is ulp-level (within 2 ulp of the bank's scale), not
    bitwise: XLA:CPU contracts multiply-adds into FMAs inside its fused
    loops, and the serial program (static eta, alpha) and the batched one
    (per-world arrays) fuse the gradient update differently.  With fusion
    off, or on an ISA without FMA (``--xla_cpu_max_isa=SSE4_2``), the
    two pin bitwise.  The per-event reference pins bitwise."""
    ring = ring_graph(N)
    comp = build_graph("complete", N)
    sim = _make_sim(backend=backend)
    batch = [
        (World(topology=ring, comms_per_grad=0.5),
         params_from_graph(ring, True), 0),
        (World(topology=ring, comms_per_grad=0.5),
         params_from_graph(ring, False), 0),
        (World(topology=ring, comms_per_grad=2.5),
         params_from_graph(ring, True), 1),
        (World(topology=comp),
         params_from_graph(comp, True), 2),
    ]
    _pin_batch(sim, batch, engine, maxulp=2 if engine else 0)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("engine", [True, False])
def test_batched_channel_distinct_horizons(backend, engine):
    """Channel worlds with DISTINCT delay horizons (plus a clean world and
    a Byzantine/drop world) share one batched channel replay; each pins to
    its serial replay — the shared ring depth H = max horizon serves every
    world the same snapshots its own-depth serial ring would.

    The engine pin is ulp-level (within 2 ulp of the bank's scale), for
    the FMA-contraction reason given above; the per-event reference pins
    bitwise."""
    ring = ring_graph(N)
    acid = params_from_graph(ring, True)
    base = params_from_graph(ring, False)
    sim = _make_sim(backend=backend)
    batch = [
        (World(topology=ring), acid, 0),   # clean: exact no-op extras
        (World(topology=ring, channel=ChannelModel(
            delay=DelayProcess(horizon=2, prob=0.7))), acid, 1),
        (World(topology=ring, channel=ChannelModel(
            delay=DelayProcess(horizon=5, prob=1.0))), base, 2),
        (World(topology=ring, channel=ChannelModel(
            adversary=ByzantineEdges(ring.edges[:2], "scale", scale=40.0,
                                     prob=0.6),
            drop_prob=0.1)), acid, 3),
    ]
    _pin_batch(sim, batch, engine, maxulp=2 if engine else 0)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("rule", ["trim", "clip", "coord"])
def test_batched_robust_rules_pin(backend, rule):
    """Robust aggregation (all three rules) on a Byzantine batch pins to
    the serial robust replay on both kernel backends."""
    ring = ring_graph(N)
    acid = params_from_graph(ring, True)
    sim = _make_sim(backend=backend, robust_clip=4.0, robust_rule=rule)
    byz = World(topology=ring, channel=ChannelModel(
        adversary=ByzantineEdges(ring.edges[:3], "scale", scale=60.0,
                                 prob=0.5)))
    batch = [(byz, acid, 0), (byz, acid, 1),
             (World(topology=ring), acid, 0)]
    _pin_batch(sim, batch, True)


def test_batched_hetero_worlds_pin():
    """Stragglers and churned (statically detached) workers ride the batch
    axis unchanged: grad_scale/alive are per-world stream data."""
    from repro.core import WorkerModel
    ring = ring_graph(N)
    acid = params_from_graph(ring, True)
    rates = np.where(np.arange(N) % 2 == 0, 1.0, 0.25)
    active = np.ones(N, bool)
    active[0] = False
    sim = _make_sim()
    batch = [
        (World(topology=ring, workers=WorkerModel(grad_rates=rates)),
         acid, 0),
        (World(topology=ring, workers=WorkerModel(active=active)), acid, 1),
        (World(topology=ring), acid, 2),
    ]
    for engine in (True, False):
        _pin_batch(sim, batch, engine)


# -------------------------------------------------------------- alignment

def test_stack_streams_alignment_and_padding():
    ring = ring_graph(N)
    scheds = [World(topology=ring, comms_per_grad=c).compile(ROUNDS, seed=s)
              for c, s in ((0.5, 0), (3.0, 1), (1.0, 2))]
    css = [coalesce_schedule(s) for s in scheds]
    t0 = np.zeros((3, N), np.float32)
    bs = stack_streams(css, t0)
    counts = np.stack([cs.batch_active.sum(axis=1) for cs in css])
    # shared skeleton: one grad tick per round + per-round max comm steps
    assert bs.is_grad.sum() == ROUNDS
    assert bs.steps == int(counts.max(axis=0).sum()) + ROUNDS
    assert np.array_equal(np.nonzero(bs.is_grad)[0], np.asarray(bs.grad_pos))
    # padding slots are identity groups (self partners); the mixing
    # segment to the next event migrates onto the last pad of a run, so
    # per-worker elapsed time is preserved exactly
    idx = np.arange(N)
    from repro.core import coalesced_stream
    for b, cs in enumerate(css):
        comm = ~bs.is_grad
        pad_rows = (bs.partners[comm, b] == idx).all(axis=1)
        assert pad_rows.sum() == int((counts.max(axis=0) - counts[b]).sum())
        solo = coalesced_stream(cs, t0[b])
        np.testing.assert_array_equal(bs.t_final[b], solo.t_final)
        np.testing.assert_allclose(
            bs.prologue[b].astype(np.float64)
            + bs.dt_next[:, b].sum(axis=0, dtype=np.float64),
            solo.prologue.astype(np.float64)
            + solo.dt_next.sum(axis=0, dtype=np.float64), rtol=1e-5)


def test_stack_streams_validates_frame():
    ring = ring_graph(N)
    s1 = coalesce_schedule(World(topology=ring).compile(4, seed=0))
    s2 = coalesce_schedule(World(topology=ring).compile(5, seed=0))
    with pytest.raises(ValueError, match="share one frame"):
        stack_streams([s1, s2], np.zeros((2, N), np.float32))
    with pytest.raises(ValueError, match="t0 must be"):
        stack_streams([s1], np.zeros((2, N), np.float32))


def test_stack_schedules_pads_and_unions_extras():
    ring = ring_graph(N)
    clean = World(topology=ring, comms_per_grad=0.5).compile(ROUNDS, seed=0)
    chan = World(topology=ring, comms_per_grad=2.0,
                 channel=ChannelModel(delay=DelayProcess(horizon=3))
                 ).compile(ROUNDS, seed=1)
    b = stack_schedules([clean, chan])
    kmax = max(clean.partners.shape[1], chan.partners.shape[1])
    assert b.partners.shape == (ROUNDS, 2, kmax, N)
    from repro.core.channel import STALE_KEY
    assert set(b.extras) == {STALE_KEY}
    assert (b.extras[STALE_KEY][:, 0] == 0).all()    # clean world: zeros
    assert (b.extras[STALE_KEY][:, 1] > 0).any()
    with pytest.raises(ValueError, match="share one frame"):
        stack_schedules([clean, World(topology=ring_graph(4)).compile(
            ROUNDS, seed=0)])


# -------------------------------------------------------------- sweep API

def test_world_sweep_over_and_points():
    ring = ring_graph(N)
    sweep = WorldSweep.over(World(topology=ring), seeds=(0, 1),
                            comms_per_grad=[0.5, 1.0, 2.0])
    assert sweep.size == 6 and len(sweep.worlds) == 3
    pts = sweep.points()
    assert [s for _, s in pts] == [0, 1, 0, 1, 0, 1]
    assert [w.comms_per_grad for w, _ in pts] == [.5, .5, 1., 1., 2., 2.]
    scheds = sweep.compile(5)
    assert len(scheds) == 6 and all(s.rounds == 5 for s in scheds)
    # point i of compile() is point i of points()
    ref = pts[3][0].compile(5, seed=pts[3][1])
    np.testing.assert_array_equal(scheds[3].partners, ref.partners)


def test_world_sweep_validation_and_json():
    ring = ring_graph(N)
    with pytest.raises(ValueError, match="at least one world"):
        WorldSweep(())
    with pytest.raises(ValueError, match="at least one seed"):
        WorldSweep((World(topology=ring),), seeds=())
    with pytest.raises(ValueError, match="share one worker count"):
        WorldSweep((World(topology=ring), World(topology=ring_graph(4))))
    with pytest.raises(ValueError, match="unknown World field"):
        WorldSweep.over(World(topology=ring), warp_factor=[1, 2])
    sweep = WorldSweep.over(
        World(topology=ring), seeds=(3,),
        channel=[None, ChannelModel(delay=DelayProcess(horizon=2))])
    s = sweep.to_json()
    back = WorldSweep.from_json(s)
    assert back == sweep and back.to_json() == s


def test_run_worlds_validates_batch():
    sim = _make_sim()
    ring = ring_graph(N)
    scheds = [World(topology=ring).compile(3, seed=i) for i in range(2)]
    states = _states(sim, 3)
    with pytest.raises(ValueError, match="3 worlds but 2 schedules"):
        sim.run_worlds(states, scheds)
    with pytest.raises(ValueError, match="one entry per world"):
        sim.run_worlds(states[:2], scheds, params=[sim.params])


# --------------------------------------------------------------- donation

def test_donating_replay_consumes_state_and_matches():
    ring = ring_graph(N)
    sch = World(topology=ring).compile(ROUNDS, seed=0)
    plain = _make_sim()
    st = plain.init(jnp.zeros(D), N, jax.random.PRNGKey(2))
    ref_fin, ref_tr = plain.run_schedule(st, sch)

    dsim = _make_sim(donate=True)
    dst = dsim.init(jnp.zeros(D), N, jax.random.PRNGKey(2))
    leaf = jax.tree.leaves(dst.x)[0]
    fin, tr = dsim.run_schedule(dst, sch)
    jax.block_until_ready(fin)
    np.testing.assert_array_equal(np.asarray(tr.consensus),
                                  np.asarray(ref_tr.consensus))
    # CPU (and TPU) honor donation: the input buffer is gone, its memory
    # rehomed into the scan carries
    assert leaf.is_deleted()


def test_donating_batched_replay_consumes_state_and_matches():
    ring = ring_graph(N)
    scheds = [World(topology=ring).compile(ROUNDS, seed=s)
              for s in range(3)]
    plain = _make_sim()
    states = _states(plain, 3)
    ref_fin, ref_tr = plain.run_worlds(states, scheds)

    dsim = _make_sim(donate=True)
    batched = dsim.batch_states(_states(dsim, 3))
    leaf = jax.tree.leaves(batched.x)[0]
    fin, tr = dsim.run_worlds(batched, scheds)
    jax.block_until_ready(fin)
    np.testing.assert_array_equal(np.asarray(tr.consensus),
                                  np.asarray(ref_tr.consensus))
    assert leaf.is_deleted()


# ----------------------------------------------------------- trace counts

def test_one_trace_per_family_shape():
    """A whole grid — baseline + accelerated across a comms grid and
    seeds — retraces the batched jit exactly once; replaying the same
    family shape again adds no trace."""
    ring = ring_graph(N)
    sweep = WorldSweep.over(World(topology=ring), seeds=(0, 1),
                            comms_per_grad=[0.5, 2.0])
    scheds = sweep.compile(ROUNDS)
    acid = params_from_graph(ring, True)
    base = params_from_graph(ring, False)
    plist = [acid, base] * 2
    sim = _make_sim()
    before = Simulator._run_worlds_jit._cache_size()
    fin, tr = sim.run_worlds(_states(sim, 4), scheds, params=plist)
    jax.block_until_ready(fin)
    mid = Simulator._run_worlds_jit._cache_size()
    fin, tr = sim.run_worlds(_states(sim, 4), scheds, params=plist)
    jax.block_until_ready(fin)
    after = Simulator._run_worlds_jit._cache_size()
    assert mid - before == 1
    assert after == mid
