"""Flat-buffer fused gossip-event engine: equivalence vs the per-event
reference path, conservation laws, and layout round-trips (see DESIGN.md)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FlatGossipEngine, FlatLayout, Simulator,
                        coalesce_schedule, make_schedule, params_from_graph,
                        ring_graph)
from repro.kernels.a2cid2_mixing.kernel import mixing_gossip_stacked
from repro.kernels.a2cid2_mixing.ref import (mixing_gossip_stacked_ref,
                                             p2p_mixing_ref)


def _mixed_dtype_tree(w=None):
    """Pytree with mixed dtypes/shapes; optionally worker-stacked."""
    key = jax.random.PRNGKey(0)

    def leaf(k, shape, dtype):
        s = ((w,) + shape) if w else shape
        return jax.random.normal(jax.random.fold_in(key, k), s).astype(dtype)

    return {
        "dense": {"w": leaf(0, (7, 5), jnp.float32),
                  "b": leaf(1, (5,), jnp.bfloat16)},
        "scale": leaf(2, (), jnp.float32),
        "embed": [leaf(3, (11, 3), jnp.float16), leaf(4, (130,), jnp.float32)],
    }


# ------------------------------------------------------------------- layout

@pytest.mark.parametrize("stacked", [False, True, "worlds"])
def test_pack_unpack_roundtrip_exact_mixed_dtypes(stacked):
    if stacked == "worlds":  # (B, W, *shape): two worlds of four workers
        tree = jax.tree.map(lambda a: jnp.stack([a, -a]),
                            _mixed_dtype_tree(w=4))
        layout = FlatLayout.from_pytree(tree, worlds=True)
        buf = layout.pack_worlds(tree)
        out = layout.unpack_worlds(buf)
    else:
        tree = _mixed_dtype_tree(w=4 if stacked else None)
        layout = FlatLayout.from_pytree(tree, stacked=stacked)
        buf = layout.pack(tree) if stacked else layout.pack_local(tree)
        out = layout.unpack(buf) if stacked else layout.unpack_local(buf)
    assert layout.d % 128 == 0 and layout.d >= layout.d_real
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # padding columns are zero (reductions over the buffer need no masking)
    flat = buf.reshape(-1, layout.d)[0]
    np.testing.assert_array_equal(flat[layout.d_real:], 0.0)


def _odd_tree():
    """Thirty leaves of odd sizes, every third one bf16 (f32 buffer)."""
    key = jax.random.PRNGKey(1)
    return {f"l{i:02d}": jax.random.normal(
        jax.random.fold_in(key, i), (2 * i + 1, 3) if i % 2 else (i + 2,)
    ).astype(jnp.bfloat16 if i % 3 == 0 else jnp.float32) for i in range(30)}


def _tailless_tree():
    """Leaves that fill the flat width exactly: no padding tail."""
    return {"w": jnp.linspace(-1.0, 1.0, 200).reshape(8, 25),
            "b": jnp.arange(56, dtype=jnp.bfloat16)}


@pytest.mark.parametrize("make_tree", [_mixed_dtype_tree, _odd_tree,
                                       _tailless_tree],
                         ids=["mixed", "odd", "tailless"])
def test_unpack_local_gradient_is_one_concatenate(make_tree):
    """The transpose of ``unpack_local`` writes the flat gradient once, one
    operand per element: no per-leaf pad summed to full width."""
    tree = make_tree()
    layout = FlatLayout.from_pytree(tree)
    assert layout.buf_dtype == jnp.float32

    def loss(t):
        return sum(jnp.sum(jnp.sin(leaf.astype(jnp.float32)) * (k + 1.5))
                   for k, leaf in enumerate(jax.tree.leaves(t)))

    def flat_loss(v):
        return loss(layout.unpack_local(v))

    vec = layout.pack_local(tree)
    text = str(jax.make_jaxpr(jax.grad(flat_loss))(vec))
    assert text.count("pad[") == 0
    assert text.count("concatenate[") == 1

    got = np.asarray(jax.jit(jax.grad(flat_loss))(vec))
    want = np.asarray(layout.pack_local(jax.jit(jax.grad(loss))(tree)))
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    np.testing.assert_array_equal(got[layout.d_real:], 0.0)


def test_layout_rejects_lossy_dtypes():
    with pytest.raises(TypeError):
        FlatLayout.from_pytree({"i": jnp.zeros(3, jnp.int32)})


# ------------------------------------------------------------- fused kernel

@pytest.mark.parametrize("w,d", [(4, 128), (16, 1000), (6, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stacked_kernel_matches_oracle(w, d, dtype):
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (w, d), dtype)
    xt = jax.random.normal(jax.random.fold_in(key, 1), (w, d), dtype)
    perm = np.arange(w)
    perm[:4] = [1, 0, 3, 2]                     # two pairs, rest idle
    partner = jnp.asarray(perm, jnp.int32)
    dt = jax.random.uniform(jax.random.fold_in(key, 2), (w,))
    kw = dict(eta=0.37, alpha=0.5, alpha_t=1.4)
    ox, ot = mixing_gossip_stacked(x, xt, partner, dt, interpret=True, **kw)
    rx, rt = mixing_gossip_stacked_ref(x, xt, partner, dt, **kw)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(ox, np.float32),
                               np.asarray(rx, np.float32), atol=atol)
    np.testing.assert_allclose(np.asarray(ot, np.float32),
                               np.asarray(rt, np.float32), atol=atol)


def test_stacked_kernel_idle_workers_untouched():
    w, d = 8, 256
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (w, d))
    xt = jax.random.normal(jax.random.fold_in(key, 1), (w, d))
    partner = jnp.asarray([1, 0] + list(range(2, w)), jnp.int32)
    dt = jnp.zeros((w,))                        # no mixing either
    ox, ot = mixing_gossip_stacked(x, xt, partner, dt, interpret=True,
                                   eta=0.5, alpha=0.5, alpha_t=0.9)
    np.testing.assert_allclose(ox[2:], x[2:], atol=1e-6)
    np.testing.assert_allclose(ot[2:], xt[2:], atol=1e-6)


def test_mixing_conserves_buffer_sum():
    """exp(dt*A) is doubly stochastic: x + x~ is invariant elementwise, for
    both the standalone mix pass and the fused batch with alpha==alpha_t==0."""
    engine = FlatGossipEngine.for_pytree(
        {"w": jnp.zeros((4, 300))}, params_from_graph(ring_graph(4), True),
        stacked=True, backend="ref")
    key = jax.random.PRNGKey(3)
    bx = jax.random.normal(key, (4, 384))
    bxt = jax.random.normal(jax.random.fold_in(key, 1), (4, 384))
    dt = jax.random.uniform(jax.random.fold_in(key, 2), (4,))
    mx, mxt = engine.mix(bx, bxt, dt)
    np.testing.assert_allclose(mx + mxt, bx + bxt, atol=1e-5)
    fx, fxt = p2p_mixing_ref(bx, bxt, bx, 1.3, eta=0.8, alpha=0.0,
                             alpha_t=0.0)
    np.testing.assert_allclose(fx + fxt, bx + bxt, atol=1e-5)


def test_p2p_batch_conserves_global_mean():
    """A coalesced p2p batch moves mass only inside pairs: the worker-mean of
    x (and of x~) is exactly preserved."""
    w, d = 8, 256
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (w, d))
    xt = jax.random.normal(jax.random.fold_in(key, 1), (w, d))
    partner = jnp.asarray([1, 0, 3, 2, 5, 4, 7, 6], jnp.int32)
    rx, rt = mixing_gossip_stacked_ref(x, xt, partner, jnp.zeros((w,)),
                                       eta=0.0, alpha=0.5, alpha_t=1.1)
    np.testing.assert_allclose(jnp.mean(rx, 0), jnp.mean(x, 0), atol=1e-6)
    np.testing.assert_allclose(jnp.mean(rt, 0), jnp.mean(xt, 0), atol=1e-6)


# -------------------------------------------------------------- coalescing

def test_coalesce_preserves_events_and_times():
    g = ring_graph(16)
    sched = make_schedule(g, rounds=80, comms_per_grad=2.0, seed=7)
    cs = coalesce_schedule(sched)
    idx = np.arange(16)
    # per-worker (time, partner) event lists are identical
    for w in range(16):
        raw = [(float(sched.event_times[r, e]),
                int(sched.partners[r, e, w]))
               for r in range(sched.rounds)
               for e in range(sched.partners.shape[1])
               if sched.event_mask[r, e] and sched.partners[r, e, w] != w]
        coal = [(float(cs.wtimes[r, b, w]), int(cs.partners[r, b, w]))
                for r in range(cs.rounds)
                for b in range(cs.partners.shape[1])
                if cs.batch_active[r, b] and cs.partners[r, b, w] != w]
        assert raw == coal
    # every batch is an involution and strictly fewer sweeps than raw slots
    for r in range(cs.rounds):
        for b in range(cs.partners.shape[1]):
            p = cs.partners[r, b]
            assert np.all(p[p] == idx)
    assert cs.num_batches() <= int(sched.event_mask.sum())
    assert cs.num_batches() < sched.rounds * sched.partners.shape[1]


def test_coalesce_merges_disjoint_events():
    """Hand-built schedule: two sequential events on disjoint pairs must
    collapse into one batch carrying each worker's own event time."""
    from repro.core.events import Schedule
    partners = np.asarray([[[1, 0, 2, 3], [0, 1, 3, 2]]], np.int32)
    times = np.asarray([[0.25, 0.75]], np.float32)
    mask = np.ones((1, 2), bool)
    grad = np.full((1, 4), 1.0, np.float32)
    cs = coalesce_schedule(Schedule(partners, times, mask, grad))
    assert cs.partners.shape[1] == 1 and bool(cs.batch_active[0, 0])
    np.testing.assert_array_equal(cs.partners[0, 0], [1, 0, 3, 2])
    np.testing.assert_allclose(cs.wtimes[0, 0], [0.25, 0.25, 0.75, 0.75])


# ------------------------------------------------- end-to-end equivalence

def _quad_grad_fn(b):
    def grad_fn(x, key, wid):
        return 0.5 * jnp.sum((x - b[wid]) ** 2), x - b[wid]
    return grad_fn


@pytest.mark.parametrize("accelerated", [False, True])
@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
def test_engine_matches_per_event_reference(accelerated, backend):
    """Same schedule through the coalesced/fused engine and the per-event
    reference path: final params, momentum buffers, and traces agree."""
    n, d = 16, 48
    rounds = 12 if backend == "pallas_interpret" else 60
    b = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    g = ring_graph(n)
    sim = Simulator(_quad_grad_fn(b), params_from_graph(g, accelerated),
                    gamma=0.05, backend=backend)
    st = sim.init(jnp.zeros(d), n, jax.random.PRNGKey(2))
    sched = make_schedule(g, rounds=rounds, comms_per_grad=1.5, seed=11)
    fin_ref, tr_ref = sim.run_schedule(st, sched, engine=False)
    fin_eng, tr_eng = sim.run_schedule(st, sched, engine=True)
    np.testing.assert_allclose(fin_eng.x, fin_ref.x, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(fin_eng.x_tilde, fin_ref.x_tilde,
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(fin_eng.t_last, fin_ref.t_last, atol=1e-6)
    np.testing.assert_allclose(tr_eng.loss, tr_ref.loss, atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(tr_eng.consensus, tr_ref.consensus,
                               atol=1e-5, rtol=1e-4)


def test_stacked_trainer_zero_comms_is_noop():
    """comms_per_step=0 must be a clean gossip no-op, not a crash."""
    from repro.launch.gossip_train import StackedGossipTrainer
    from repro.optim import sgd
    g = ring_graph(4)
    def grad_fn(p, batch):
        return (0.5 * jnp.sum((p["w"] - batch) ** 2), None), {"w": p["w"] - batch}
    tr = StackedGossipTrainer(grad_fn, sgd(momentum=0.0, weight_decay=0.0),
                              g, params_from_graph(g, True),
                              comms_per_step=0)
    state = tr.init({"w": jnp.zeros((3,))}, jax.random.PRNGKey(0))
    batch = jnp.ones((4, 3))
    state, m = jax.jit(tr.make_step())(state, batch)
    assert state.x["w"].shape == (4, 3) and jnp.isfinite(m["loss"])


def test_run_schedule_handles_f64_state():
    """float64 state (x64 mode) worked on the per-event path; the engine
    default must keep working (the layout infers an f64 buffer)."""
    with jax.enable_x64():
        n, d = 4, 8
        b = jax.random.normal(jax.random.PRNGKey(1), (n, d))

        def grad_fn(x, key, wid):
            g = x - b[wid]
            return 0.5 * jnp.sum(g ** 2), g

        g = ring_graph(n)
        sim = Simulator(grad_fn, params_from_graph(g, True), gamma=0.05)
        st = sim.init(jnp.zeros(d, jnp.float64), n, jax.random.PRNGKey(2))
        # event times are f32 schedule data regardless of x64 mode
        st = st._replace(t_last=jnp.zeros((n,), jnp.float32))
        sched = make_schedule(g, rounds=5, comms_per_grad=1.0, seed=0)
        fin, tr = sim.run_schedule(st, sched)
        assert fin.x.dtype == jnp.float64
        assert np.isfinite(float(tr.loss[-1]))


def test_layout_infers_native_dtype_for_uniform_trees():
    """A uniform-bf16 pytree must pack at bf16 (a gossip event is the unit of
    communication cost — it must not silently double its bytes)."""
    tree = {"w": jnp.zeros((4, 8), jnp.bfloat16),
            "b": jnp.zeros((3,), jnp.bfloat16)}
    layout = FlatLayout.from_pytree(tree)
    assert layout.buf_dtype == jnp.dtype(jnp.bfloat16)
    assert layout.pack_local(tree).dtype == jnp.bfloat16
    # mixed sub-f32 floats widen to f32, not further (explicit f32 leaf so
    # the assertion is mode-independent under JAX_ENABLE_X64)
    mixed = {"w": jnp.zeros((2,), jnp.bfloat16),
             "b": jnp.zeros((2,), jnp.float32)}
    assert FlatLayout.from_pytree(mixed).buf_dtype == jnp.dtype(jnp.float32)


def test_engine_tracker_identity_at_common_clock():
    """mean(x) == mean(x~) at synchronized measurement times (Eq 5) holds
    through the fused path too."""
    from repro.core import worker_mean
    n, d = 8, 8
    b = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    g = ring_graph(n)
    sched = make_schedule(g, rounds=60, comms_per_grad=1.0, seed=0,
                          jitter_grad_times=False)
    sim = Simulator(_quad_grad_fn(b), params_from_graph(g, True), gamma=0.05,
                    backend="ref")
    st = sim.init(jnp.zeros(d), n, jax.random.PRNGKey(2))
    fin, _ = sim.run_schedule(st, sched)
    np.testing.assert_allclose(worker_mean(fin.x), worker_mean(fin.x_tilde),
                               atol=1e-5)
