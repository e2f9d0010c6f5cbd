"""Bring-up smoke run: decentralized nano-LM training on one TPU chip.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the sharded replay only

With no option it drives the main path through its normal entry points:

  1. each of the six A2CiD2 gossip kernels (Pallas, compiled for the chip)
     at nano-LM's flat width, against its jnp reference;
  2. nano-LM at its own widths (d_model 768, 12 layers, 12/4 GQA heads,
     vocab 32,000; 128,404,224 f32 parameters) trained by 4 asynchronous
     gossip workers on a ring with A2CiD2 — ``Simulator.run_schedule`` on
     the ``auto`` backend, as ``examples/lm_decentralized.py`` builds it —
     then the same schedule on the Pallas and ``ref`` backends at f32
     matmul precision, and the two compared.

With ``--chips 4`` it runs only the sharded replay
(``Simulator.run_worlds(..., mesh=MeshReplay(...))``): a ring of 4
workers, one per chip, at the same width on the quadratic objective of
``benchmarks/run.py``'s scale bench, compared with the same replay on
one chip.

Earlier lines print bring-up observations (device, sizes, compile and
round seconds); they are not a benchmark.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero and prints no result; any failed
check exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

WORKERS = 4
# relative f32 agreement: kernel vs reference (same arithmetic, fused
# differently), and whole replays at f32 matmul precision (ulp
# differences carried through a few rounds of training)
KERNEL_RTOL = 1e-6
REPLAY_RTOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def rel_err(a, b) -> float:
    """max |a - b| / max |b| over two host arrays."""
    return float(np.max(np.abs(a - b))) / max(float(np.max(np.abs(b))),
                                              1e-30)


# ----------------------------------------------------------- the workload

def nano_lm_training(rounds: int, batch: int, seq: int, seed: int,
                     backend: str):
    """(init, simulator, schedule) of the nano-LM gossip run, built as
    ``examples/lm_decentralized.py`` builds it, with one difference: each
    replica is handed to the simulator as its flat (D,) vector
    (``FlatLayout.pack_local``), unpacked inside the gradient.  The
    replay's argument is then the engine's (W, D) bank itself.  As a
    pytree the argument and the bank are two copies of the state, 4.1 GB
    more at W = 4, and the ``ref`` replay no longer fits 16 GB.

    ``init(key)`` draws one replica's flat start."""
    from repro.configs import get_config
    from repro.core import Algorithm, Simulator, World, ring_graph
    from repro.core.flatbuf import FlatLayout
    from repro.data import LMTaskStream
    from repro.models import Model

    cfg = get_config("nano-lm")
    model = Model(cfg)
    layout = FlatLayout.from_pytree(jax.eval_shape(model.init,
                                                   jax.random.PRNGKey(0)))
    stream = LMTaskStream(vocab_size=cfg.vocab_size, seq_len=seq,
                          batch_size=batch, concentration=0.15)

    def grad_fn(vec, key, wid):
        data = stream.sample(jax.random.fold_in(key, wid))

        def loss_fn(v):
            # rematerialize each block: the saved activations of four
            # vmapped workers alone would not fit the chip's 16 GB
            loss, _ = model.loss(layout.unpack_local(v), data, remat=True)
            return loss
        return jax.value_and_grad(loss_fn)(vec)

    world = World(topology=ring_graph(WORKERS),
                  algorithm=Algorithm("a2cid2"))
    sched = world.compile(rounds, seed=seed)
    # donated: the replay updates the 4.1 GB of banks in place
    sim = Simulator(grad_fn, world.algorithm_params(), gamma=0.05,
                    backend=backend, donate=True)
    init = jax.jit(lambda key: layout.pack_local(model.init(key)))
    return init, sim, sched


def rbg_key(key: jax.Array) -> jax.Array:
    """An "rbg" key from a raw threefry key.  Uniform draws from it
    compile in about a second at nano-LM width, where a threefry normal
    of (4, D) takes the TPU compiler 15 s."""
    return jax.random.wrap_key_data(jnp.tile(key, 2), impl="rbg")


def quad_replay(d: int, seed: int):
    """(simulator, schedule) of the scale bench's quadratic objective at
    width ``d``: worker w pulls towards its own target b_w, with gradient
    noise.  Targets and noise are uniform threefry draws made on the
    device (from the worker id and the step key), so no (W, d) target
    array is a compile-time constant.  Threefry, because its draws are the
    same however the worker axis is split over chips ("rbg" draws are
    not); uniform, because threefry normals compile slowly at this
    width."""
    from repro.core import Simulator, World, params_from_graph, ring_graph

    def grad_fn(x, key, wid):
        tkey = jax.random.fold_in(jax.random.PRNGKey(seed), wid)
        b = jax.random.uniform(tkey, x.shape, x.dtype, -1.0, 1.0)
        noise = jax.random.uniform(key, x.shape, x.dtype, -1.0, 1.0)
        return 0.5 * jnp.sum((x - b) ** 2), (x - b) + 0.05 * noise

    g = ring_graph(WORKERS)
    sim = Simulator(grad_fn, params_from_graph(g, True), gamma=0.05,
                    donate=True)
    sched = World(topology=g).compile(3, seed=seed)
    return sim, sched


# ------------------------------------------------------------- phases

def kernel_checks(d: int) -> dict:
    """{kernel name: jitted seed -> [(max |out - ref|, max |ref|)]} for
    each gossip kernel at width ``d``, inputs drawn on the device."""
    from repro.core import params_from_graph, ring_graph
    from repro.kernels.a2cid2_mixing import kernel as K
    from repro.kernels.a2cid2_mixing import ref as R

    p = params_from_graph(ring_graph(WORKERS), True)
    kw = dict(eta=p.eta, alpha=p.alpha, alpha_t=p.alpha_tilde)
    f32 = jnp.float32
    # worlds: one accelerated world and one baseline (eta 0) world
    dyn = (jnp.asarray([p.eta, 0.0], f32), jnp.asarray([p.alpha] * 2, f32),
           jnp.asarray([p.alpha_tilde, p.alpha], f32))
    pw = jnp.asarray([[1, 0], [0, 1]], jnp.int32)
    ps = jnp.asarray([1, 0, 3, 2], jnp.int32)
    corrupt = jnp.asarray([0.0, -2.0, 0.0, 0.5], f32)
    mscale = jnp.asarray([1.0, 1.0, 0.0, 0.5], f32)

    def draw(key, n, shape):
        return [jax.random.uniform(k, shape, f32, -1.0, 1.0)
                for k in jax.random.split(key, n)]

    def flat(fn, rfn):
        def case(key):
            x, xt, xp = draw(key, 3, (d,))
            return fn(x, xt, xp, f32(0.7), **kw), rfn(x, xt, xp, 0.7, **kw)
        return case

    def stacked(key):
        x, xt = draw(key, 2, (WORKERS, d))
        dt = jax.random.uniform(key, (WORKERS,))
        return (K.mixing_gossip_stacked(x, xt, ps, dt, **kw),
                R.mixing_gossip_stacked_ref(x, xt, ps, dt, **kw))

    def channel_stacked(key):
        x, xt = draw(key, 2, (WORKERS, d))
        xp = R.take_rows(x, ps)
        dt = jax.random.uniform(key, (WORKERS,))
        args = (x, xt, xp, corrupt, mscale, dt)
        return (K.channel_gossip_stacked(*args, clip=1.0, **kw),
                R.channel_gossip_stacked_ref(*args, clip=1.0, **kw))

    def worlds(key):
        x, xt = draw(key, 2, (2, 2, d))
        dt = jax.random.uniform(key, (2, 2))
        return (K.mixing_gossip_worlds(x, xt, pw, dt, *dyn),
                R.mixing_gossip_worlds_ref(x, xt, pw, dt, *dyn))

    def channel_worlds(key):
        x, xt = draw(key, 2, (2, 2, d))
        xp = R.take_partner_rows(x, pw)
        dt = jax.random.uniform(key, (2, 2))
        c2, m2 = corrupt.reshape(2, 2), mscale.reshape(2, 2)
        return (K.channel_gossip_worlds(x, xt, xp, c2, m2, dt, *dyn,
                                        want_rej=True),
                R.channel_gossip_worlds_ref(x, xt, xp, c2, m2, dt, *dyn,
                                            want_rej=True))

    cases = {
        "mixing_p2p": flat(K.mixing_p2p, R.mixing_p2p_ref),
        "p2p_mixing": flat(K.p2p_mixing, R.p2p_mixing_ref),
        "mixing_gossip_stacked": stacked,
        "channel_gossip_stacked": channel_stacked,
        "mixing_gossip_worlds": worlds,
        "channel_gossip_worlds": channel_worlds,
    }

    def errs(case):
        def fn(seed):
            outs, refs = case(rbg_key(jax.random.PRNGKey(seed)))
            return [(jnp.max(jnp.abs(o - r)), jnp.max(jnp.abs(r)))
                    for o, r in zip(outs, refs)]
        return jax.jit(fn)
    return {name: errs(case) for name, case in cases.items()}


def kernel_phase(d: int, seed: int) -> None:
    """Each gossip kernel at width ``d`` against its reference."""
    for i, (name, fn) in enumerate(kernel_checks(d).items()):
        arg = jnp.int32(seed + i)
        compiled = fn.lower(arg).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no Pallas kernel in the compiled program")
        worst = max(float(e) / max(float(s), 1e-30)
                    for e, s in compiled(arg))
        log(f"kernel {name}: d={d} max rel err vs ref {worst:.3e}")
        check(worst <= KERNEL_RTOL, f"{name} disagrees with its reference "
              f"({worst:.3e} > {KERNEL_RTOL})")


def training_phase(rounds: int, batch: int, seq: int, seed: int) -> None:
    """nano-LM gossip training on the Pallas path as users run it, then
    the Pallas and ``ref`` replays compared at f32 matmul precision.

    At the TPU's default precision an f32 matmul rounds its inputs to
    bf16, so a 1-ulp difference in a parameter (from XLA fusing the code
    around the two kernels differently) can flip a rounding and grow
    through the next gradient: on a v5e the two drift 1.3e-4 apart in 3
    rounds with bitwise-equal kernels.  With f32-accurate
    matmuls the two replays must agree to f32 tolerance."""
    from repro.kernels.a2cid2_mixing.ops import resolve_backend

    check(resolve_backend("auto") == "pallas",
          f"backend 'auto' resolves to {resolve_backend('auto')!r}")

    runs = {b: nano_lm_training(rounds, batch, seq, seed, b)
            for b in ("auto", "ref")}

    def start(backend):
        init, sim, _ = runs[backend]
        return sim.init(init(jax.random.PRNGKey(seed)), WORKERS,
                        jax.random.PRNGKey(seed + 1))

    def replay(backend):
        _, sim, sched = runs[backend]
        state = start(backend)
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        state, trace = sim.run_schedule(state, sched)
        jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        loss = np.asarray(trace.loss)
        check(bool(np.all(np.isfinite(loss))), f"[{backend}] loss not "
              f"finite: {loss.tolist()}")
        # on the host, so the next replay has the chip's memory
        return jax.device_get(state.x), jax.device_get(state.x_tilde), \
            loss, dt

    _, sim, sched = runs["auto"]
    state = start("auto")
    log(f"nano-lm: {state.x.shape[1]} params per replica, W={WORKERS}, "
        f"batch {batch}x{seq} per worker, {rounds} rounds")
    fn, args = sim.schedule_executable(state, sched)
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    log(f"replay compile: {time.perf_counter() - t0:.2f} s")
    check("tpu_custom_call" in compiled.as_text(),
          "the compiled replay holds no Pallas kernel")
    mem = compiled.memory_analysis()
    log(f"replay memory: args {mem.argument_size_in_bytes} B, "
        f"temp {mem.temp_size_in_bytes} B, "
        f"aliased {mem.alias_size_in_bytes} B")
    del compiled, args, state
    *_, loss, first = replay("auto")
    log(f"[pallas] first replay: {first:.2f} s; loss {loss.tolist()}")
    *_, warm = replay("auto")
    log(f"[pallas] warm replay: {warm:.3f} s, {warm / rounds:.3f} s "
        f"per round")

    with jax.default_matmul_precision("highest"):
        px, pxt, pl, t_p = replay("auto")
        rx, rxt, rl, t_r = replay("ref")
    worst = max(rel_err(px, rx), rel_err(pxt, rxt))
    log(f"f32 matmuls: pallas replay {t_p:.2f} s, ref replay {t_r:.2f} s "
        f"(compile included); max rel err {worst:.3e}; "
        f"loss {pl.tolist()} vs {rl.tolist()}")
    check(worst <= REPLAY_RTOL, f"Pallas replay disagrees with ref "
          f"({worst:.3e} > {REPLAY_RTOL})")
    check(np.allclose(pl, rl, rtol=REPLAY_RTOL), "losses disagree")


def sharded_phase(d: int, seed: int) -> None:
    """The worker axis over four chips vs the same replay on one."""
    from repro.core import Simulator
    from repro.launch.mesh import make_replay_mesh
    from repro.launch.mesh_replay import MeshReplay

    sim, sched = quad_replay(d, seed)
    mr = MeshReplay(make_replay_mesh(WORKERS), lag=0)

    def fresh():
        # one world, batched here so that only the (1, W, d) banks stay
        state = sim.init(jnp.zeros((d,), jnp.float32), WORKERS,
                         jax.random.PRNGKey(seed + 2))
        return Simulator.batch_states([state])

    finals = {}
    for name, mesh in (("one chip", None), ("4 chips", mr)):
        t0 = time.perf_counter()
        fin, trace = sim.run_worlds(fresh(), [sched], mesh=mesh)
        jax.block_until_ready(fin)
        log(f"[{name}] replay (compile + run): "
            f"{time.perf_counter() - t0:.2f} s; consensus "
            f"{np.asarray(trace.consensus).tolist()}")
        if mesh is not None:
            for leaf in (fin.x, fin.x_tilde):
                devs = {s.device for s in leaf.addressable_shards}
                check(len(devs) == WORKERS, f"a bank sits on {len(devs)} "
                      f"device(s), not {WORKERS}")
        # on the host, so the next replay has the chip's memory
        finals[name] = (jax.device_get(fin.x), jax.device_get(fin.x_tilde))
        del fin, trace
    worst = max(rel_err(a, b)
                for a, b in zip(finals["4 chips"], finals["one chip"]))
    log(f"4-chip vs 1-chip replay at d={d}: max rel err {worst:.3e}")
    check(worst <= REPLAY_RTOL, f"sharded replay disagrees ({worst:.3e})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform is "
                         f"{dev.platform!r}); this run needs a TPU chip")
    check(len(devices) >= args.chips,
          f"{args.chips} chips asked for, {len(devices)} found")

    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config
    from repro.core.flatbuf import FlatLayout
    from repro.models import Model

    cache = enable_compile_cache()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache}")
    shapes = jax.eval_shape(Model(get_config("nano-lm")).init,
                            jax.random.PRNGKey(0))
    d = FlatLayout.from_pytree(shapes).d
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(d, args.seed)
    else:
        kernel_phase(d, args.seed)
        training_phase(args.rounds, args.batch, args.seq, args.seed)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s; "
        f"peak device memory {peak} B")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
