"""Quickstart: A2CiD2 in 100 lines — decentralized optimization of a
heterogeneous quadratic on a ring, accelerated vs baseline; the same world
made hostile (stragglers, churn, a mid-run topology switch), described
declaratively with the World API (DESIGN.md §9); a LOSSY ring —
stale partner reads plus two Byzantine edges (DESIGN.md §10) — replayed
with and without the robust trimmed-aggregation defense; the SELF-HEALING
version of that defense (adaptive tau + edge quarantine, DESIGN.md §12)
against an attack the static trim cannot see; and a whole SWEEP of worlds
replayed as one batched scan (DESIGN.md §11).

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (AdaptiveDefense, ByzantineEdges, ChannelModel,
                        DelayProcess, PhaseSwitch, Simulator, WorkerModel,
                        World, WorldSweep, hypercube_graph,
                        params_from_graph, ring_graph, worker_mean)

enable_compile_cache()
N_WORKERS, DIM, ROUNDS = 16, 64, 300

# each worker i minimizes f_i(x) = ||x - b_i||^2 / 2; the consensus optimum
# is mean(b) — exactly the setting of the paper's theory (Sec 3.2)
b = jax.random.normal(jax.random.PRNGKey(1), (N_WORKERS, DIM))


def grad_fn(x, key, worker_id):
    noise = 0.05 * jax.random.normal(key, x.shape)
    return 0.5 * jnp.sum((x - b[worker_id]) ** 2), (x - b[worker_id]) + noise


graph = ring_graph(N_WORKERS)
print(f"ring graph: chi1={graph.chi1():.1f} chi2={graph.chi2():.2f} "
      f"(A2CiD2 accelerates chi1 -> sqrt(chi1*chi2)="
      f"{(graph.chi1()*graph.chi2())**0.5:.1f})")

calm = World(topology=graph)
for accelerated in (False, True):
    acid = params_from_graph(graph, accelerated=accelerated)
    sim = Simulator(grad_fn, acid, gamma=0.05)
    state = sim.init(jnp.zeros(DIM), N_WORKERS, jax.random.PRNGKey(2))
    state, trace = sim.run_world(state, calm, ROUNDS, seed=0)
    err = float(jnp.sum((worker_mean(state.x) - jnp.mean(b, 0)) ** 2))
    name = "A2CiD2  " if accelerated else "baseline"
    print(f"{name}: consensus distance {float(trace.consensus[-1]):.3f}  "
          f"distance to optimum {err:.2e}")

# -- the same ring made hostile, declared as a World: odd workers compute
#    gradients at 1/4 rate, two workers drop out mid-run, and the survivors
#    switch to a hypercube.  The description is serializable (to_json) and
#    compiles to ONE event schedule both replay paths consume unchanged.
print("\nheterogeneous world: stragglers + churn + ring->hypercube switch")
stragglers = np.where(np.arange(N_WORKERS) % 2 == 0, 1.0, 0.25)
active = np.ones(N_WORKERS, bool)
active[:2] = False
world = World(
    topology=graph,                                           # calm ring
    workers=WorkerModel(grad_rates=stragglers),
    faults=(PhaseSwitch(ROUNDS // 3, active=tuple(active)),   # churn window
            PhaseSwitch(2 * (ROUNDS // 3),
                        topology=hypercube_graph(4))),        # rewire+rejoin
)
hostile = world.compile(ROUNDS, seed=0)
phases = world.phase_plan(ROUNDS)
for accelerated in (False, True):
    acid = params_from_graph(graph, accelerated=accelerated)
    sim = Simulator(grad_fn, acid, gamma=0.05)
    state = sim.init(jnp.zeros(DIM), N_WORKERS, jax.random.PRNGKey(2))
    state, trace = sim.run_schedule(state, hostile)
    name = "A2CiD2  " if accelerated else "baseline"
    print(f"{name}: consensus distance {float(trace.consensus[-1]):.3f}  "
          f"(per-phase chi1: "
          f"{', '.join(f'{c1:.1f}' for c1, _ in phases.phase_chis())})")

# -- the same ring over a LOSSY channel: every partner read is a stale
#    snapshot (up to 3 rounds old, served from the engine's ring buffer),
#    2% of messages are dropped outright, and two edges are Byzantine — a
#    compromised link injecting garbage on half its exchanges.  The channel
#    is part of the declarative World; the defense (norm-trim robust
#    aggregation: reject any p2p delta with ||m|| > tau) is a replay knob.
print("\nlossy ring: stale reads + drops + 2 Byzantine edges")
lossy = World(
    topology=graph,
    channel=ChannelModel(
        delay=DelayProcess(horizon=3, prob=0.5),
        adversary=ByzantineEdges((graph.edges[0], graph.edges[8]),
                                 mode="scale", scale=1e3, prob=0.5),
        drop_prob=0.02,
    ),
)
acid = params_from_graph(graph, accelerated=True)
for robust in (False, True):
    sim = Simulator(grad_fn, acid, gamma=0.05,
                    robust_clip=5.0 if robust else None, robust_rule="trim")
    state = sim.init(jnp.zeros(DIM), N_WORKERS, jax.random.PRNGKey(2))
    state, trace = sim.run_world(state, lossy, ROUNDS, seed=0)
    tail = float(trace.consensus[-1])
    name = "A2CiD2 + trim   " if robust else "A2CiD2 no defense"
    print(f"{name}: consensus distance "
          f"{'DIVERGED' if not np.isfinite(tail) else f'{tail:.3f}'}")

# -- self-healing gossip (DESIGN.md §12): a sign-flip adversary corrupts
#    exchanges at HONEST scale, so the static tau above never fires — the
#    trimmed replay is bitwise the undefended one.  Declaring a defense on
#    the World closes the loop inside the compiled scan: an EMA quantile
#    of admitted delta norms tightens tau to the honest noise floor, and
#    per-edge trust quarantines (then heals) edges that keep violating it.
print("\nself-healing: sign-flip attack at honest scale, adaptive tau")
# shared target, scaled so a flipped exchange has norm ~2||x|| ~ 3 < tau=5
# — under the static threshold's radar, well above the honest noise floor
b_shared = 0.2 * b[0]
flippy = ChannelModel(adversary=ByzantineEdges(
    (graph.edges[0], graph.edges[8]), mode="sign_flip", prob=1.0))


def shared_grad(x, key, worker_id):
    del worker_id
    return (0.5 * jnp.sum((x - b_shared) ** 2),
            (x - b_shared) + 0.05 * jax.random.normal(key, x.shape))


for label, defense in (("static trim    ", None),
                       ("adaptive defense", AdaptiveDefense())):
    world = World(topology=graph, channel=flippy, defense=defense)
    sim = Simulator(shared_grad, acid, gamma=0.05,
                    robust_clip=5.0, robust_rule="trim")
    state = sim.init(jnp.zeros(DIM), N_WORKERS, jax.random.PRNGKey(2))
    state, trace = sim.run_world(state, world, ROUNDS, seed=0)
    rej = float(jnp.sum(trace.defense.rejections)) if trace.defense else 0.0
    print(f"{label}: consensus distance {float(trace.consensus[-1]):.4f}  "
          f"(rejected exchanges: {rej:.0f})")

# -- many worlds at once: the paper's claims are sweep-shaped, so sweeps
#    are first-class.  A WorldSweep names a grid declaratively; run_worlds
#    replays the WHOLE grid (x 2 seeds here) in ONE compiled scan — one
#    jit trace, one dispatch — with each world's trace row bit-identical
#    to its serial replay (DESIGN.md §11).
print("\nbatched sweep: comms_per_grad grid x 2 seeds, one compiled scan")
sweep = WorldSweep.over(World(topology=graph), seeds=(0, 1),
                        comms_per_grad=[0.5, 1.0, 2.0])
sim = Simulator(grad_fn, params_from_graph(graph, accelerated=True),
                gamma=0.05)
states = [sim.init(jnp.zeros(DIM), N_WORKERS, jax.random.PRNGKey(2))
          for _ in range(sweep.size)]
_, traces = sim.run_worlds(states, sweep.compile(ROUNDS))
for i, (w, s) in enumerate(sweep.points()):
    print(f"comms/grad={w.comms_per_grad:<4} seed={s}: "
          f"consensus distance {float(traces.consensus[i, -1]):.3f}")
