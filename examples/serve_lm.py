"""Quickstart: a gossip-serving fleet — 8 decode replicas on a lossy ring
that never stop averaging, surviving a mid-serve churn kill.

    PYTHONPATH=src python examples/serve_lm.py
"""
import jax

from repro.compile_cache import enable_compile_cache
from repro.configs.nano_lm import reduced
from repro.core import (Algorithm, ChannelModel, DelayProcess, PhaseSwitch,
                        ServeLoad, World, ring_graph)
from repro.launch.fleet import GossipFleet
from repro.models import Model

enable_compile_cache()
model = Model(reduced())
params = model.init(jax.random.PRNGKey(0))

world = World(
    topology=ring_graph(8),
    algorithm=Algorithm("a2cid2"),
    channel=ChannelModel(delay=DelayProcess(horizon=2, prob=0.3),
                         drop_prob=0.1),                   # stale + lossy links
    faults=(PhaseSwitch(20, active=(True,) * 7 + (False,)),),  # kill one replica
    serve=ServeLoad(rate=1.0, prompt_len=(3, 6), gen_len=(4, 10)),
)

fleet = GossipFleet(model, params, world, max_batch=4, max_len=24,
                    drift="perturb", drift_scale=0.02)
rep = fleet.run(rounds=60, seed=0)
s = rep.summary()
print(f"fleet: {s['completed']}/{s['requests_total']} requests, "
      f"{s['tokens_per_second']:.0f} tok/s, p95 latency {s['latency_p95']:.1f} "
      f"rounds, consensus distance {s['consensus_final']:.2f}")
print(f"churn recovery: replica killed at round 20 — lost {s['lost']}, "
      f"re-admitted {s['restarted']} in-flight requests to survivors")
