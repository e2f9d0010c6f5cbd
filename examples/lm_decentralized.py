"""End-to-end driver: decentralized training of the ~100M-class nano-lm with
8 asynchronous gossip workers for a few hundred rounds, comparing the
asynchronous baseline against A2CiD2 on the ring graph.

Reduced-scale by default so it runs on CPU in a few minutes; pass --full for
the ~100M configuration and more rounds.

    PYTHONPATH=src python examples/lm_decentralized.py --rounds 200
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core import Algorithm, Simulator, World, ring_graph
from repro.data import LMTaskStream
from repro.models import Model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config("nano-lm", reduced=not args.full)
    model = Model(cfg)
    stream = LMTaskStream(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          batch_size=args.batch_size, concentration=0.15)

    def grad_fn(params, key, wid):
        batch = stream.sample(jax.random.fold_in(key, wid))
        def loss_fn(p):
            loss, _ = model.loss(p, batch)
            return loss
        return jax.value_and_grad(loss_fn)(params)

    graph = ring_graph(args.workers)
    # coupled-clock algorithms compile the identical schedule; declare the
    # zoo arms as Worlds and reuse one compile
    arms = {"adpsgd": World(topology=graph, algorithm=Algorithm("adpsgd")),
            "a2cid2": World(topology=graph, algorithm=Algorithm("a2cid2"))}
    sched = arms["a2cid2"].compile(args.rounds, seed=args.seed)
    params0 = model.init(jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree.leaves(params0))
    print(f"nano-lm: {n_params/1e6:.1f}M params, {args.workers} workers, "
          f"ring graph, bayes CE {stream.bayes_ce():.3f}")

    for kind, world in arms.items():
        accel = kind == "a2cid2"
        sim = Simulator(grad_fn, world.algorithm_params(), gamma=0.05)
        state = sim.init(params0, args.workers, jax.random.PRNGKey(1))
        t0 = time.time()
        state, trace = sim.run_schedule(state, sched)
        tag = "A2CiD2  " if accel else "baseline"
        print(f"{tag}: loss {float(trace.loss[0]):.3f} -> "
              f"{float(jnp.mean(trace.loss[-10:])):.3f}   "
              f"consensus {float(jnp.mean(trace.consensus[-10:])):.2e}   "
              f"({time.time()-t0:.0f}s)")


if __name__ == "__main__":
    main()
