"""The paper's own experiment family at CPU scale: ResNet on CIFAR-like data
with asynchronous decentralized workers (paper Sec 4, Tab 4).

    PYTHONPATH=src python examples/cifar_decentralized.py --rounds 60
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import Algorithm, Simulator, World, build_graph, worker_mean
from repro.data import SyntheticCIFAR
from repro.models.resnet import init_resnet, resnet8_cifar, resnet_loss


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=25)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--graph", default="ring")
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = resnet8_cifar()
    stream = SyntheticCIFAR(batch_size=args.batch_size, noise=0.5)

    def grad_fn(params, key, wid):
        batch = stream.sample(jax.random.fold_in(key, wid))
        def loss_fn(p):
            loss, _ = resnet_loss(p, cfg, batch)
            return loss
        return jax.value_and_grad(loss_fn)(params)

    graph = build_graph(args.graph, args.workers)
    # both arms are coupled-clock algorithms, so they compile the identical
    # schedule — declare the worlds and reuse one compile
    arms = {"adpsgd": World(topology=graph, algorithm=Algorithm("adpsgd")),
            "a2cid2": World(topology=graph, algorithm=Algorithm("a2cid2"))}
    sched = arms["a2cid2"].compile(args.rounds, seed=args.seed)
    params0 = init_resnet(jax.random.PRNGKey(0), cfg)

    for kind, world in arms.items():
        accel = kind == "a2cid2"
        sim = Simulator(grad_fn, world.algorithm_params(), gamma=0.05)
        state = sim.init(params0, args.workers, jax.random.PRNGKey(1))
        t0 = time.time()
        state, trace = sim.run_schedule(state, sched)
        # evaluate the consensus model
        params = worker_mean(state.x)
        test = stream.sample(jax.random.PRNGKey(123))
        _, metrics = resnet_loss(params, cfg, test)
        tag = "A2CiD2  " if accel else "baseline"
        print(f"{tag} ({args.graph}): loss {float(trace.loss[0]):.3f} -> "
              f"{float(jnp.mean(trace.loss[-5:])):.3f}  "
              f"test acc {float(metrics['acc']):.2f}  ({time.time()-t0:.0f}s)")


if __name__ == "__main__":
    main()
