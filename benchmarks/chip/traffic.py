"""Traffic of a gossip-training replay: the event schedule, drawn from a seed.

One general generator reads every traffic file (``traffic/<name>.json``).
The schedule follows the paper's process (A2CiD2, arXiv:2306.08289, Def 3.1
and App E.2): in each round of unit time the graph fires a Poisson number
(mean ``comms_per_grad``) of pairwise-averaging events at uniform times, each
a maximal matching sampled by scanning the edges in random order; every
worker then takes its gradient step at a jittered time in the second half of
the round.

The number of events in each round is drawn from the traffic file's
``count_seed``, not from the run's seed, so every seed replays the same
number of gossip batches and gradient ticks per dispatch: the seed moves the
matchings, the event times, the weights and the data, not the amount of work.
"""
from __future__ import annotations

import math

import jax
import numpy as np


def prng_key(seed: int) -> jax.Array:
    """A threefry key for a seed of any size (PRNGKey keeps 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def graph_edges(topology: str, n: int) -> list[tuple[int, int]]:
    if topology != "ring":
        raise ValueError(f"unknown topology {topology!r}")
    if n == 2:
        return [(0, 1)]
    return [(min(i, (i + 1) % n), max(i, (i + 1) % n)) for i in range(n)]


def laplacian(n: int, edges, rate: float) -> np.ndarray:
    lap = np.zeros((n, n))
    for i, j in edges:
        lap[i, i] += rate
        lap[j, j] += rate
        lap[i, j] -= rate
        lap[j, i] -= rate
    return lap


def a2cid2_constants(traffic: dict) -> dict:
    """eta, alpha, alpha_tilde of Prop 3.6 for the traffic's graph, each
    worker communicating at unit rate (so each edge of a ring at 1/2)."""
    n, edges = traffic["workers"], graph_edges(traffic["topology"],
                                               traffic["workers"])
    rate = 1.0 / 2.0 if n > 2 else 1.0
    lap = laplacian(n, edges, rate)
    chi1 = 1.0 / np.linalg.eigvalsh(lap)[1]
    pinv = np.linalg.pinv(lap)
    chi2 = 0.5 * max(pinv[i, i] + pinv[j, j] - 2 * pinv[i, j]
                     for i, j in edges)
    if traffic["algorithm"] == "a2cid2":
        root = math.sqrt(chi1 * chi2)
        return {"eta": 1.0 / (2.0 * root), "alpha": 0.5,
                "alpha_tilde": 0.5 * math.sqrt(chi1 / chi2),
                "chi": root}
    if traffic["algorithm"] == "baseline":
        return {"eta": 0.0, "alpha": 0.5, "alpha_tilde": 0.5, "chi": chi1}
    raise ValueError(f"unknown algorithm {traffic['algorithm']!r}")


def schedule(traffic: dict, seed: int, rounds: int) -> dict:
    """Raw per-event schedule arrays for ``rounds`` rounds:
    partners (R, K, n) int32, event_times (R, K) f32, event_mask (R, K)
    bool, grad_times (R, n) f32, counts (R,) int."""
    n = traffic["workers"]
    edges = graph_edges(traffic["topology"], n)
    counts = np.random.default_rng(traffic["count_seed"]).poisson(
        traffic["comms_per_grad"], size=rounds)
    rng = np.random.default_rng(seed)
    kmax = max(1, int(counts.max()))
    partners = np.tile(np.arange(n, dtype=np.int32), (rounds, kmax, 1))
    event_times = np.zeros((rounds, kmax), np.float32)
    event_mask = np.zeros((rounds, kmax), bool)
    grad_times = np.zeros((rounds, n), np.float32)
    for r in range(rounds):
        k = int(counts[r])
        times = np.sort(rng.uniform(r, r + 1, size=k)).astype(np.float32)
        last = np.float32(r)
        for e in range(kmax):
            if e < k:
                used = np.zeros(n, bool)
                for idx in rng.permutation(len(edges)):
                    i, j = edges[idx]
                    if not (used[i] or used[j]):
                        used[i] = used[j] = True
                        partners[r, e, i], partners[r, e, j] = j, i
                event_times[r, e] = last = times[e]
                event_mask[r, e] = True
            else:
                event_times[r, e] = last
        jitter = (r + 0.5 + 0.5 * rng.uniform(size=n)).astype(np.float32)
        grad_times[r] = np.maximum(jitter, event_times[r].max() + 1e-4)
    return {"partners": partners, "event_times": event_times,
            "event_mask": event_mask, "grad_times": grad_times,
            "counts": counts}


def items(sched: dict) -> list[tuple]:
    """The schedule as the ordered list of replay items: ("comm", r, e)
    for each event, then ("grad", r) closing round r.  Two maximal
    matchings of one graph always share a worker, so no two events of a
    round can be applied as one batch: each item is one step of the
    engine's coalesced stream, and the reference replays the same list."""
    out = []
    for r, k in enumerate(sched["counts"]):
        out += [("comm", r, e) for e in range(int(k))]
        out.append(("grad", r))
    return out

