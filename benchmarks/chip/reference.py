"""Plain reference of a gossip-training replay, and the comparison that
decides ``correct``.

The reference imports nothing of the program.  It replays the raw per-event
schedule (``traffic.items``) one event at a time on W replicas held as
pytrees, as Algorithm 1 of the A2CiD2 paper states it:

  at a pairwise event at time t, each involved worker first mixes its two
  buffers over the time since its last event,
      c = (1 - exp(-2 eta (t - t_last))) / 2,
      x += c (x~ - x),  x~ -= c (x~ - x),
  then, with m = x_i - x_partner,  x -= alpha m,  x~ -= alpha~ m;

  at a gradient tick every worker mixes up to its gradient time and takes
  one SGD step on both buffers, x -= gamma g, x~ -= gamma g, with g the
  gradient of the configuration's ``reference_loss`` on that worker's batch
  (the simulator's key discipline: key, sub = split(key); the worker keys
  are split(sub, W); worker w's batch is drawn from fold_in(key_w, w)).

The workers' gradients are taken one worker at a time, so the reference
fits next to nothing but its own two banks.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from traffic import items as schedule_items


def _mix(x, xt, c):
    leaves, tdef = jax.tree.flatten(x)
    mixed_x, mixed_t = [], []
    for a, b in zip(leaves, tdef.flatten_up_to(xt)):
        cc = jnp.reshape(c, c.shape + (1,) * (a.ndim - 1)).astype(a.dtype)
        d = b - a
        mixed_x.append(a + cc * d)
        mixed_t.append(b - cc * d)
    return tdef.unflatten(mixed_x), tdef.unflatten(mixed_t)


@partial(jax.jit, donate_argnums=(0, 1))
def _comm(x, xt, c, partner, alpha, alpha_t):
    x, xt = _mix(x, xt, c)
    m = jax.tree.map(lambda a: a - a[partner], x)
    x = jax.tree.map(lambda a, d: a - alpha.astype(a.dtype) * d, x, m)
    xt = jax.tree.map(lambda a, d: a - alpha_t.astype(a.dtype) * d, xt, m)
    return x, xt


_TICKS: dict = {}


def _grad_tick(mod, cfg, traffic, dtype):
    """The jitted gradient tick, built once per configuration and traffic
    in a process."""
    key = (id(mod), repr(sorted(cfg.items())), repr(sorted(traffic.items())),
           jnp.dtype(dtype).name)
    if key not in _TICKS:
        _TICKS[key] = _make_tick(mod, cfg, traffic, dtype)
    return _TICKS[key]


def _make_tick(mod, cfg, traffic, dtype):
    w_n = traffic["workers"]

    @partial(jax.jit, donate_argnums=(0, 1))
    def tick(x, xt, c, key, gamma):
        x, xt = _mix(x, xt, c)
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, w_n)

        def one(args):
            xw, kw, w = args
            data = mod.example_batch(jax.random.fold_in(kw, w), cfg, traffic)
            return jax.value_and_grad(mod.reference_loss)(xw, data, cfg,
                                                          dtype)
        losses, grads = jax.lax.map(one, (x, keys, jnp.arange(w_n)))
        step = jax.tree.map(lambda g: (gamma * g).astype(g.dtype), grads)
        x = jax.tree.map(jnp.subtract, x, step)
        xt = jax.tree.map(jnp.subtract, xt, step)
        return x, xt, key, jnp.mean(losses)
    return tick


@jax.jit
def _leaf_norms(x, xt, x0):
    """(2, W, leaves) norms of each worker's change of each leaf."""
    def bank(b):
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(
                (a.astype(jnp.float32) - a0.astype(jnp.float32))
                .reshape(a.shape[0], -1)), axis=1))
            for a, a0 in zip(jax.tree.leaves(b), jax.tree.leaves(x0))],
            axis=1)
    return jnp.stack([bank(x), bank(xt)])


def replay(mod, cfg: dict, traffic: dict, consts: dict, sched: dict,
           x0: dict, key: jax.Array, marks: list[int], dtype=jnp.float32):
    """Replay the first ``max(marks)`` items of ``sched`` from ``x0`` on
    every worker.  Returns (losses of each gradient tick, {mark: (2, W,
    leaves) change norms}), each mark's state taken as the engine holds it
    after that many steps: the workers of the next item already mixed up
    to its time."""
    w_n = traffic["workers"]
    its = schedule_items(sched)
    eta = consts["eta"]
    alpha = jnp.asarray(consts["alpha"], jnp.float32)
    alpha_t = jnp.asarray(consts["alpha_tilde"], jnp.float32)
    gamma = jnp.asarray(traffic["gamma"], jnp.float32)
    tick = _grad_tick(mod, cfg, traffic, dtype)
    x = jax.tree.map(lambda a: jnp.broadcast_to(
        a.astype(dtype), (w_n,) + a.shape) + jnp.zeros((), dtype), x0)
    xt = jax.tree.map(jnp.copy, x)
    t_last = np.zeros(w_n, np.float32)
    ident = np.arange(w_n)

    def advance(item):
        """(mixing coefficients, new clocks) of the workers this item
        involves, mixed up to its time."""
        if item[0] == "comm":
            _, r, e = item
            involved = sched["partners"][r, e] != ident
            t = np.where(involved, sched["event_times"][r, e], t_last)
        else:
            t = sched["grad_times"][item[1]]
        dt = (t - t_last).astype(np.float32)
        c = 0.5 * (1.0 - np.exp(-2.0 * eta * dt.astype(np.float64)))
        return jnp.asarray(c, jnp.float32), t.astype(np.float32)

    losses, norms = [], {}
    for i in range(max(marks)):
        item = its[i]
        c, t_last = advance(item)
        if item[0] == "comm":
            _, r, e = item
            x, xt = _comm(x, xt, c, jnp.asarray(sched["partners"][r, e]),
                          alpha, alpha_t)
        else:
            x, xt, key, loss = tick(x, xt, c, key, gamma)
            losses.append(loss)
        if i + 1 in marks:
            # the engine's last step of a dispatch also mixes the next
            # step's workers up to its time; mixing is lazy, so doing it
            # here and moving their clocks changes nothing after
            c, t_last = advance(its[i + 1])
            x, xt = _mix(x, xt, c)
            norms[i + 1] = np.asarray(_leaf_norms(x, xt, x0))
    del x, xt
    return np.asarray(jax.device_get(losses), np.float64), norms


# --------------------------------------------------------------- comparing

def change_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Worst leaf's gap between the program's norm of a change and the
    reference's, against the reference's norm of that leaf or of the median
    leaf of its (bank, worker) row, whichever is larger.  Leaves the
    reference moves by under a thousandth of the median leaf (a gradient
    that is zero to rounding) count only where the program moves them."""
    med = np.median(ref, axis=-1, keepdims=True)
    still = ref < 1e-3 * med
    gap = np.abs(prog - ref) / np.maximum(ref, med)
    # such a leaf still counts if the program moves it
    moved = np.where(prog >= 1e-3 * med, prog / med, 0.0)
    return float(np.max(np.where(still, moved, gap)))


def numbers(prog_losses, prog_norms, ref_losses, ref_norms, marks):
    """The compared numbers, by name."""
    prog_losses = np.asarray(prog_losses, np.float64)
    if prog_losses.shape != ref_losses.shape or not np.all(
            np.isfinite(prog_losses)):
        loss_gap = float("inf")
    else:
        loss_gap = float(np.max(np.abs(prog_losses - ref_losses)
                                / np.abs(ref_losses)))
    first, last = min(marks), max(marks)
    nums = {"loss_gap": loss_gap,
            "step1_change": change_gap(prog_norms[first], ref_norms[first]),
            "step3_change": change_gap(prog_norms[last], ref_norms[last])}
    return nums


def verdict(nums: dict, limits: dict) -> bool:
    return all(np.isfinite(v) and v <= limits[k] for k, v in nums.items())
