"""Discrete-event simulator of Algorithm 1 — the faithful reproduction.

Simulates n asynchronous workers on one host: every leaf of the worker state
carries a leading worker axis ``(n, ...)``; gradient computations are vmapped
and the Poisson event schedule (events.Schedule) is replayed exactly:

  for each comm event e (time u_e, matching P_e):
      involved workers apply the lazy mixing exp((u_e - t_last) A)   [Algo 1 l.17]
      then the p2p update  x -= alpha*m, x~ -= alpha_t*m             [l.18-19]
  at each worker's gradient time t_g:
      lazy mixing exp((t_g - t_last) A)                              [l.9]
      gradient step on BOTH buffers                                  [Eq 4]

With eta = 0, alpha = alpha_t = 1/2 this is exactly the asynchronous baseline
(Eq 6, ~AD-PSGD).  The simulator is jit'd end-to-end with lax.scan.

Two replay paths exist:

  * ``run`` — the per-event reference: one unfused (mix, p2p) pytree sweep
    per schedule slot, masked slots included.  Kept as the equivalence
    oracle and the benchmark baseline.
  * ``run_coalesced`` — the flat-buffer event engine (default in
    ``run_schedule``): the schedule is compiled to coalesced batches
    (events.coalesce_schedule) and each batch is ONE fused sweep of a
    packed (n, D) state buffer (engine.FlatGossipEngine; Pallas on TPU).
    Same dynamic, ~kmax/E_active fewer sweeps and 2x less traffic per sweep.

Both paths have unreliable-channel twins (DESIGN.md §10) that
``run_schedule`` dispatches to when the schedule carries ``stale``/
``corrupt`` extras or robust aggregation is on: they thread a ring buffer
of the last H flat states through the scan (stale partner reads), apply
per-event corruption multipliers, and optionally trim/clip the p2p delta
(``robust_clip``/``robust_rule``).  Channel-free schedules run the
original paths bit-for-bit.

All three flavors (plain reference, coalesced engine, channel) also exist
WORLD-BATCHED (DESIGN.md §11): ``run_worlds`` replays B independent
worlds in ONE compiled ``lax.scan`` over (B, W, D) buffers / (B, H, W, D)
snapshot rings, with per-world A2CiD2 dynamics as (B,) arrays so an
entire sweep family — baseline and accelerated, every grid point, every
seed — is one trace and one device dispatch.  Batched replay is pinned
equal to the serial per-world replay (tests/test_batched_replay.py).

``Simulator(donate=True)`` opts the scan jits into buffer donation
(``donate_argnums`` on the state), letting XLA reuse the input state's
memory for the scan carries instead of round-tripping through fresh
allocations.  Donation consumes the passed state — callers must thread
the returned one — so it is opt-in; the default keeps states reusable
(the equivalence suites replay one state down several paths).
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .a2cid2 import (A2CiD2Params, apply_mixing, consensus_distance,
                     matched_p2p_update, worker_mean)
from .channel import CORRUPT_KEY, STALE_KEY
from .defense import (DefenseTrace, defense_absorb, defense_comm,
                      defense_grad, defense_init, knobs_single, knobs_worlds)
from .engine import FlatGossipEngine
from .events import Schedule, coalesce_schedule
from .flatbuf import FlatLayout
from .telemetry import (Telemetry, batch_schedule_columns, finalize_trace,
                        row_bytes_of, schedule_columns)


def _jit_pair(impl, *, static=(0,), donate=(1,)):
    """(plain, donating) jit twins of one scan impl: the donating variant
    hands the state argument's buffers to XLA (``donate_argnums``) so the
    scan carries alias them in place; the plain one leaves inputs alive."""
    return (partial(jax.jit, static_argnums=static)(impl),
            partial(jax.jit, static_argnums=static,
                    donate_argnums=donate)(impl))

PyTree = Any
# grad_fn(params_i, key, worker_id) -> (loss_i, grads_i) for ONE worker;
# vmapped inside.  worker_id lets each worker sample its own data stream
# (paper Sec 4.1: every worker sees the whole dataset with its own shuffle).
GradFn = Callable[[PyTree, jax.Array, jax.Array], tuple[jax.Array, PyTree]]


class SimState(NamedTuple):
    x: PyTree          # leaves (n, ...)
    x_tilde: PyTree    # leaves (n, ...)
    t_last: jax.Array  # (n,) last per-worker event time (for lazy mixing)
    key: jax.Array


class SimTrace(NamedTuple):
    loss: jax.Array               # (rounds,) mean worker loss
    consensus: jax.Array          # (rounds,) ||pi x||^2 / n
    mean_param_norm: jax.Array    # (rounds,)
    # control-loop trace (defense.DefenseTrace) on the self-healing
    # replays, None elsewhere — a defaulted tail field so every existing
    # 3-tuple construction/unpacking site stays valid
    defense: Any = None
    # flight-recorder columns (telemetry.TelemetryTrace) when a Telemetry
    # spec was passed, None elsewhere — same defaulted-tail mechanism.
    # Inside the jitted impls this briefly holds the raw in-scan runtime
    # tuple; the public entry points replace it with the finalized trace.
    telemetry: Any = None


@dataclasses.dataclass(frozen=True)
class Simulator:
    grad_fn: GradFn
    params: A2CiD2Params
    gamma: float
    backend: str = "auto"  # engine kernel backend: auto | ref | pallas[_interpret]
    # robust aggregation (DESIGN.md §10): the replay-side defense knob
    # against Byzantine channel worlds.  None = plain m-term; with a
    # threshold tau = robust_clip, robust_rule selects 'trim' (reject the
    # delta when ||m|| > tau — garbage rejection), 'clip' (rescale to
    # norm tau, ClippedGossip-style), or 'coord' (per-coordinate clip).
    robust_clip: float | None = None
    robust_rule: str = "trim"
    # opt-in buffer donation for every scan jit (see module docstring):
    # the replay consumes the passed state, so callers must thread the
    # returned one instead of reusing the input
    donate: bool = False

    def __post_init__(self):
        if self.robust_rule not in ("trim", "clip", "coord"):
            raise ValueError("robust_rule must be 'trim', 'clip', or "
                             f"'coord', got {self.robust_rule!r}")

    def init(self, x0: PyTree, n: int, key: jax.Array) -> SimState:
        """All workers start at consensus (paper: one all-reduce before training)."""
        stack = jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), x0)
        # donation hands each argument buffer to XLA exactly once, so the
        # two state buffers must not alias (f(donate(a), donate(a)) is an
        # error); without donation they can share until first divergence
        x_tilde = jax.tree.map(jnp.copy, stack) if self.donate else stack
        return SimState(x=stack, x_tilde=x_tilde, t_last=jnp.zeros((n,)),
                        key=key)

    # ----------------------------------------------- telemetry accumulation
    # (DESIGN.md §15) When a Telemetry spec is active, the channel/defense
    # flavors thread a tiny f32 accumulator — (applied, rejected,
    # norm_sum, norm_sq_sum), scalars serially / (B,) world-batched —
    # through their comm steps and emit + reset it at every gradient
    # tick, exactly the DefenseTrace mechanism.  The spec is a STATIC jit
    # argument, so ``tel=None`` traces contain none of this machinery:
    # the None jaxpr is the pre-telemetry jaxpr, bit for bit.

    @staticmethod
    def _tel_zeros(shape=()):
        z = jnp.zeros(shape, jnp.float32)
        return (z, z, z, z)

    def _tel_rej(self, nrm, tau=None):
        """Rejected-read mask under the replay's robust rule.  Only the
        trim rule REJECTS a read; 'clip'/'coord' attenuate but still
        apply it.  ``tau`` (traced scalar or (B,) array) overrides the
        static threshold — the lifted ``robust_clips`` axis; tau = inf
        rejects nothing, matching its bitwise-plain degeneration."""
        tval = tau if tau is not None else self.robust_clip
        if tval is None or self.robust_rule != "trim":
            return jnp.zeros_like(nrm)
        t = jnp.asarray(tval, jnp.float32)
        t = jnp.reshape(t, t.shape + (1,) * (nrm.ndim - t.ndim))
        return (nrm > t).astype(jnp.float32)

    @staticmethod
    def _tel_step(acc, involved, rej, nrm, batched: bool = False):
        """Fold one comm step into the accumulator.  ``involved`` is the
        directed-read mask ((n,) or (B, n)), ``rej`` the rejected subset,
        ``nrm`` the per-read channel-delta norms (the moments are taken
        over ADMITTED reads only — rejected garbage would swamp them)."""
        a_cnt, r_cnt, s1, s2 = acc
        inv = involved.astype(jnp.float32)
        rj = jnp.asarray(rej, jnp.float32) * inv
        adm = inv - rj
        ax = 1 if batched else 0
        a_cnt = a_cnt + adm.sum(axis=ax)
        r_cnt = r_cnt + rj.sum(axis=ax)
        nf = nrm.astype(jnp.float32)
        s1 = s1 + (nf * adm).sum(axis=ax)
        s2 = s2 + (nf * nf * adm).sum(axis=ax)
        return (a_cnt, r_cnt, s1, s2)

    def _row_bytes(self, state: SimState, worlds: bool = False) -> int:
        """Flat-row transfer size for the bytes-moved column.  Falls back
        to summing leaf widths when no exact buffer dtype exists (the
        same pytrees that reject the engine path)."""
        try:
            return row_bytes_of(FlatLayout.from_pytree(
                state.x, stacked=True, worlds=worlds))
        except TypeError:
            lead = 2 if worlds else 1
            return sum(int(np.prod(leaf.shape[lead:], dtype=np.int64))
                       * int(np.dtype(leaf.dtype).itemsize)
                       for leaf in jax.tree.leaves(state.x))

    # ------------------------------------------------------------- one round
    def _comm_event(self, carry, event):
        x, x_tilde, t_last = carry
        partner, time, mask = event
        involved = (partner != jnp.arange(partner.shape[0])) & mask
        # lazy mixing for involved workers only (their clocks advance)
        dt = jnp.where(involved, time - t_last, 0.0)
        x, x_tilde = apply_mixing(x, x_tilde, self.params.eta, dt)
        t_last = jnp.where(involved, time, t_last)
        # p2p update; idle workers have partner=i => m=0 no-op. Masked events
        # have partner=identity by construction.
        x, x_tilde = matched_p2p_update(x, x_tilde, partner, self.params)
        return (x, x_tilde, t_last), None

    def _round(self, state: SimState, round_sched) -> tuple[SimState, dict]:
        partners, times, mask, grad_times, grad_scale, alive = round_sched
        carry = (state.x, state.x_tilde, state.t_last)
        carry, _ = jax.lax.scan(self._comm_event, carry, (partners, times, mask))
        x, x_tilde, t_last = carry

        # gradient event per worker at its own clock; detached (not-alive)
        # workers neither advance their clock nor mix, stragglers (alive but
        # grad_scale 0) advance and mix but skip the gradient
        dt = jnp.where(alive, grad_times - t_last, 0.0)
        x, x_tilde = apply_mixing(x, x_tilde, self.params.eta, dt)
        n = grad_times.shape[0]
        key, sub = jax.random.split(state.key)
        keys = jax.random.split(sub, n)
        losses, grads = jax.vmap(self.grad_fn)(x, keys, jnp.arange(n))

        def upd(p, g):
            s = jnp.reshape(grad_scale, grad_scale.shape
                            + (1,) * (g.ndim - 1)).astype(g.dtype)
            return p - self.gamma * (s * g)

        x = jax.tree.map(upd, x, grads)
        x_tilde = jax.tree.map(upd, x_tilde, grads)

        new_state = SimState(x, x_tilde,
                             jnp.where(alive, grad_times, t_last), key)
        metrics = {
            "loss": jnp.mean(losses),
            "consensus": consensus_distance(x),
            "mean_param_norm": sum(jnp.sum(m ** 2) for m in
                                   jax.tree.leaves(worker_mean(x))),
        }
        return new_state, metrics

    # ------------------------------------------ coalesced flat-buffer steps
    def _engine_step(self, engine: FlatGossipEngine, n: int, carry, xs):
        """One event-stream step: a fused comm batch OR a gradient tick,
        each followed by the precomputed mixing segment to the next step."""
        partner, dt_nxt, is_grad, gscale = xs

        def comm(args):
            bx, bxt, key = args
            bx, bxt = engine.batch(bx, bxt, partner, dt_nxt)
            z = jnp.zeros((), jnp.float32)
            return (bx, bxt, key), (z, z, z)

        def grad(args):
            bx, bxt, key = args
            key, sub = jax.random.split(key)
            keys = jax.random.split(sub, n)
            losses, grads = jax.vmap(self.grad_fn)(engine.unpack(bx), keys,
                                                   jnp.arange(n))
            g = engine.pack(grads)
            # grad_scale masks straggler/churned ticks (1.0 elsewhere)
            g = gscale[:, None].astype(g.dtype) * g
            bx = bx - self.gamma * g
            bxt = bxt - self.gamma * g
            mean = jnp.mean(bx, axis=0, keepdims=True)
            # padding columns are zero across workers: they add 0 to both
            loss = jnp.mean(losses).astype(jnp.float32)
            consensus = (jnp.sum((bx - mean) ** 2) / n).astype(jnp.float32)
            mean_norm = jnp.sum(mean ** 2).astype(jnp.float32)
            bx, bxt = engine.mix(bx, bxt, dt_nxt)
            return (bx, bxt, key), (loss, consensus, mean_norm)

        return jax.lax.cond(is_grad, grad, comm, carry)

    # ----------------------------------------- unreliable-channel replays
    # (DESIGN.md §10) Channel worlds attach per-event ``stale``/``corrupt``
    # extras; both replay paths thread a ring buffer of the last H flat
    # states (one snapshot per round, taken right after the gradient tick)
    # and serve stale partner reads from it.  Slot indices are resolved
    # host-side — the jit'd loops gather/scatter with schedule data only.

    def _partner_leaf(self, a, ring_a, partner, src_slot, horizon: int):
        """Per-leaf partner read: fresh rows of ``a`` where src_slot == H,
        ring snapshots otherwise.  a: (n, *s); ring_a: (H, n, *s)."""
        fresh = jnp.take(a, partner, axis=0)
        if not horizon:
            return fresh
        stale = ring_a[jnp.minimum(src_slot, horizon - 1), partner]
        sel = jnp.reshape(src_slot < horizon,
                          (a.shape[0],) + (1,) * (a.ndim - 1))
        return jnp.where(sel, stale, fresh)

    def _channel_p2p(self, x, x_tilde, xp, corrupt):
        """p2p update from (possibly corrupted/stale) received values, with
        the optional robust rule on the m-term (norm trim/clip across the
        whole replica, matching the engine's flat-row norm; or the
        per-coordinate clip).  Delegates to the dynamic-params twin with
        the static alphas lifted to traced constants — ``jnp.asarray`` of
        a Python float lands on the same bits a weak scalar would (full
        precision under x64, f32 otherwise)."""
        return self._channel_p2p_dyn(x, x_tilde, xp, corrupt,
                                     jnp.asarray(self.params.alpha),
                                     jnp.asarray(self.params.alpha_tilde))

    def _comm_event_channel(self, horizon: int, ring, carry, event,
                            tel=None):
        if tel is None:
            x, x_tilde, t_last = carry
        else:
            x, x_tilde, t_last, acc = carry
        partner, time, mask, src_slot, corrupt = event
        involved = (partner != jnp.arange(partner.shape[0])) & mask
        dt = jnp.where(involved, time - t_last, 0.0)
        x, x_tilde = apply_mixing(x, x_tilde, self.params.eta, dt)
        t_last = jnp.where(involved, time, t_last)
        flat_x, treedef = jax.tree_util.tree_flatten(x)
        ring_leaves = treedef.flatten_up_to(ring) if horizon \
            else [None] * len(flat_x)
        xp = treedef.unflatten([
            self._partner_leaf(a, ra, partner, src_slot, horizon)
            for a, ra in zip(flat_x, ring_leaves)])
        if tel is not None:
            nrm = self._delta_norms_tree(x, xp, corrupt)
            acc = self._tel_step(acc, involved, self._tel_rej(nrm), nrm)
        # idle/masked rows read themselves fresh with corrupt 0 => m = 0
        x, x_tilde = self._channel_p2p(x, x_tilde, xp, corrupt)
        if tel is None:
            return (x, x_tilde, t_last), None
        return (x, x_tilde, t_last, acc), None

    def _round_channel(self, horizon: int, carry, round_sched, tel=None):
        x, x_tilde, t_last, ring, key = carry
        (partners, times, mask, src_slots, corrupts, grad_times, grad_scale,
         alive, ring_pos) = round_sched
        inner = partial(self._comm_event_channel, horizon, ring, tel=tel)
        # the telemetry accumulator is LOCAL to the round's event scan —
        # zeroed here, emitted through the metrics dict below — so the
        # round-level carry keeps its public shape (the fleet jits this
        # round body directly)
        inner_carry = (x, x_tilde, t_last) if tel is None else \
            (x, x_tilde, t_last, self._tel_zeros())
        inner_carry, _ = jax.lax.scan(
            inner, inner_carry,
            (partners, times, mask, src_slots, corrupts))
        if tel is None:
            x, x_tilde, t_last = inner_carry
        else:
            x, x_tilde, t_last, acc = inner_carry

        dt = jnp.where(alive, grad_times - t_last, 0.0)
        x, x_tilde = apply_mixing(x, x_tilde, self.params.eta, dt)
        n = grad_times.shape[0]
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, n)
        losses, grads = jax.vmap(self.grad_fn)(x, keys, jnp.arange(n))

        def upd(p, g):
            s = jnp.reshape(grad_scale, grad_scale.shape
                            + (1,) * (g.ndim - 1)).astype(g.dtype)
            return p - self.gamma * (s * g)

        x = jax.tree.map(upd, x, grads)
        x_tilde = jax.tree.map(upd, x_tilde, grads)
        if horizon:
            # end-of-round snapshot: post-gradient, pre-trailing-mixing —
            # exactly what the engine path's ring_push captures
            ring = jax.tree.map(lambda ra, a: ra.at[ring_pos].set(a),
                                ring, x)
        t_last = jnp.where(alive, grad_times, t_last)
        metrics = {
            "loss": jnp.mean(losses),
            "consensus": consensus_distance(x),
            "mean_param_norm": sum(jnp.sum(m ** 2) for m in
                                   jax.tree.leaves(worker_mean(x))),
        }
        if tel is not None:
            metrics.update(tel_applied=acc[0], tel_rejected=acc[1],
                           tel_norm_sum=acc[2], tel_norm_sq=acc[3])
        return (x, x_tilde, t_last, ring, key), metrics

    def _run_channel_reference_impl(self, state: SimState, schedule_arrays,
                                    horizon: int, tel=None
                                    ) -> tuple[SimState, SimTrace]:
        ring = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (horizon,) + a.shape), state.x) \
            if horizon else None
        carry = (state.x, state.x_tilde, state.t_last, ring, state.key)
        carry, metrics = jax.lax.scan(
            partial(self._round_channel, horizon, tel=tel), carry,
            schedule_arrays)
        x, x_tilde, t_last, _, key = carry
        return SimState(x, x_tilde, t_last, key), \
            SimTrace(metrics["loss"], metrics["consensus"],
                     metrics["mean_param_norm"],
                     telemetry=None if tel is None else
                     (metrics["tel_applied"], metrics["tel_rejected"],
                      metrics["tel_norm_sum"], metrics["tel_norm_sq"]))

    _run_channel_reference_jit, _run_channel_reference_dnt = _jit_pair(
        _run_channel_reference_impl, static=(0, 3, 4))

    def _round_defense(self, horizon: int, dk, carry, round_sched,
                       tel=None):
        """Defense twin of ``_round_channel``: defense_comm runs per EVENT
        here where the engine path runs it per fused batch — equivalent
        because a batch merges only disjoint matchings (each reader row
        and its trust entry sees at most one event per batch, so the row
        updates commute; DESIGN.md §12)."""
        x, x_tilde, t_last, ring, key, ds = carry
        (partners, times, mask, src_slots, corrupts, grad_times, grad_scale,
         alive, ring_pos) = round_sched
        alpha = jnp.asarray(self.params.alpha)
        alpha_t = jnp.asarray(self.params.alpha_tilde)
        idx = jnp.arange(t_last.shape[0])

        def comm_event(carry, event):
            if tel is None:
                x, xt, tl, ds = carry
            else:
                x, xt, tl, ds, acc = carry
            partner, time, msk, src_slot, corrupt = event
            involved = (partner != idx) & msk
            dt = jnp.where(involved, time - tl, 0.0)
            x, xt = apply_mixing(x, xt, self.params.eta, dt)
            tl = jnp.where(involved, time, tl)
            flat_x, treedef = jax.tree_util.tree_flatten(x)
            ring_leaves = treedef.flatten_up_to(ring) if horizon \
                else [None] * len(flat_x)
            xp = treedef.unflatten([
                self._partner_leaf(a, ra, partner, src_slot, horizon)
                for a, ra in zip(flat_x, ring_leaves)])
            nrm = self._delta_norms_tree(x, xp, corrupt)
            mscale, quar, ds = defense_comm(dk, ds, partner, involved, nrm)
            x, xt = self._channel_p2p_scaled(x, xt, xp, corrupt, mscale,
                                             alpha, alpha_t)
            # the kernel's rejection output IS (mscale == 0) — provably,
            # so the reference folds the same mask into the counters
            rej = (mscale == 0.0).astype(jnp.float32)
            ds = defense_absorb(ds, rej, quar, involved)
            if tel is None:
                return (x, xt, tl, ds), None
            acc = self._tel_step(acc, involved, rej, nrm)
            return (x, xt, tl, ds, acc), None

        inner_carry = (x, x_tilde, t_last, ds) if tel is None else \
            (x, x_tilde, t_last, ds, self._tel_zeros())
        inner_carry, _ = jax.lax.scan(
            comm_event, inner_carry,
            (partners, times, mask, src_slots, corrupts))
        if tel is None:
            x, x_tilde, t_last, ds = inner_carry
        else:
            x, x_tilde, t_last, ds, acc = inner_carry

        dt = jnp.where(alive, grad_times - t_last, 0.0)
        x, x_tilde = apply_mixing(x, x_tilde, self.params.eta, dt)
        n = grad_times.shape[0]
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, n)
        losses, grads = jax.vmap(self.grad_fn)(x, keys, jnp.arange(n))

        def upd(p, g):
            s = jnp.reshape(grad_scale, grad_scale.shape
                            + (1,) * (g.ndim - 1)).astype(g.dtype)
            return p - self.gamma * (s * g)

        x = jax.tree.map(upd, x, grads)
        x_tilde = jax.tree.map(upd, x_tilde, grads)
        ds, (tau, rejn, quarn) = defense_grad(dk, ds)
        if horizon:
            ring = jax.tree.map(lambda ra, a: ra.at[ring_pos].set(a),
                                ring, x)
        t_last = jnp.where(alive, grad_times, t_last)
        metrics = {
            "loss": jnp.mean(losses),
            "consensus": consensus_distance(x),
            "mean_param_norm": sum(jnp.sum(m ** 2) for m in
                                   jax.tree.leaves(worker_mean(x))),
            "tau": tau, "rejections": rejn, "quarantined": quarn,
        }
        if tel is not None:
            metrics.update(tel_applied=acc[0], tel_rejected=acc[1],
                           tel_norm_sum=acc[2], tel_norm_sq=acc[3])
        return (x, x_tilde, t_last, ring, key, ds), metrics

    def _run_defense_reference_impl(self, state: SimState, dk,
                                    schedule_arrays, horizon: int, tel=None
                                    ) -> tuple[SimState, SimTrace]:
        ring = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (horizon,) + a.shape), state.x) \
            if horizon else None
        n = jnp.asarray(state.t_last).shape[0]
        carry = (state.x, state.x_tilde, state.t_last, ring, state.key,
                 defense_init(n))
        carry, metrics = jax.lax.scan(
            partial(self._round_defense, horizon, dk, tel=tel), carry,
            schedule_arrays)
        x, x_tilde, t_last, _, key, _ = carry
        return SimState(x, x_tilde, t_last, key), \
            SimTrace(metrics["loss"], metrics["consensus"],
                     metrics["mean_param_norm"],
                     DefenseTrace(metrics["tau"], metrics["rejections"],
                                  metrics["quarantined"]),
                     telemetry=None if tel is None else
                     (metrics["tel_applied"], metrics["tel_rejected"],
                      metrics["tel_norm_sum"], metrics["tel_norm_sq"]))

    _run_defense_reference_jit, _run_defense_reference_dnt = _jit_pair(
        _run_defense_reference_impl, static=(0, 4, 5))

    def _channel_step(self, engine: FlatGossipEngine, n: int, horizon: int,
                      carry, xs, tel=None):
        """Channel twin of ``_engine_step``: fused channel batches with
        ring-buffer stale reads, ring rotation at gradient ticks.  With a
        telemetry spec the carry tail holds the round accumulator —
        emitted + reset at each gradient tick, DefenseTrace-style."""
        partner, dt_nxt, is_grad, gscale, corrupt, src_slot, ring_pos = xs

        def comm(args):
            if tel is None:
                bx, bxt, ring, key = args
            else:
                bx, bxt, ring, key, acc = args
            if horizon:
                xp = engine.partner_values(ring, bx, partner, src_slot)
            else:
                xp = jnp.take(bx, partner, axis=0)
            if tel is not None:
                nrm = engine.delta_norms(bx, xp, corrupt, axes=1)
                involved = partner != jnp.arange(n)
                acc = self._tel_step(acc, involved, self._tel_rej(nrm),
                                     nrm)
            bx, bxt = engine.channel_batch(bx, bxt, xp, corrupt, dt_nxt)
            z = jnp.zeros((), jnp.float32)
            if tel is None:
                return (bx, bxt, ring, key), (z, z, z)
            return (bx, bxt, ring, key, acc), (z,) * 7

        def grad(args):
            if tel is None:
                bx, bxt, ring, key = args
            else:
                bx, bxt, ring, key, acc = args
            key, sub = jax.random.split(key)
            keys = jax.random.split(sub, n)
            losses, grads = jax.vmap(self.grad_fn)(engine.unpack(bx), keys,
                                                   jnp.arange(n))
            g = engine.pack(grads)
            g = gscale[:, None].astype(g.dtype) * g
            bx = bx - self.gamma * g
            bxt = bxt - self.gamma * g
            mean = jnp.mean(bx, axis=0, keepdims=True)
            loss = jnp.mean(losses).astype(jnp.float32)
            consensus = (jnp.sum((bx - mean) ** 2) / n).astype(jnp.float32)
            mean_norm = jnp.sum(mean ** 2).astype(jnp.float32)
            if horizon:
                ring = engine.ring_push(ring, bx, ring_pos)
            bx, bxt = engine.mix(bx, bxt, dt_nxt)
            if tel is None:
                return (bx, bxt, ring, key), (loss, consensus, mean_norm)
            return (bx, bxt, ring, key, self._tel_zeros()), \
                (loss, consensus, mean_norm) + acc

        return jax.lax.cond(is_grad, grad, comm, carry)

    def _run_channel_impl(self, state: SimState, stream_arrays, horizon: int,
                          tel=None) -> tuple[SimState, SimTrace]:
        (prologue, partners, dt_next, is_grad, grad_scale, grad_pos,
         t_final, corrupt, src_slot, ring_pos) = stream_arrays
        engine = FlatGossipEngine.for_pytree(state.x, self.params,
                                             stacked=True,
                                             backend=self.backend,
                                             robust_clip=self.robust_clip,
                                             robust_rule=self.robust_rule)
        bx = engine.pack(state.x)
        bxt = engine.pack(state.x_tilde)
        bx, bxt = engine.mix(bx, bxt, prologue)
        n = prologue.shape[0]
        ring = engine.ring_init(bx, horizon) if horizon else None
        init = (bx, bxt, ring, state.key) if tel is None else \
            (bx, bxt, ring, state.key, self._tel_zeros())
        carry, ys = jax.lax.scan(
            partial(self._channel_step, engine, n, horizon, tel=tel),
            init,
            (partners, dt_next, is_grad, grad_scale, corrupt, src_slot,
             ring_pos))
        bx, bxt, _, key = carry[:4]
        final = SimState(engine.unpack(bx), engine.unpack(bxt), t_final, key)
        if tel is None:
            loss, consensus, mean_norm = ys
            tcols = None
        else:
            loss, consensus, mean_norm = ys[:3]
            tcols = tuple(c[grad_pos] for c in ys[3:])
        return final, SimTrace(loss[grad_pos], consensus[grad_pos],
                               mean_norm[grad_pos], telemetry=tcols)

    _run_channel_jit, _run_channel_dnt = _jit_pair(
        _run_channel_impl, static=(0, 3, 4))

    # ------------------------------------------- self-healing replays
    # (DESIGN.md §12) The defense flavors are the channel flavors with the
    # control loop threaded through the scan carry: per comm step the
    # delta norms feed defense_comm (adaptive tau + trust/quarantine ->
    # the external mscale), the fused kernel emits its rejection mask back
    # into the trust counters, and each gradient tick runs defense_grad
    # (quantile EMA update + trace row).  NEUTRAL knobs reproduce the
    # static trim arithmetic bitwise, so one trace serves the whole
    # none-vs-static-vs-adaptive grid.

    def _defense_step(self, engine: FlatGossipEngine, n: int, horizon: int,
                      dk, carry, xs, tel=None):
        """Defense twin of ``_channel_step``: the control loop rides the
        carry as a ``defense.DefenseState``."""
        partner, dt_nxt, is_grad, gscale, corrupt, src_slot, ring_pos = xs

        def comm(args):
            if tel is None:
                bx, bxt, ring, key, ds = args
            else:
                bx, bxt, ring, key, ds, acc = args
            if horizon:
                xp = engine.partner_values(ring, bx, partner, src_slot)
            else:
                xp = jnp.take(bx, partner, axis=0)
            nrm = engine.delta_norms(bx, xp, corrupt, axes=1)
            involved = partner != jnp.arange(n)
            mscale, quar, ds = defense_comm(dk, ds, partner, involved, nrm)
            bx, bxt, rej = engine.channel_batch_scaled(bx, bxt, xp, corrupt,
                                                       mscale, dt_nxt)
            ds = defense_absorb(ds, rej, quar, involved)
            z = jnp.zeros((), jnp.float32)
            if tel is None:
                return (bx, bxt, ring, key, ds), (z, z, z, z, z, z)
            acc = self._tel_step(acc, involved, rej, nrm)
            return (bx, bxt, ring, key, ds, acc), (z,) * 10

        def grad(args):
            if tel is None:
                bx, bxt, ring, key, ds = args
            else:
                bx, bxt, ring, key, ds, acc = args
            key, sub = jax.random.split(key)
            keys = jax.random.split(sub, n)
            losses, grads = jax.vmap(self.grad_fn)(engine.unpack(bx), keys,
                                                   jnp.arange(n))
            g = engine.pack(grads)
            g = gscale[:, None].astype(g.dtype) * g
            bx = bx - self.gamma * g
            bxt = bxt - self.gamma * g
            mean = jnp.mean(bx, axis=0, keepdims=True)
            loss = jnp.mean(losses).astype(jnp.float32)
            consensus = (jnp.sum((bx - mean) ** 2) / n).astype(jnp.float32)
            mean_norm = jnp.sum(mean ** 2).astype(jnp.float32)
            ds, (tau, rejn, quarn) = defense_grad(dk, ds)
            if horizon:
                ring = engine.ring_push(ring, bx, ring_pos)
            bx, bxt = engine.mix(bx, bxt, dt_nxt)
            if tel is None:
                return (bx, bxt, ring, key, ds), (loss, consensus,
                                                  mean_norm, tau, rejn,
                                                  quarn)
            return (bx, bxt, ring, key, ds, self._tel_zeros()), \
                (loss, consensus, mean_norm, tau, rejn, quarn) + acc

        return jax.lax.cond(is_grad, grad, comm, carry)

    def _run_defense_impl(self, state: SimState, dk, stream_arrays,
                          horizon: int, tel=None
                          ) -> tuple[SimState, SimTrace]:
        (prologue, partners, dt_next, is_grad, grad_scale, grad_pos,
         t_final, corrupt, src_slot, ring_pos) = stream_arrays
        engine = FlatGossipEngine.for_pytree(state.x, self.params,
                                             stacked=True,
                                             backend=self.backend,
                                             robust_clip=self.robust_clip,
                                             robust_rule=self.robust_rule)
        bx = engine.pack(state.x)
        bxt = engine.pack(state.x_tilde)
        bx, bxt = engine.mix(bx, bxt, prologue)
        n = prologue.shape[0]
        ring = engine.ring_init(bx, horizon) if horizon else None
        init = (bx, bxt, ring, state.key, defense_init(n))
        if tel is not None:
            init = init + (self._tel_zeros(),)
        carry, ys = jax.lax.scan(
            partial(self._defense_step, engine, n, horizon, dk, tel=tel),
            init,
            (partners, dt_next, is_grad, grad_scale, corrupt, src_slot,
             ring_pos))
        bx, bxt, _, key = carry[:4]
        loss, consensus, mean_norm, tau, rejn, quarn = ys[:6]
        tcols = None if tel is None else tuple(c[grad_pos] for c in ys[6:])
        final = SimState(engine.unpack(bx), engine.unpack(bxt), t_final, key)
        return final, SimTrace(
            loss[grad_pos], consensus[grad_pos], mean_norm[grad_pos],
            DefenseTrace(tau[grad_pos], rejn[grad_pos], quarn[grad_pos]),
            telemetry=tcols)

    _run_defense_jit, _run_defense_dnt = _jit_pair(
        _run_defense_impl, static=(0, 4, 5))

    @staticmethod
    def _channel_extras(extras: dict, shape, horizon_from: str = STALE_KEY):
        """(stale, corrupt, horizon) materialized at ``shape`` (zeros where
        a key is absent); the ring depth is the max staleness the schedule
        actually demands, so replays are self-contained."""
        stale = extras.get(STALE_KEY)
        stale = np.zeros(shape, np.int32) if stale is None \
            else np.asarray(stale, np.int32)
        corrupt = extras.get(CORRUPT_KEY)
        corrupt = np.zeros(shape, np.float32) if corrupt is None \
            else np.asarray(corrupt, np.float32)
        horizon = int(stale.max()) if stale.size else 0
        return stale, corrupt, horizon

    def channel_coalesced_arrays(self, state: SimState, sched: Schedule, *,
                                 cs=None):
        """Engine scan inputs for a channel schedule + the ring depth H.

        Staleness offsets are resolved to absolute ring slots host-side:
        an event in round r reading s rounds back is served from slot
        ``(r - s) mod H``; the sentinel H means a fresh read.
        """
        from .events import coalesced_stream
        stream = coalesced_stream(cs or coalesce_schedule(sched),
                                  np.asarray(state.t_last))
        S, n = stream.partners.shape
        stale, corrupt, horizon = self._channel_extras(
            stream.extras or {}, (S, n))
        h = max(horizon, 1)
        # round index per step: a round closes at its gradient tick
        step_round = np.searchsorted(np.asarray(stream.grad_pos),
                                     np.arange(S), side="left")
        src_slot = np.where(stale > 0, (step_round[:, None] - stale) % h,
                            horizon).astype(np.int32)
        ring_pos = (step_round % h).astype(np.int32)
        return (jnp.asarray(stream.prologue), jnp.asarray(stream.partners),
                jnp.asarray(stream.dt_next), jnp.asarray(stream.is_grad),
                jnp.asarray(stream.grad_scale),
                jnp.asarray(stream.grad_pos),
                jnp.asarray(stream.t_final),
                jnp.asarray(corrupt), jnp.asarray(src_slot),
                jnp.asarray(ring_pos)), horizon

    def channel_reference_arrays(self, sched: Schedule):
        """Per-event channel replay inputs + ring depth H (slot resolution
        as in ``channel_coalesced_arrays``, at (R, K, n))."""
        R, K, n = sched.partners.shape
        stale, corrupt, horizon = self._channel_extras(
            sched.extras_dict(), (R, K, n))
        h = max(horizon, 1)
        rr = np.arange(R)[:, None, None]
        src_slot = np.where(stale > 0, (rr - stale) % h,
                            horizon).astype(np.int32)
        ring_pos = (np.arange(R) % h).astype(np.int32)
        return (jnp.asarray(sched.partners), jnp.asarray(sched.event_times),
                jnp.asarray(sched.event_mask), jnp.asarray(src_slot),
                jnp.asarray(corrupt), jnp.asarray(sched.grad_times),
                jnp.asarray(sched.grad_scale()),
                jnp.asarray(sched.alive_arr()),
                jnp.asarray(ring_pos)), horizon

    # ------------------------------------------------------------------ run
    def _run_reference_impl(self, state: SimState, schedule_arrays
                            ) -> tuple[SimState, SimTrace]:
        final, metrics = jax.lax.scan(self._round, state, schedule_arrays)
        return final, SimTrace(metrics["loss"], metrics["consensus"],
                               metrics["mean_param_norm"])

    _run_reference_jit, _run_reference_dnt = _jit_pair(_run_reference_impl)

    def run(self, state: SimState, schedule_arrays) -> tuple[SimState, SimTrace]:
        """Per-event reference replay (unfused, sweeps masked slots too)."""
        fn = self._run_reference_dnt if self.donate \
            else self._run_reference_jit
        return fn(state, schedule_arrays)

    def _run_coalesced_impl(self, state: SimState, stream_arrays
                            ) -> tuple[SimState, SimTrace]:
        (prologue, partners, dt_next, is_grad, grad_scale, grad_pos,
         t_final) = stream_arrays
        engine = FlatGossipEngine.for_pytree(state.x, self.params,
                                             stacked=True,
                                             backend=self.backend)
        bx = engine.pack(state.x)
        bxt = engine.pack(state.x_tilde)
        bx, bxt = engine.mix(bx, bxt, prologue)
        n = prologue.shape[0]
        (bx, bxt, key), ys = jax.lax.scan(
            partial(self._engine_step, engine, n), (bx, bxt, state.key),
            (partners, dt_next, is_grad, grad_scale))
        loss, consensus, mean_norm = ys
        final = SimState(engine.unpack(bx), engine.unpack(bxt), t_final, key)
        # compact per-step metrics back to per-round (gradient-tick rows)
        return final, SimTrace(loss[grad_pos], consensus[grad_pos],
                               mean_norm[grad_pos])

    _run_coalesced_jit, _run_coalesced_dnt = _jit_pair(_run_coalesced_impl)

    def coalesced_arrays(self, state: SimState, sched: Schedule, *, cs=None):
        """Compile a schedule + start clocks into the engine's scan inputs.

        ``cs`` reuses an already-coalesced schedule (else coalesced here).
        """
        from .events import coalesced_stream
        stream = coalesced_stream(cs or coalesce_schedule(sched),
                                  np.asarray(state.t_last))
        return (jnp.asarray(stream.prologue), jnp.asarray(stream.partners),
                jnp.asarray(stream.dt_next), jnp.asarray(stream.is_grad),
                jnp.asarray(stream.grad_scale),
                jnp.asarray(stream.grad_pos),
                jnp.asarray(stream.t_final))

    def reference_arrays(self, sched: Schedule):
        """Schedule arrays for the per-event reference replay (``run``)."""
        return (jnp.asarray(sched.partners), jnp.asarray(sched.event_times),
                jnp.asarray(sched.event_mask), jnp.asarray(sched.grad_times),
                jnp.asarray(sched.grad_scale()),
                jnp.asarray(sched.alive_arr()))

    def run_coalesced(self, state: SimState, stream_arrays
                      ) -> tuple[SimState, SimTrace]:
        """Flat-buffer engine replay of a coalesced event stream (hot path)."""
        fn = self._run_coalesced_dnt if self.donate \
            else self._run_coalesced_jit
        return fn(state, stream_arrays)

    def schedule_executable(self, state: SimState, sched: Schedule):
        """The exact (jitted replay, argument tuple) ``run_schedule``
        dispatches for a plain (channel-free) schedule on the engine path,
        in this simulator's donation flavor — for AOT inspection of the
        hot path (``fn.lower(*args).compile()``) without a replay."""
        fn = self._run_coalesced_dnt if self.donate \
            else self._run_coalesced_jit
        return fn, (self, state, self.coalesced_arrays(state, sched))

    def run_world(self, state: SimState, world, rounds: int | None = None, *,
                  seed: int = 0, engine: bool = True):
        """Compile a declarative ``world.World`` and replay it.

        Sugar for ``run_schedule(state, world.compile(rounds, seed))`` —
        the scenario description stays first-class up to the replay call.
        A ``world.defense`` rides along: its comm controller was already
        applied by ``compile``, its in-scan loop engages here.
        """
        return self.run_schedule(state, world.compile(rounds, seed=seed),
                                 engine=engine,
                                 defense=getattr(world, "defense", None),
                                 telemetry=getattr(world, "telemetry", None))

    def run_schedule(self, state: SimState, sched: Schedule, *,
                     engine: bool = True, defense=None, telemetry=None,
                     mesh=None):
        if mesh is not None:
            # lift to a B=1 worlds replay (the sharded flavors are
            # world-batched only) and squeeze the world axis back off —
            # the pinned batched-equals-serial precedent
            finalw, trw = self.run_worlds(
                [state], [sched],
                defenses=None if defense is None else [defense],
                engine=engine, telemetry=telemetry, mesh=mesh)

            def _sq(v):
                return v[0] if getattr(v, "ndim", 0) >= 1 else v

            def _sqt(t):
                return None if t is None else type(t)(*[_sq(v) for v in t])

            final = SimState(jax.tree.map(lambda a: a[0], finalw.x),
                             jax.tree.map(lambda a: a[0], finalw.x_tilde),
                             finalw.t_last[0], finalw.key[0])
            return final, SimTrace(trw.loss[0], trw.consensus[0],
                                   trw.mean_param_norm[0],
                                   _sqt(trw.defense), _sqt(trw.telemetry))
        tel = telemetry
        active = defense is not None and defense.is_active
        if active and self.robust_rule != "trim":
            raise ValueError("the self-healing defense needs "
                             "robust_rule='trim' (its accept/reject loop "
                             f"is binary), got {self.robust_rule!r}")
        if engine:
            try:
                # layout build validates an exact buffer dtype exists
                FlatLayout.from_pytree(state.x, stacked=True)
            except TypeError:
                engine = False  # e.g. int leaves: per-event path handles
        # channel worlds (stale/corrupt extras) and robust aggregation run
        # on the channel twins of both paths; an active defense selects
        # the self-healing twins; everything else stays on the original
        # replays bit-for-bit
        extras = sched.extras_dict()
        # a telemetry spec forces the channel flavor too: plain schedules
        # degenerate on it bitwise (horizon 0 / corrupt 0 — the pinned
        # channel-equals-plain precedent), and the flavor carries the
        # accumulator machinery
        channel = (STALE_KEY in extras or CORRUPT_KEY in extras
                   or self.robust_clip is not None or tel is not None)
        # schedule columns + row bytes BEFORE dispatch: under donation the
        # replay consumes ``state``, and only shapes survive it
        rb = self._row_bytes(state) if tel is not None and tel.bytes_moved \
            else 0
        cols = schedule_columns(tel, sched) if tel is not None else None
        if engine:
            if active:
                arrays, horizon = self.channel_coalesced_arrays(state, sched)
                dk = knobs_single(defense, self.robust_clip)
                fn = self._run_defense_dnt if self.donate \
                    else self._run_defense_jit
                out = fn(state, dk, arrays, horizon, tel)
            elif channel:
                arrays, horizon = self.channel_coalesced_arrays(state, sched)
                fn = self._run_channel_dnt if self.donate \
                    else self._run_channel_jit
                out = fn(state, arrays, horizon, tel)
            else:
                return self.run_coalesced(state,
                                          self.coalesced_arrays(state,
                                                                sched))
        elif active:
            arrays, horizon = self.channel_reference_arrays(sched)
            dk = knobs_single(defense, self.robust_clip)
            fn = self._run_defense_reference_dnt if self.donate \
                else self._run_defense_reference_jit
            out = fn(state, dk, arrays, horizon, tel)
        elif channel:
            arrays, horizon = self.channel_reference_arrays(sched)
            fn = self._run_channel_reference_dnt if self.donate \
                else self._run_channel_reference_jit
            out = fn(state, arrays, horizon, tel)
        else:
            return self.run(state, self.reference_arrays(sched))
        if tel is None:
            return out
        final, tr = out
        return final, tr._replace(
            telemetry=finalize_trace(tel, tr.telemetry, cols, rb))

    # ---------------------------------------- batched many-worlds replay
    # (DESIGN.md §11) B independent worlds in ONE compiled scan: (B, W, D)
    # buffers, (B, H, W, D) snapshot rings, per-world A2CiD2 dynamics as
    # (B,) arrays.  The batched stream aligns every world's gradient ticks
    # on shared step indices (events.stack_streams), so the scan keeps the
    # serial replay's single lax.cond — the batch axis never enters
    # control flow, and per world the replay is the serial one bit-for-bit
    # (signed zeros aside; pinned in tests/test_batched_replay.py).

    @staticmethod
    def world_params(params_list) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Per-world (eta, alpha, alpha_tilde) as (B,) arrays — the
        dynamic twins of the static Python-float scalars the serial
        replays bind.  Built at the DEFAULT float precision (f64 under
        JAX_ENABLE_X64, f32 otherwise) so every consumer can reproduce
        the serial arithmetic bitwise: the p2p multiplies cast to the
        buffer dtype (full precision under x64, exactly like a weak
        Python scalar), while the kernels' mixing-coefficient pipeline
        downcasts eta to f32 — the precision the serial fused kernels
        compute c in regardless of x64 (their dt operand is f32 and weak
        scalars don't promote).  Rounding to f32 once commutes with the
        power-of-two multiplies (rn(2x) = 2 rn(x)), so both routes land
        on the serial bits."""
        return (jnp.asarray([p.eta for p in params_list]),
                jnp.asarray([p.alpha for p in params_list]),
                jnp.asarray([p.alpha_tilde for p in params_list]))

    @staticmethod
    def batch_states(states) -> SimState:
        """Stack per-world SimStates onto a leading world axis (leaves
        (n, ...) -> (B, n, ...); keys (B, 2) — each world keeps its own
        stream)."""
        states = list(states)
        if not states:
            raise ValueError("need at least one state")
        return SimState(
            x=jax.tree.map(lambda *a: jnp.stack(a),
                           *[s.x for s in states]),
            x_tilde=jax.tree.map(lambda *a: jnp.stack(a),
                                 *[s.x_tilde for s in states]),
            t_last=jnp.stack([s.t_last for s in states]),
            key=jnp.stack([s.key for s in states]))

    def _grad_worlds(self, engine: FlatGossipEngine, n: int, bx, bxt, key,
                     gscale, gammas):
        """Shared gradient tick of the batched engine flavors: per-world
        key streams (identical to each serial replay's), doubly-vmapped
        grad_fn, per-world metrics.  ``gammas`` is the (B,) per-world
        step-size array (built at default precision, so the cast to the
        buffer dtype reproduces the serial weak-scalar multiply
        bitwise)."""
        ks = jax.vmap(jax.random.split)(key)
        key, sub = ks[:, 0], ks[:, 1]
        wkeys = jax.vmap(lambda k: jax.random.split(k, n))(sub)
        losses, grads = jax.vmap(jax.vmap(self.grad_fn),
                                 in_axes=(0, 0, None))(
            engine.unpack_worlds(bx), wkeys, jnp.arange(n))
        g = engine.pack_worlds(grads)
        g = gscale[:, :, None].astype(g.dtype) * g
        gs = jnp.asarray(gammas).astype(g.dtype)[:, None, None]
        bx = bx - gs * g
        bxt = bxt - gs * g
        mean = jnp.mean(bx, axis=1, keepdims=True)
        loss = jnp.mean(losses, axis=1).astype(jnp.float32)
        consensus = (jnp.sum((bx - mean) ** 2, axis=(1, 2)) / n
                     ).astype(jnp.float32)
        mean_norm = jnp.sum(mean ** 2, axis=(1, 2)).astype(jnp.float32)
        return bx, bxt, key, (loss, consensus, mean_norm)

    def _worlds_step(self, engine: FlatGossipEngine, n: int, pw, gammas,
                     carry, xs):
        """Batched twin of ``_engine_step``; ``is_grad`` is shared across
        the batch (stream alignment), so the step keeps one lax.cond."""
        partner, dt_nxt, is_grad, gscale = xs

        def comm(args):
            bx, bxt, key = args
            bx, bxt = engine.batch_worlds(bx, bxt, partner, dt_nxt, pw)
            z = jnp.zeros((partner.shape[0],), jnp.float32)
            return (bx, bxt, key), (z, z, z)

        def grad(args):
            bx, bxt, key = args
            bx, bxt, key, metrics = self._grad_worlds(engine, n, bx, bxt,
                                                      key, gscale, gammas)
            bx, bxt = engine.mix_batch(bx, bxt, dt_nxt, pw[0])
            return (bx, bxt, key), metrics

        return jax.lax.cond(is_grad, grad, comm, carry)

    def _run_worlds_impl(self, state: SimState, pw, gammas, stream_arrays
                         ) -> tuple[SimState, SimTrace]:
        (prologue, partners, dt_next, is_grad, grad_scale, grad_pos,
         t_final) = stream_arrays
        engine = FlatGossipEngine.for_pytree(state.x, self.params,
                                             stacked=True, worlds=True,
                                             backend=self.backend)
        bx = engine.pack_worlds(state.x)
        bxt = engine.pack_worlds(state.x_tilde)
        bx, bxt = engine.mix_batch(bx, bxt, prologue, pw[0])
        n = prologue.shape[1]
        (bx, bxt, key), ys = jax.lax.scan(
            partial(self._worlds_step, engine, n, pw, gammas),
            (bx, bxt, state.key),
            (partners, dt_next, is_grad, grad_scale))
        loss, consensus, mean_norm = ys
        final = SimState(engine.unpack_worlds(bx), engine.unpack_worlds(bxt),
                         t_final, key)
        # per-step (S, B) metrics -> per-world (B, R) traces
        return final, SimTrace(loss[grad_pos].T, consensus[grad_pos].T,
                               mean_norm[grad_pos].T)

    _run_worlds_jit, _run_worlds_dnt = _jit_pair(_run_worlds_impl)

    def _worlds_channel_step(self, engine: FlatGossipEngine, n: int,
                             horizon: int, pw, gammas, taus, carry, xs,
                             tel=None):
        """Batched twin of ``_channel_step``: per-world ring reads, one
        shared ring rotation slot per gradient tick.  ``taus`` (None or a
        traced (B,) array) is the lifted per-world robust threshold."""
        (partner, dt_nxt, is_grad, gscale, corrupt, src_slot,
         ring_pos) = xs

        def comm(args):
            if tel is None:
                bx, bxt, ring, key = args
            else:
                bx, bxt, ring, key, acc = args
            if horizon:
                xp = engine.partner_values_worlds(ring, bx, partner,
                                                  src_slot)
            else:
                xp = jnp.take_along_axis(bx, partner[:, :, None], axis=1)
            if tel is not None:
                nrm = engine.delta_norms(bx, xp, corrupt, axes=2)
                involved = partner != jnp.arange(n)[None, :]
                acc = self._tel_step(acc, involved,
                                     self._tel_rej(nrm, taus), nrm,
                                     batched=True)
            bx, bxt = engine.channel_batch_worlds(bx, bxt, xp, corrupt,
                                                  dt_nxt, pw, taus)
            z = jnp.zeros((partner.shape[0],), jnp.float32)
            if tel is None:
                return (bx, bxt, ring, key), (z, z, z)
            return (bx, bxt, ring, key, acc), (z,) * 7

        def grad(args):
            if tel is None:
                bx, bxt, ring, key = args
            else:
                bx, bxt, ring, key, acc = args
            bx, bxt, key, metrics = self._grad_worlds(engine, n, bx, bxt,
                                                      key, gscale, gammas)
            if horizon:
                ring = engine.ring_push_worlds(ring, bx, ring_pos)
            bx, bxt = engine.mix_batch(bx, bxt, dt_nxt, pw[0])
            if tel is None:
                return (bx, bxt, ring, key), metrics
            B = partner.shape[0]
            return (bx, bxt, ring, key, self._tel_zeros((B,))), \
                metrics + acc

        return jax.lax.cond(is_grad, grad, comm, carry)

    def _run_worlds_channel_impl(self, state: SimState, pw, gammas, taus,
                                 stream_arrays, horizon: int, tel=None
                                 ) -> tuple[SimState, SimTrace]:
        (prologue, partners, dt_next, is_grad, grad_scale, grad_pos,
         t_final, corrupt, src_slot, ring_pos) = stream_arrays
        engine = FlatGossipEngine.for_pytree(state.x, self.params,
                                             stacked=True, worlds=True,
                                             backend=self.backend,
                                             robust_clip=self.robust_clip,
                                             robust_rule=self.robust_rule)
        bx = engine.pack_worlds(state.x)
        bxt = engine.pack_worlds(state.x_tilde)
        bx, bxt = engine.mix_batch(bx, bxt, prologue, pw[0])
        B, n = prologue.shape
        ring = engine.ring_init_worlds(bx, horizon) if horizon else None
        init = (bx, bxt, ring, state.key) if tel is None else \
            (bx, bxt, ring, state.key, self._tel_zeros((B,)))
        carry, ys = jax.lax.scan(
            partial(self._worlds_channel_step, engine, n, horizon, pw,
                    gammas, taus, tel=tel),
            init,
            (partners, dt_next, is_grad, grad_scale, corrupt, src_slot,
             ring_pos))
        bx, bxt, _, key = carry[:4]
        final = SimState(engine.unpack_worlds(bx), engine.unpack_worlds(bxt),
                         t_final, key)
        loss, consensus, mean_norm = ys[:3]
        tcols = None if tel is None else \
            tuple(c[grad_pos].T for c in ys[3:])
        return final, SimTrace(loss[grad_pos].T, consensus[grad_pos].T,
                               mean_norm[grad_pos].T, telemetry=tcols)

    _run_worlds_channel_jit, _run_worlds_channel_dnt = _jit_pair(
        _run_worlds_channel_impl, static=(0, 6, 7))

    def _worlds_defense_step(self, engine: FlatGossipEngine, n: int,
                             horizon: int, pw, gammas, dk, carry, xs,
                             tel=None):
        """Batched twin of ``_defense_step``: the control loop vmaps over
        the world axis (``dk`` a DefenseKnobs of (B,) arrays — every arm,
        including 'no defense' lowered to the neutral knobs, shares this
        one trace)."""
        (partner, dt_nxt, is_grad, gscale, corrupt, src_slot,
         ring_pos) = xs

        def comm(args):
            if tel is None:
                bx, bxt, ring, key, ds = args
            else:
                bx, bxt, ring, key, ds, acc = args
            if horizon:
                xp = engine.partner_values_worlds(ring, bx, partner,
                                                  src_slot)
            else:
                xp = jnp.take_along_axis(bx, partner[:, :, None], axis=1)
            nrm = engine.delta_norms(bx, xp, corrupt, axes=2)
            involved = partner != jnp.arange(n)[None, :]
            mscale, quar, ds = jax.vmap(defense_comm)(dk, ds, partner,
                                                      involved, nrm)
            bx, bxt, rej = engine.channel_batch_worlds_scaled(
                bx, bxt, xp, corrupt, mscale, dt_nxt, pw)
            ds = jax.vmap(defense_absorb)(ds, rej, quar, involved)
            z = jnp.zeros((partner.shape[0],), jnp.float32)
            if tel is None:
                return (bx, bxt, ring, key, ds), (z, z, z, z, z, z)
            acc = self._tel_step(acc, involved, rej, nrm, batched=True)
            return (bx, bxt, ring, key, ds, acc), (z,) * 10

        def grad(args):
            if tel is None:
                bx, bxt, ring, key, ds = args
            else:
                bx, bxt, ring, key, ds, acc = args
            bx, bxt, key, metrics = self._grad_worlds(engine, n, bx, bxt,
                                                      key, gscale, gammas)
            ds, (tau, rejn, quarn) = jax.vmap(defense_grad)(dk, ds)
            if horizon:
                ring = engine.ring_push_worlds(ring, bx, ring_pos)
            bx, bxt = engine.mix_batch(bx, bxt, dt_nxt, pw[0])
            if tel is None:
                return (bx, bxt, ring, key, ds), metrics + (tau, rejn,
                                                            quarn)
            B = partner.shape[0]
            return (bx, bxt, ring, key, ds, self._tel_zeros((B,))), \
                metrics + (tau, rejn, quarn) + acc

        return jax.lax.cond(is_grad, grad, comm, carry)

    def _run_worlds_defense_impl(self, state: SimState, pw, gammas, dk,
                                 stream_arrays, horizon: int, tel=None
                                 ) -> tuple[SimState, SimTrace]:
        (prologue, partners, dt_next, is_grad, grad_scale, grad_pos,
         t_final, corrupt, src_slot, ring_pos) = stream_arrays
        engine = FlatGossipEngine.for_pytree(state.x, self.params,
                                             stacked=True, worlds=True,
                                             backend=self.backend,
                                             robust_clip=self.robust_clip,
                                             robust_rule=self.robust_rule)
        bx = engine.pack_worlds(state.x)
        bxt = engine.pack_worlds(state.x_tilde)
        bx, bxt = engine.mix_batch(bx, bxt, prologue, pw[0])
        B, n = prologue.shape
        ring = engine.ring_init_worlds(bx, horizon) if horizon else None
        init = (bx, bxt, ring, state.key, defense_init(n, batch=B))
        if tel is not None:
            init = init + (self._tel_zeros((B,)),)
        carry, ys = jax.lax.scan(
            partial(self._worlds_defense_step, engine, n, horizon, pw,
                    gammas, dk, tel=tel),
            init,
            (partners, dt_next, is_grad, grad_scale, corrupt, src_slot,
             ring_pos))
        bx, bxt, _, key = carry[:4]
        loss, consensus, mean_norm, tau, rejn, quarn = ys[:6]
        tcols = None if tel is None else \
            tuple(c[grad_pos].T for c in ys[6:])
        final = SimState(engine.unpack_worlds(bx), engine.unpack_worlds(bxt),
                         t_final, key)
        return final, SimTrace(
            loss[grad_pos].T, consensus[grad_pos].T, mean_norm[grad_pos].T,
            DefenseTrace(tau[grad_pos].T, rejn[grad_pos].T,
                         quarn[grad_pos].T),
            telemetry=tcols)

    _run_worlds_defense_jit, _run_worlds_defense_dnt = _jit_pair(
        _run_worlds_defense_impl, static=(0, 6, 7))

    # --- batched per-event reference flavor: the serial round body with
    # dynamic per-world params, vmapped over the world axis inside the
    # round scan (the equivalence oracle at batch scale)

    @staticmethod
    def _mix_dyn(x, x_tilde, eta, dt):
        """``apply_mixing`` with a traced per-world eta (no eta == 0
        shortcut: baseline worlds compute the exact-zero coefficient).
        ``dt`` keeps its incoming dtype exactly like the serial path —
        under x64 the reference round promotes it to f64, and the
        coefficient must be computed there at full precision to match."""
        dt = jnp.asarray(dt)

        def mix(a, b):
            c = (0.5 * (1.0 - jnp.exp(-2.0 * eta * dt))).astype(a.dtype)
            c = jnp.reshape(c, c.shape + (1,) * (a.ndim - c.ndim))
            d = b - a
            return a + c * d, b - c * d

        flat_x, treedef = jax.tree_util.tree_flatten(x)
        flat_t = treedef.flatten_up_to(x_tilde)
        mixed = [mix(a, b) for a, b in zip(flat_x, flat_t)]
        return (treedef.unflatten([m[0] for m in mixed]),
                treedef.unflatten([m[1] for m in mixed]))

    @staticmethod
    def _p2p_dyn(x, x_tilde, partner, alpha, alpha_t):
        """``matched_p2p_update`` with traced per-world alphas."""
        def upd(a, at):
            b = jnp.take(a, partner, axis=0)
            m = a - b
            return (a - alpha.astype(a.dtype) * m,
                    at - alpha_t.astype(a.dtype) * m)

        flat_x, treedef = jax.tree_util.tree_flatten(x)
        flat_t = treedef.flatten_up_to(x_tilde)
        out = [upd(a, at) for a, at in zip(flat_x, flat_t)]
        return (treedef.unflatten([o[0] for o in out]),
                treedef.unflatten([o[1] for o in out]))

    def _channel_p2p_dyn(self, x, x_tilde, xp, corrupt, alpha, alpha_t,
                         tau=None):
        """``_channel_p2p`` with traced per-world alphas (robust rule and
        clip stay static — they are replay knobs, not world data).  A
        traced per-world ``tau`` overrides the static threshold (the
        lifted ``robust_clips`` axis; norm rules only): tau = inf arms
        degenerate bitwise to the plain m-term for finite deltas."""
        clip = self.robust_clip
        rule = self.robust_rule
        flat_x, treedef = jax.tree_util.tree_flatten(x)
        flat_t = treedef.flatten_up_to(x_tilde)
        flat_p = treedef.flatten_up_to(xp)

        def cadv_for(a):
            c = (1.0 + corrupt).astype(a.dtype)
            return jnp.reshape(c, c.shape + (1,) * (a.ndim - 1))

        mscale = None
        if tau is not None or (clip is not None and rule != "coord"):
            nrm2 = sum(
                jnp.sum(((a - cadv_for(a) * b).astype(jnp.float32)) ** 2,
                        axis=tuple(range(1, a.ndim)))
                for a, b in zip(flat_x, flat_p))
            nrm = jnp.sqrt(nrm2)
            tval = tau if tau is not None else clip
            if rule == "trim":
                mscale = (nrm <= tval).astype(jnp.float32)
            else:
                mscale = jnp.minimum(1.0, tval / jnp.maximum(nrm, 1e-30))

        def upd(a, at, b):
            m = a - cadv_for(a) * b
            if mscale is not None:
                s = mscale.astype(a.dtype)
                m = m * jnp.reshape(s, s.shape + (1,) * (a.ndim - 1))
            elif clip is not None:
                m = jnp.clip(m, -clip, clip)
            return (a - alpha.astype(a.dtype) * m,
                    at - alpha_t.astype(a.dtype) * m)

        out = [upd(a, at, b) for a, at, b in zip(flat_x, flat_t, flat_p)]
        return (treedef.unflatten([o[0] for o in out]),
                treedef.unflatten([o[1] for o in out]))

    @staticmethod
    def _delta_norms_tree(x, xp, corrupt):
        """Pytree twin of ``engine.delta_norms``: (n,) f32 L2 norms of the
        corrupted channel deltas (per-leaf f32 square-sums, the same
        arithmetic ``_channel_p2p_dyn`` runs for its norm rules)."""
        flat_x, treedef = jax.tree_util.tree_flatten(x)
        flat_p = treedef.flatten_up_to(xp)

        def cadv_for(a):
            c = (1.0 + corrupt).astype(a.dtype)
            return jnp.reshape(c, c.shape + (1,) * (a.ndim - 1))

        nrm2 = sum(
            jnp.sum(((a - cadv_for(a) * b).astype(jnp.float32)) ** 2,
                    axis=tuple(range(1, a.ndim)))
            for a, b in zip(flat_x, flat_p))
        return jnp.sqrt(nrm2)

    @staticmethod
    def _channel_p2p_scaled(x, x_tilde, xp, corrupt, mscale, alpha,
                            alpha_t):
        """Channel p2p with an EXTERNAL (n,) mscale (the defense loop's
        adaptive-tau + quarantine decision) — the reference twin of
        ``engine.channel_batch_scaled``'s m-term."""
        flat_x, treedef = jax.tree_util.tree_flatten(x)
        flat_t = treedef.flatten_up_to(x_tilde)
        flat_p = treedef.flatten_up_to(xp)

        def upd(a, at, b):
            c = (1.0 + corrupt).astype(a.dtype)
            c = jnp.reshape(c, c.shape + (1,) * (a.ndim - 1))
            m = a - c * b
            s = mscale.astype(a.dtype)
            m = m * jnp.reshape(s, s.shape + (1,) * (a.ndim - 1))
            return (a - alpha.astype(a.dtype) * m,
                    at - alpha_t.astype(a.dtype) * m)

        out = [upd(a, at, b) for a, at, b in zip(flat_x, flat_t, flat_p)]
        return (treedef.unflatten([o[0] for o in out]),
                treedef.unflatten([o[1] for o in out]))

    def _grad_world_ref(self, x, x_tilde, t_last, key, eta, gamma,
                        grad_times, grad_scale, alive):
        """Shared gradient tail of the per-world reference round;
        ``gamma`` is the traced per-world step size (cast to the leaf
        dtype — the same bits the serial weak-scalar multiply lands
        on)."""
        dt = jnp.where(alive, grad_times - t_last, 0.0)
        x, x_tilde = self._mix_dyn(x, x_tilde, eta, dt)
        n = grad_times.shape[0]
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, n)
        losses, grads = jax.vmap(self.grad_fn)(x, keys, jnp.arange(n))

        def upd(p, g):
            s = jnp.reshape(grad_scale, grad_scale.shape
                            + (1,) * (g.ndim - 1)).astype(g.dtype)
            return p - gamma.astype(g.dtype) * (s * g)

        x = jax.tree.map(upd, x, grads)
        x_tilde = jax.tree.map(upd, x_tilde, grads)
        metrics = {
            "loss": jnp.mean(losses),
            "consensus": consensus_distance(x),
            "mean_param_norm": sum(jnp.sum(m ** 2) for m in
                                   jax.tree.leaves(worker_mean(x))),
        }
        return x, x_tilde, key, metrics

    def _run_worlds_reference_impl(self, state: SimState, pw, gammas,
                                   sched_arrays
                                   ) -> tuple[SimState, SimTrace]:
        def per_world(x, xt, tl, key, eta, alpha, alphat, gamma, partners,
                      times, mask, grad_times, grad_scale, alive):
            idx = jnp.arange(tl.shape[0])

            def comm_event(carry, event):
                x, xt, tl = carry
                partner, time, msk = event
                involved = (partner != idx) & msk
                dt = jnp.where(involved, time - tl, 0.0)
                x, xt = self._mix_dyn(x, xt, eta, dt)
                tl = jnp.where(involved, time, tl)
                x, xt = self._p2p_dyn(x, xt, partner, alpha, alphat)
                return (x, xt, tl), None

            (x, xt, tl), _ = jax.lax.scan(comm_event, (x, xt, tl),
                                          (partners, times, mask))
            x, xt, key, metrics = self._grad_world_ref(
                x, xt, tl, key, eta, gamma, grad_times, grad_scale, alive)
            tl = jnp.where(alive, grad_times, tl)
            return (x, xt, tl, key), metrics

        def round_fn(carry, xs):
            x, xt, tl, key = carry
            partners, times, mask, grad_times, grad_scale, alive = xs
            (x, xt, tl, key), metrics = jax.vmap(per_world)(
                x, xt, tl, key, *pw, gammas, partners, times, mask,
                grad_times, grad_scale, alive)
            return (x, xt, tl, key), metrics

        carry = (state.x, state.x_tilde, state.t_last, state.key)
        (x, xt, tl, key), metrics = jax.lax.scan(round_fn, carry,
                                                 sched_arrays)
        return SimState(x, xt, tl, key), \
            SimTrace(metrics["loss"].T, metrics["consensus"].T,
                     metrics["mean_param_norm"].T)

    _run_worlds_reference_jit, _run_worlds_reference_dnt = _jit_pair(
        _run_worlds_reference_impl)

    def _run_worlds_channel_reference_impl(self, state: SimState, pw,
                                           gammas, taus, sched_arrays,
                                           horizon: int, tel=None
                                           ) -> tuple[SimState, SimTrace]:
        def per_world(x, xt, tl, ring, key, eta, alpha, alphat, gamma, tau,
                      partners, times, mask, src_slots, corrupts,
                      grad_times, grad_scale, alive, ring_pos):
            idx = jnp.arange(tl.shape[0])

            def comm_event(carry, event):
                if tel is None:
                    x, xt, tl = carry
                else:
                    x, xt, tl, acc = carry
                partner, time, msk, src_slot, corrupt = event
                involved = (partner != idx) & msk
                dt = jnp.where(involved, time - tl, 0.0)
                x, xt = self._mix_dyn(x, xt, eta, dt)
                tl = jnp.where(involved, time, tl)
                flat_x, treedef = jax.tree_util.tree_flatten(x)
                ring_leaves = treedef.flatten_up_to(ring) if horizon \
                    else [None] * len(flat_x)
                xp = treedef.unflatten([
                    self._partner_leaf(a, ra, partner, src_slot, horizon)
                    for a, ra in zip(flat_x, ring_leaves)])
                if tel is not None:
                    nrm = self._delta_norms_tree(x, xp, corrupt)
                    acc = self._tel_step(acc, involved,
                                         self._tel_rej(nrm, tau), nrm)
                x, xt = self._channel_p2p_dyn(x, xt, xp, corrupt, alpha,
                                              alphat, tau)
                if tel is None:
                    return (x, xt, tl), None
                return (x, xt, tl, acc), None

            inner = (x, xt, tl) if tel is None else \
                (x, xt, tl, self._tel_zeros())
            inner, _ = jax.lax.scan(
                comm_event, inner,
                (partners, times, mask, src_slots, corrupts))
            if tel is None:
                x, xt, tl = inner
            else:
                x, xt, tl, acc = inner
            x, xt, key, metrics = self._grad_world_ref(
                x, xt, tl, key, eta, gamma, grad_times, grad_scale, alive)
            if tel is not None:
                metrics = {**metrics, "tel_applied": acc[0],
                           "tel_rejected": acc[1], "tel_norm_sum": acc[2],
                           "tel_norm_sq": acc[3]}
            if horizon:
                ring = jax.tree.map(lambda ra, a: ra.at[ring_pos].set(a),
                                    ring, x)
            tl = jnp.where(alive, grad_times, tl)
            return (x, xt, tl, ring, key), metrics

        ring = jax.tree.map(
            lambda a: jnp.broadcast_to(
                a[:, None], (a.shape[0], horizon) + a.shape[1:]),
            state.x) if horizon else None

        def round_fn(carry, xs):
            x, xt, tl, ring, key = carry
            (partners, times, mask, src_slots, corrupts, grad_times,
             grad_scale, alive, ring_pos) = xs
            out, metrics = jax.vmap(
                per_world,
                in_axes=(0,) * 18 + (None,))(
                x, xt, tl, ring, key, *pw, gammas, taus, partners, times,
                mask, src_slots, corrupts, grad_times, grad_scale, alive,
                ring_pos)
            return out, metrics

        carry = (state.x, state.x_tilde, state.t_last, ring, state.key)
        (x, xt, tl, _, key), metrics = jax.lax.scan(round_fn, carry,
                                                    sched_arrays)
        return SimState(x, xt, tl, key), \
            SimTrace(metrics["loss"].T, metrics["consensus"].T,
                     metrics["mean_param_norm"].T,
                     telemetry=None if tel is None else
                     (metrics["tel_applied"].T, metrics["tel_rejected"].T,
                      metrics["tel_norm_sum"].T, metrics["tel_norm_sq"].T))

    _run_worlds_channel_reference_jit, _run_worlds_channel_reference_dnt = \
        _jit_pair(_run_worlds_channel_reference_impl, static=(0, 6, 7))

    def _run_worlds_defense_reference_impl(self, state: SimState, pw,
                                           gammas, dk, sched_arrays,
                                           horizon: int, tel=None
                                           ) -> tuple[SimState, SimTrace]:
        def per_world(x, xt, tl, ring, key, ds, eta, alpha, alphat, gamma,
                      dkr, partners, times, mask, src_slots, corrupts,
                      grad_times, grad_scale, alive, ring_pos):
            idx = jnp.arange(tl.shape[0])

            def comm_event(carry, event):
                if tel is None:
                    x, xt, tl, ds = carry
                else:
                    x, xt, tl, ds, acc = carry
                partner, time, msk, src_slot, corrupt = event
                involved = (partner != idx) & msk
                dt = jnp.where(involved, time - tl, 0.0)
                x, xt = self._mix_dyn(x, xt, eta, dt)
                tl = jnp.where(involved, time, tl)
                flat_x, treedef = jax.tree_util.tree_flatten(x)
                ring_leaves = treedef.flatten_up_to(ring) if horizon \
                    else [None] * len(flat_x)
                xp = treedef.unflatten([
                    self._partner_leaf(a, ra, partner, src_slot, horizon)
                    for a, ra in zip(flat_x, ring_leaves)])
                nrm = self._delta_norms_tree(x, xp, corrupt)
                mscale, quar, ds = defense_comm(dkr, ds, partner, involved,
                                                nrm)
                x, xt = self._channel_p2p_scaled(x, xt, xp, corrupt,
                                                 mscale, alpha, alphat)
                rej = (mscale == 0.0).astype(jnp.float32)
                ds = defense_absorb(ds, rej, quar, involved)
                if tel is None:
                    return (x, xt, tl, ds), None
                acc = self._tel_step(acc, involved, rej, nrm)
                return (x, xt, tl, ds, acc), None

            inner = (x, xt, tl, ds) if tel is None else \
                (x, xt, tl, ds, self._tel_zeros())
            inner, _ = jax.lax.scan(
                comm_event, inner,
                (partners, times, mask, src_slots, corrupts))
            if tel is None:
                x, xt, tl, ds = inner
            else:
                x, xt, tl, ds, acc = inner
            x, xt, key, metrics = self._grad_world_ref(
                x, xt, tl, key, eta, gamma, grad_times, grad_scale, alive)
            ds, (tau, rejn, quarn) = defense_grad(dkr, ds)
            if horizon:
                ring = jax.tree.map(lambda ra, a: ra.at[ring_pos].set(a),
                                    ring, x)
            tl = jnp.where(alive, grad_times, tl)
            metrics = {**metrics, "tau": tau, "rejections": rejn,
                       "quarantined": quarn}
            if tel is not None:
                metrics = {**metrics, "tel_applied": acc[0],
                           "tel_rejected": acc[1], "tel_norm_sum": acc[2],
                           "tel_norm_sq": acc[3]}
            return (x, xt, tl, ring, key, ds), metrics

        ring = jax.tree.map(
            lambda a: jnp.broadcast_to(
                a[:, None], (a.shape[0], horizon) + a.shape[1:]),
            state.x) if horizon else None
        B, n = jnp.asarray(state.t_last).shape

        def round_fn(carry, xs):
            x, xt, tl, ring, key, ds = carry
            (partners, times, mask, src_slots, corrupts, grad_times,
             grad_scale, alive, ring_pos) = xs
            out, metrics = jax.vmap(
                per_world,
                in_axes=(0,) * 19 + (None,))(
                x, xt, tl, ring, key, ds, *pw, gammas, dk, partners,
                times, mask, src_slots, corrupts, grad_times, grad_scale,
                alive, ring_pos)
            return out, metrics

        carry = (state.x, state.x_tilde, state.t_last, ring, state.key,
                 defense_init(n, batch=B))
        (x, xt, tl, _, key, _), metrics = jax.lax.scan(round_fn, carry,
                                                       sched_arrays)
        return SimState(x, xt, tl, key), \
            SimTrace(metrics["loss"].T, metrics["consensus"].T,
                     metrics["mean_param_norm"].T,
                     DefenseTrace(metrics["tau"].T,
                                  metrics["rejections"].T,
                                  metrics["quarantined"].T),
                     telemetry=None if tel is None else
                     (metrics["tel_applied"].T, metrics["tel_rejected"].T,
                      metrics["tel_norm_sum"].T, metrics["tel_norm_sq"].T))

    _run_worlds_defense_reference_jit, _run_worlds_defense_reference_dnt = \
        _jit_pair(_run_worlds_defense_reference_impl, static=(0, 6, 7))

    # --- host-side batch compilation + the public entry point

    @staticmethod
    def _coalesce_batch(scheds):
        """Coalesce each schedule once per unique OBJECT — a sweep grid
        legitimately repeats one schedule across arms (baseline vs
        accelerated replay the identical world), and coalescing is the
        expensive host-side pass."""
        cache = {}
        for s in scheds:
            if id(s) not in cache:
                cache[id(s)] = coalesce_schedule(s)
        return [cache[id(s)] for s in scheds]

    def worlds_coalesced_arrays(self, states: SimState, scheds, *,
                                css=None):
        """Engine scan inputs for B schedules: coalesce each world, align
        the streams (events.stack_streams), lift to device arrays."""
        from .events import stack_streams
        css = css if css is not None else self._coalesce_batch(scheds)
        bs = stack_streams(css, np.asarray(states.t_last))
        return (jnp.asarray(bs.prologue), jnp.asarray(bs.partners),
                jnp.asarray(bs.dt_next), jnp.asarray(bs.is_grad),
                jnp.asarray(bs.grad_scale), jnp.asarray(bs.grad_pos),
                jnp.asarray(bs.t_final))

    def worlds_channel_arrays(self, states: SimState, scheds, *, css=None):
        """Channel twin of ``worlds_coalesced_arrays`` + shared ring depth
        H = the max staleness ANY world demands (worlds with a shallower —
        or no — delay read the same snapshots they would serially: a
        deeper ring holds a superset of their window, and fresh reads use
        the sentinel H)."""
        from .events import stack_streams
        css = css if css is not None else self._coalesce_batch(scheds)
        bs = stack_streams(css, np.asarray(states.t_last))
        S, B, n = bs.partners.shape
        stale, corrupt, horizon = self._channel_extras(bs.extras_dict(),
                                                       (S, B, n))
        h = max(horizon, 1)
        step_round = np.searchsorted(np.asarray(bs.grad_pos), np.arange(S),
                                     side="left")
        src_slot = np.where(stale > 0,
                            (step_round[:, None, None] - stale) % h,
                            horizon).astype(np.int32)
        ring_pos = (step_round % h).astype(np.int32)
        return (jnp.asarray(bs.prologue), jnp.asarray(bs.partners),
                jnp.asarray(bs.dt_next), jnp.asarray(bs.is_grad),
                jnp.asarray(bs.grad_scale), jnp.asarray(bs.grad_pos),
                jnp.asarray(bs.t_final), jnp.asarray(corrupt),
                jnp.asarray(src_slot), jnp.asarray(ring_pos)), horizon

    def worlds_reference_arrays(self, scheds):
        """Batched per-event reference inputs (events.stack_schedules)."""
        from .events import stack_schedules
        b = stack_schedules(list(scheds))
        return (jnp.asarray(b.partners), jnp.asarray(b.event_times),
                jnp.asarray(b.event_mask), jnp.asarray(b.grad_times),
                jnp.asarray(b.grad_scale), jnp.asarray(b.alive))

    def worlds_channel_reference_arrays(self, scheds):
        """Batched per-event channel reference inputs + shared ring depth
        (slot resolution as in ``worlds_channel_arrays``)."""
        from .events import stack_schedules
        b = stack_schedules(list(scheds))
        R, B, K, n = b.partners.shape
        stale, corrupt, horizon = self._channel_extras(b.extras_dict(),
                                                       (R, B, K, n))
        h = max(horizon, 1)
        rr = np.arange(R)[:, None, None, None]
        src_slot = np.where(stale > 0, (rr - stale) % h,
                            horizon).astype(np.int32)
        ring_pos = (np.arange(R) % h).astype(np.int32)
        return (jnp.asarray(b.partners), jnp.asarray(b.event_times),
                jnp.asarray(b.event_mask), jnp.asarray(src_slot),
                jnp.asarray(corrupt), jnp.asarray(b.grad_times),
                jnp.asarray(b.grad_scale), jnp.asarray(b.alive),
                jnp.asarray(ring_pos)), horizon

    def run_worlds(self, states, scheds, *, params=None, gammas=None,
                   robust_clips=None, defenses=None, worlds=None,
                   engine: bool = True, telemetry=None, mesh=None
                   ) -> tuple[SimState, SimTrace]:
        """Replay B independent worlds in ONE compiled scan.

        states — a list of per-world SimStates (stacked here via
          ``batch_states``) or an already world-batched SimState (leaves
          (B, n, ...)).
        scheds — B compiled ``events.Schedule``s sharing (rounds, n) —
          e.g. ``WorldSweep(...).compile(rounds)``.  Ragged event counts
          are padded with identity groups (exact no-ops), never branches.
        params — optional per-world ``A2CiD2Params`` (one per schedule),
          letting baseline and accelerated worlds — and any parameter
          grid — share the ONE trace; default replicates ``self.params``.
        worlds — optional B ``World`` specs (one per schedule): derives
          what the spec declares and the call didn't pass explicitly —
          ``params`` from each world's algorithm zoo arm
          (``World.algorithm_params()``; worlds with ``algorithm=None``
          keep ``self.params``, so scenario grids without a declared
          algorithm stay bitwise PR 6) and ``defenses`` from each world's
          ``defense`` field.  Explicit kwargs always win.
        gammas — optional per-world step sizes (floats; default
          ``self.gamma``), lifted to a traced (B,) array so a step-size
          grid shares the trace too.
        robust_clips — optional per-world robust-trim/clip thresholds
          (None entries fall back to ``self.robust_clip``, or +inf = the
          non-robust m-term bitwise), lifted to a traced (B,) array so
          robust-vs-plain ablations stop forcing a second trace.
        defenses — optional per-world ``AdaptiveDefense | None`` arms.
          Any ACTIVE arm routes the whole batch onto the self-healing
          flavor; inactive arms lower to the neutral knobs, which
          reproduce their static trim (or plain-channel) arithmetic
          bitwise — none-vs-static-vs-adaptive is still ONE trace.
        telemetry — optional ``telemetry.Telemetry`` spec (or declared on
          the ``worlds``; all declaring worlds must share ONE spec — it
          is a static jit argument).  Adds per-round flight-recorder
          columns as ``trace.telemetry`` ((B, rounds) arrays) without
          changing any replayed number; ``None`` is a bitwise no-op.
        mesh — optional ``jax.sharding.Mesh`` (a 1-D worker mesh from
          ``launch.mesh.make_replay_mesh``) or ``launch.mesh_replay.
          MeshReplay``: shard the worker axis of the flat banks over the
          mesh's devices and serve cross-shard partner reads through the
          bounded-staleness permute ring (DESIGN.md §16).  At lag 0 the
          final state is bitwise the single-device replay; a ragged
          worker axis (n % n_shards != 0) warns and falls back to the
          single-device flavors.

        Returns the world-batched final state and a SimTrace whose arrays
        are (B, rounds) — row b equals the serial replay of world b.
        Dispatch mirrors ``run_schedule``: channel extras or robust
        aggregation select the channel flavor; ``engine=False`` (or a
        layout-rejected pytree) the per-event reference flavor.
        """
        twin, args, tel, cols, rb = self._worlds_plan(
            states, scheds, params=params, gammas=gammas,
            robust_clips=robust_clips, defenses=defenses, worlds=worlds,
            engine=engine, telemetry=telemetry, mesh=mesh)
        fn = self._twin_fn(twin, self.donate)
        out = fn(*args)
        if tel is None:
            return out
        final, tr = out
        return final, tr._replace(
            telemetry=finalize_trace(tel, tr.telemetry, cols, rb))

    def worlds_executable(self, states, scheds, **kw):
        """The exact (jitted twin, argument tuple) a ``run_worlds`` call
        would dispatch — plain (non-donating) flavor, host-side batching
        already done.  Callers AOT-lower the grid's ONE executable
        (``fn.lower(*args).compile()``) for cost/roofline analysis
        without paying a replay, and without tracing through the host
        prep (``jax.jit(lambda: sim.run_worlds(...))`` would trip on
        ``batch_states``'s host numpy).  ``kw`` mirrors ``run_worlds``'s
        keywords."""
        twin, args, _, _, _ = self._worlds_plan(states, scheds, **kw)
        return self._twin_fn(twin, False), args

    def _twin_fn(self, twin: str, donate: bool):
        """Resolve a ``_worlds_plan`` twin name to its jitted callable.
        Class-level twins hang on ``type(self)``; the sharded-replay
        twins (``"@sharded_*"``) live in ``launch.mesh_replay`` —
        imported lazily so core never depends on launch at import."""
        if twin.startswith("@sharded_"):
            from ..launch.mesh_replay import sharded_twin
            return sharded_twin(twin[len("@sharded_"):], donate)
        return getattr(type(self), twin + ("_dnt" if donate else "_jit"))

    def worlds_sharded_arrays(self, states: SimState, scheds, mr, *,
                              css=None):
        """Sharded twin of ``worlds_channel_arrays``: the channel stream
        arrays plus the host-compiled shard plan
        (``events.shard_partition``) for ``mr``'s worker mesh.  A
        positive ``mr.lag`` first floors the staleness of every
        cross-shard read (``events.shard_lag_stale``) and deepens the
        shared ring to hold the lagged window."""
        from .events import shard_lag_stale, shard_partition, stack_streams
        css = css if css is not None else self._coalesce_batch(scheds)
        bs = stack_streams(css, np.asarray(states.t_last))
        S, B, n = bs.partners.shape
        stale, corrupt, horizon = self._channel_extras(bs.extras_dict(),
                                                       (S, B, n))
        step_round = np.searchsorted(np.asarray(bs.grad_pos), np.arange(S),
                                     side="left")
        if mr.lag > 0 and mr.n_shards > 1:
            stale = shard_lag_stale(bs.partners, stale, step_round,
                                    mr.n_shards, mr.lag)
            horizon = max(horizon, int(stale.max()))
        h = max(horizon, 1)
        src_slot = np.where(stale > 0,
                            (step_round[:, None, None] - stale) % h,
                            horizon).astype(np.int32)
        ring_pos = (step_round % h).astype(np.int32)
        plan = shard_partition(bs.partners, src_slot, mr.n_shards, horizon)
        return (jnp.asarray(bs.prologue), jnp.asarray(bs.partners),
                jnp.asarray(bs.dt_next), jnp.asarray(bs.is_grad),
                jnp.asarray(bs.grad_scale), jnp.asarray(bs.grad_pos),
                jnp.asarray(bs.t_final), jnp.asarray(corrupt),
                jnp.asarray(src_slot), jnp.asarray(ring_pos),
                jnp.asarray(plan.local_partner),
                jnp.asarray(plan.is_cross), jnp.asarray(plan.hop),
                jnp.asarray(plan.pool_pos), jnp.asarray(plan.pub_row),
                jnp.asarray(plan.pub_slot)), horizon

    def _worlds_plan(self, states, scheds, *, params=None, gammas=None,
                     robust_clips=None, defenses=None, worlds=None,
                     engine: bool = True, telemetry=None, mesh=None):
        """Shared host-side prep of a worlds replay: validate, derive
        per-world knobs, build the batched device arrays, pick the scan
        flavor.  Returns ``(twin_name, args, tel, cols, rb)`` where
        ``twin_name + '_jit'/'_dnt'`` names the class-level jit twin and
        ``args`` is its FULL argument tuple (``self`` included — the
        twins hang unbound on the class with ``self`` static)."""
        scheds = list(scheds)
        if not isinstance(states, SimState):
            states = self.batch_states(states)
        B = len(scheds)
        lead = jax.tree.leaves(states.x)[0].shape[0]
        if lead != B:
            raise ValueError(f"states are batched for {lead} worlds but "
                             f"{B} schedules were given")
        if worlds is not None:
            wlist = list(worlds)
            if len(wlist) != B:
                raise ValueError(f"worlds must have one entry per schedule "
                                 f"({B}), got {len(wlist)}")
            if params is None:
                params = [self.params if w.algorithm is None
                          else w.algorithm_params() for w in wlist]
            if defenses is None and any(w.defense is not None
                                        for w in wlist):
                defenses = [w.defense for w in wlist]
            if telemetry is None:
                tspecs = {w.telemetry for w in wlist
                          if getattr(w, "telemetry", None) is not None}
                if len(tspecs) > 1:
                    raise ValueError(
                        "worlds declare multiple distinct Telemetry specs; "
                        "a batch shares ONE static spec (it is a jit "
                        "static argument)")
                if tspecs:
                    telemetry = next(iter(tspecs))
        plist = list(params) if params is not None else [self.params] * B
        if len(plist) != B:
            raise ValueError(f"params must have one entry per world "
                             f"({B}), got {len(plist)}")
        pw = self.world_params(plist)
        glist = list(gammas) if gammas is not None else [self.gamma] * B
        if len(glist) != B:
            raise ValueError(f"gammas must have one entry per world "
                             f"({B}), got {len(glist)}")
        gw = jnp.asarray([float(g) for g in glist])
        clist = list(robust_clips) if robust_clips is not None \
            else [None] * B
        if len(clist) != B:
            raise ValueError(f"robust_clips must have one entry per world "
                             f"({B}), got {len(clist)}")
        taus_list = [self.robust_clip if c is None else float(c)
                     for c in clist]
        any_clip = robust_clips is not None
        dlist = list(defenses) if defenses is not None else [None] * B
        if len(dlist) != B:
            raise ValueError(f"defenses must have one entry per world "
                             f"({B}), got {len(dlist)}")
        active = any(d is not None and d.is_active for d in dlist)
        if (active or any_clip) and self.robust_rule == "coord":
            raise ValueError("per-world thresholds and the self-healing "
                             "defense need a norm rule ('trim' or "
                             "'clip'), not 'coord'")
        if active and self.robust_rule != "trim":
            raise ValueError("the self-healing defense needs "
                             "robust_rule='trim' (its accept/reject loop "
                             f"is binary), got {self.robust_rule!r}")
        tel = telemetry
        if engine:
            try:
                FlatLayout.from_pytree(states.x, stacked=True, worlds=True)
            except TypeError:
                engine = False
        mr = None
        if mesh is not None:
            from ..launch.mesh_replay import MeshReplay
            mr = mesh if isinstance(mesh, MeshReplay) else MeshReplay(mesh)
            if not engine:
                raise ValueError(
                    "the sharded replay (mesh=) runs on the flat-buffer "
                    "engine; engine=False (or a layout-rejected pytree) "
                    "has no worker banks to shard")
            n = jax.tree.leaves(states.x)[0].shape[1]
            if n % mr.n_shards != 0:
                warnings.warn(
                    f"worker axis {n} is not divisible by {mr.n_shards} "
                    f"shards; falling back to the single-device replay",
                    RuntimeWarning, stacklevel=3)
                mr = None
            elif tel is not None and tel.shards == 0:
                tel = dataclasses.replace(tel, shards=mr.n_shards)
        channel = (active or any_clip or self.robust_clip is not None
                   or tel is not None
                   or any(STALE_KEY in s.extras_dict()
                          or CORRUPT_KEY in s.extras_dict()
                          for s in scheds))
        taus = None
        if any_clip and not active:
            taus = jnp.asarray([float("inf") if t is None else t
                                for t in taus_list], jnp.float32)
        # exact schedule columns + row bytes before dispatch (donation
        # consumes the state buffers)
        rb = self._row_bytes(states, worlds=True) \
            if tel is not None and tel.bytes_moved else 0
        cols = batch_schedule_columns(tel, scheds) if tel is not None \
            else None
        if engine:
            if mr is not None:
                # the sharded twins are channel-based; plain worlds
                # degenerate on them bitwise (the pinned channel-equals-
                # plain precedent)
                arrays, horizon = self.worlds_sharded_arrays(states,
                                                             scheds, mr)
                if active:
                    dk = knobs_worlds(dlist, taus_list)
                    return ("@sharded_defense",
                            (self, states, pw, gw, dk, arrays, horizon,
                             tel, mr), tel, cols, rb)
                return ("@sharded_channel",
                        (self, states, pw, gw, taus, arrays, horizon,
                         tel, mr), tel, cols, rb)
            if active:
                arrays, horizon = self.worlds_channel_arrays(states, scheds)
                dk = knobs_worlds(dlist, taus_list)
                return ("_run_worlds_defense",
                        (self, states, pw, gw, dk, arrays, horizon, tel),
                        tel, cols, rb)
            if channel:
                arrays, horizon = self.worlds_channel_arrays(states, scheds)
                return ("_run_worlds_channel",
                        (self, states, pw, gw, taus, arrays, horizon, tel),
                        tel, cols, rb)
            return ("_run_worlds",
                    (self, states, pw, gw,
                     self.worlds_coalesced_arrays(states, scheds)),
                    None, None, 0)
        if active:
            arrays, horizon = self.worlds_channel_reference_arrays(scheds)
            dk = knobs_worlds(dlist, taus_list)
            return ("_run_worlds_defense_reference",
                    (self, states, pw, gw, dk, arrays, horizon, tel),
                    tel, cols, rb)
        if channel:
            arrays, horizon = self.worlds_channel_reference_arrays(scheds)
            return ("_run_worlds_channel_reference",
                    (self, states, pw, gw, taus, arrays, horizon, tel),
                    tel, cols, rb)
        return ("_run_worlds_reference",
                (self, states, pw, gw, self.worlds_reference_arrays(scheds)),
                None, None, 0)


# --------------------------------------------------------------- AR-SGD ref

def allreduce_sgd(grad_fn: GradFn, gamma: float, x0: PyTree, n: int,
                  rounds: int, key: jax.Array) -> tuple[PyTree, jax.Array]:
    """Synchronous All-Reduce SGD baseline (the paper's AR-SGD)."""

    stack = jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), x0)

    def step(carry, _):
        x, key = carry
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, n)
        losses, grads = jax.vmap(grad_fn)(x, keys, jnp.arange(n))
        mean_g = jax.tree.map(lambda g: jnp.mean(g, axis=0, keepdims=True), grads)
        x = jax.tree.map(lambda p, g: p - gamma * jnp.broadcast_to(g, p.shape),
                         x, mean_g)
        return (x, key), jnp.mean(losses)

    (x, _), losses = jax.lax.scan(step, (stack, key), None, length=rounds)
    return jax.tree.map(lambda a: a[0], x), losses
