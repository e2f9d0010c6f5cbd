"""Sharded giant-world replay (DESIGN.md §16): the worker axis of the
flat gossip banks split over a device mesh.

``Simulator.run_worlds(..., mesh=MeshReplay(mesh))`` replays the SAME
batched streams the single-device engine consumes, but the (B, W, D)
state banks, the (B, H, W, D) snapshot rings, and every fused
mixing/channel kernel pass live per-shard under ``shard_map`` over a
1-D ``("worker",)`` mesh (``launch.mesh.make_replay_mesh``).  Only one
operation ever crosses a shard boundary: the partner-value fetch of a
cross-shard pair, served by the **bounded-staleness permute ring** —

  * the host-side shard compiler (``events.shard_partition``) splits each
    step's matching into intra-shard pairs (the partner involution
    restricted to a shard is still an involution) and cross-shard
    boundary reads, and precomputes which local rows each shard must
    publish at each step;
  * at every comm step each shard resolves its published boundary rows
    against its OWN local snapshot ring (``engine.publish_rows`` — the
    publisher applies the read's scheduled staleness, so the value that
    crosses the wire is bitwise the single-device ``ring_read``), then
    ``n_shards - 1`` static ``lax.ppermute`` ring hops stack every
    shard's block into an (NS, B, nb, D) pool
    (``flatbuf.ring_pool_exchange``) readers index by (hop, pos);
  * ``MeshReplay.lag > 0`` floors the staleness of every cross-shard
    read at ``lag`` rounds (``events.shard_lag_stale``) — boundary
    exchanges then ride snapshots at least ``lag`` rounds old, which cuts
    the per-step exchange off the critical path in exchange for bounded
    staleness.  Semantically this IS a ``ChannelModel(delay=...)``: the
    lag-L sharded replay is pinned bitwise against the single-device
    replay of ``world.shard_lag_schedule(sched, NS, L)``.

Why the final state stays BITWISE at lag 0: the flat layout is
row-independent (per-worker rows pack identically at any W), every
kernel pass is row-local, cross-shard values are exact copies, and the
per-world key stream is computed redundantly on every shard (each shard
derives the full (B, n) key fan-out and slices its rows).  Only the
TRACE metrics (loss/consensus/mean-norm) cross shards — via ``psum`` of
per-shard partials, floating-point-reassociated but never fed back into
the state — so traces are allclose while states match bit for bit.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.defense import (DefenseState, defense_absorb, defense_comm,
                            defense_grad)
from ..core.engine import FlatGossipEngine
from ..core.flatbuf import ring_pool_exchange
from ..core.simulator import SimState, SimTrace, _jit_pair
from ..kernels.a2cid2_mixing.ref import take_partner_rows


@dataclasses.dataclass(frozen=True)
class MeshReplay:
    """Hashable sharded-replay spec: a 1-D device mesh with a worker
    axis, plus the permute ring's staleness lag.  Doubles as a static
    jit argument (``jax.sharding.Mesh`` is hashable), so every distinct
    (mesh, lag) — not every world — costs a trace.

    lag — staleness floor (in rounds) on cross-shard partner reads.
      0 = per-step boundary exchange, bitwise the single-device engine;
      L > 0 = boundary reads ride snapshots >= L rounds old, exactly a
      ``ChannelModel(delay=...)`` on the boundary edges.
    """

    mesh: jax.sharding.Mesh
    lag: int = 0
    axis: str = "worker"

    def __post_init__(self):
        if self.axis not in self.mesh.axis_names:
            raise ValueError(f"mesh has no {self.axis!r} axis "
                             f"(axes: {self.mesh.axis_names})")
        if self.lag < 0:
            raise ValueError(f"lag must be >= 0, got {self.lag}")

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[self.axis]

    # ------------------------------------------------------------ placement
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def bank_sharding(self) -> NamedSharding:
        """(B, W, ...) state banks / (B, W) columns: split on workers."""
        return self.sharding(None, self.axis)

    def ring_sharding(self) -> NamedSharding:
        """(B, H, W, D) snapshot rings: split on the worker axis."""
        return self.sharding(None, None, self.axis)

    def place_states(self, states: SimState) -> SimState:
        """Commit a world-batched SimState to the mesh — leaves (B, n,
        ...) split on the worker axis, keys replicated — so a replay
        reads its inputs in place instead of resharding them on entry."""
        bank, rep = self.bank_sharding(), self.sharding()
        put = lambda s: (lambda a: jax.device_put(a, s))
        return SimState(x=jax.tree.map(put(bank), states.x),
                        x_tilde=jax.tree.map(put(bank), states.x_tilde),
                        t_last=jax.device_put(states.t_last, bank),
                        key=jax.device_put(states.key, rep))

    def place_args(self, args: tuple) -> tuple:
        """Commit a sharded twin's argument tuple (as returned by
        ``Simulator.worlds_executable(..., mesh=...)``) to the mesh, so
        benchmark timings measure the replay, not input resharding."""
        sim, states, *mid, arrays, horizon, tel, mr = args
        row, col = self.sharding(None, self.axis), \
            self.sharding(None, None, self.axis)
        pub, rep = self.sharding(None, self.axis), self.sharding()
        specs = (row, col, col, rep, col, rep, rep, col, col, rep,
                 col, col, col, col, pub, pub)
        arrays = tuple(jax.device_put(a, s)
                       for a, s in zip(arrays, specs))
        mid = jax.device_put(tuple(mid), rep)
        return (sim, self.place_states(states), *mid, arrays, horizon,
                tel, mr)


# --------------------------------------------------------------------------
# The sharded scan impls.  Signatures mirror the single-device worlds
# twins (simulator._run_worlds_channel_impl / _run_worlds_defense_impl)
# with the MeshReplay appended as a trailing static argument; ``arrays``
# extends the channel stream arrays with the shard plan:
#   (prologue, partners, dt_next, is_grad, grad_scale, grad_pos, t_final,
#    corrupt, src_slot, ring_pos,
#    local_partner, is_cross, hop, pool_pos, pub_row, pub_slot)
# --------------------------------------------------------------------------

def _sharded_scan(sim, state, pw, gammas, taus, dk, arrays, horizon, tel,
                  mr):
    """Shared body of both sharded flavors; ``dk`` is None for the
    channel flavor, the per-world DefenseKnobs for the self-healing
    one."""
    (prologue, partners, dt_next, is_grad, grad_scale, grad_pos, t_final,
     corrupt, src_slot, ring_pos, lpart, cross, hop, ppos, pub_row,
     pub_slot) = arrays
    engine = FlatGossipEngine.for_pytree(state.x, sim.params,
                                         stacked=True, worlds=True,
                                         backend=sim.backend,
                                         robust_clip=sim.robust_clip,
                                         robust_rule=sim.robust_rule)
    bx = engine.pack_worlds(state.x)
    bxt = engine.pack_worlds(state.x_tilde)
    B, n = prologue.shape
    ns, ax = mr.n_shards, mr.axis
    wloc = n // ns
    defense = dk is not None

    def region(bx, bxt, key, prologue, xs, pw, gammas, taus_t, dk_t):
        taus_l = taus_t[0] if taus_t else None
        dk_l = dk_t[0] if dk_t else None
        bx, bxt = engine.mix_batch(bx, bxt, prologue, pw[0])
        ring = engine.ring_init_worlds(bx, horizon) if horizon else None
        i0 = jax.lax.axis_index(ax) * wloc
        wid = i0 + jnp.arange(wloc)
        init = (bx, bxt, ring, key)
        if defense:
            init = init + (DefenseState(
                qest=jnp.zeros((B,), jnp.float32),
                trust=jnp.ones((B, wloc, n), jnp.float32),
                lastn=jnp.zeros((B, wloc), jnp.float32),
                lastv=jnp.zeros((B, wloc), bool),
                rej_acc=jnp.zeros((B,), jnp.float32),
                quar_acc=jnp.zeros((B,), jnp.float32)),)
        if tel is not None:
            init = init + (sim._tel_zeros((B,)),)

        n_out = (6 if defense else 3) + (4 if tel is not None else 0)

        def step(carry, xs_t):
            (pg, lp, dtn, isg, gsc, cor, slot, rpos, crs, hp, pp, prow,
             pslot) = xs_t

            def comm(args):
                bx, bxt, ring, key = args[:4]
                rest = args[4:]
                if horizon:
                    xp = engine.partner_values_worlds(ring, bx, lp, slot)
                else:
                    xp = take_partner_rows(bx, lp)
                # boundary publish -> permute-ring pool -> cross reads
                pv = engine.publish_rows(ring, bx, prow[0], pslot[0])
                pool = ring_pool_exchange(pv, ax, ns)
                xp = engine.pool_partner_values(pool, hp, pp, xp, crs)
                involved = pg != wid[None, :]
                if defense:
                    ds = rest[0]
                    nrm = engine.delta_norms(bx, xp, cor, axes=2)
                    mscale, quar, ds = jax.vmap(defense_comm)(
                        dk_l, ds, pg, involved, nrm)
                    bx, bxt, rej = engine.channel_batch_worlds_scaled(
                        bx, bxt, xp, cor, mscale, dtn, pw)
                    ds = jax.vmap(defense_absorb)(ds, rej, quar, involved)
                    out = (bx, bxt, ring, key, ds)
                else:
                    if tel is not None:
                        nrm = engine.delta_norms(bx, xp, cor, axes=2)
                        rej = sim._tel_rej(nrm, taus_l)
                    bx, bxt = engine.channel_batch_worlds(
                        bx, bxt, xp, cor, dtn, pw, taus_l)
                    out = (bx, bxt, ring, key)
                if tel is not None:
                    acc = sim._tel_step(rest[-1], involved, rej, nrm,
                                        batched=True)
                    out = out + (acc,)
                z = jnp.zeros((B,), jnp.float32)
                return out, (z,) * n_out

            def grad(args):
                bx, bxt, ring, key = args[:4]
                rest = args[4:]
                bx, bxt, key, metrics = _grad_worlds_sharded(
                    sim, engine, n, wloc, ax, bx, bxt, key, gsc, gammas)
                if defense:
                    ds = rest[0]
                    dsg = DefenseState(
                        qest=ds.qest, trust=ds.trust,
                        lastn=jax.lax.all_gather(ds.lastn, ax, axis=1,
                                                 tiled=True),
                        lastv=jax.lax.all_gather(ds.lastv, ax, axis=1,
                                                 tiled=True),
                        rej_acc=jax.lax.psum(ds.rej_acc, ax),
                        quar_acc=jax.lax.psum(ds.quar_acc, ax))
                    ds, dtrace = jax.vmap(defense_grad)(dk_l, dsg)
                    ds = ds._replace(
                        lastn=jnp.zeros((B, wloc), jnp.float32),
                        lastv=jnp.zeros((B, wloc), bool))
                    metrics = metrics + dtrace
                if horizon:
                    ring = engine.ring_push_worlds(ring, bx, rpos)
                bx, bxt = engine.mix_batch(bx, bxt, dtn, pw[0])
                out = (bx, bxt, ring, key)
                if defense:
                    out = out + (ds,)
                if tel is not None:
                    acc = tuple(jax.lax.psum(a, ax) for a in rest[-1])
                    out = out + (sim._tel_zeros((B,)),)
                    metrics = metrics + acc
                return out, metrics

            return jax.lax.cond(isg, grad, comm, carry)

        carry, ys = jax.lax.scan(step, init, xs)
        return carry[0], carry[1], carry[3], ys

    rep = P()
    bank = P(None, ax, None)
    row = P(None, ax)
    col = P(None, None, ax)
    pub = P(None, ax, None, None)
    xs = (partners, lpart, dt_next, is_grad, grad_scale, corrupt,
          src_slot, ring_pos, cross, hop, ppos, pub_row, pub_slot)
    xs_specs = (col, col, col, rep, col, col, col, rep, col, col, col,
                pub, pub)
    taus_t = () if taus is None else (taus,)
    dk_t = () if dk is None else (dk,)
    bx, bxt, key, ys = shard_map(
        region, mesh=mr.mesh,
        in_specs=(bank, bank, rep, row, xs_specs, rep, rep,
                  (rep,) * len(taus_t), (rep,) * len(dk_t)),
        out_specs=(bank, bank, rep, rep),
        check_vma=False,
    )(bx, bxt, state.key, prologue, xs, pw, gammas, taus_t, dk_t)
    final = SimState(engine.unpack_worlds(bx), engine.unpack_worlds(bxt),
                     t_final, key)
    return final, ys, grad_pos


def _grad_worlds_sharded(sim, engine, n, wloc, ax, bx, bxt, key, gscale,
                         gammas):
    """Sharded twin of ``Simulator._grad_worlds``: every shard derives
    the FULL per-world (B, n) key fan-out and slices its own rows, so
    per-worker gradient noise is bitwise the single-device stream; the
    trace metrics are per-shard partial sums ``psum``-ed over the worker
    axis (metrics never feed back into the state)."""
    ks = jax.vmap(jax.random.split)(key)
    key, sub = ks[:, 0], ks[:, 1]
    wkeys = jax.vmap(lambda k: jax.random.split(k, n))(sub)
    i0 = jax.lax.axis_index(ax) * wloc
    wkeys = jax.lax.dynamic_slice_in_dim(wkeys, i0, wloc, axis=1)
    wid = i0 + jnp.arange(wloc)
    losses, grads = jax.vmap(jax.vmap(sim.grad_fn), in_axes=(0, 0, None))(
        engine.unpack_worlds(bx), wkeys, wid)
    g = engine.pack_worlds(grads)
    g = gscale[:, :, None].astype(g.dtype) * g
    gs = jnp.asarray(gammas).astype(g.dtype)[:, None, None]
    bx = bx - gs * g
    bxt = bxt - gs * g
    mean = (jax.lax.psum(jnp.sum(bx, axis=1), ax) / n)[:, None, :]
    loss = (jax.lax.psum(jnp.sum(losses, axis=1), ax) / n
            ).astype(jnp.float32)
    consensus = (jax.lax.psum(jnp.sum((bx - mean) ** 2, axis=(1, 2)), ax)
                 / n).astype(jnp.float32)
    mean_norm = jnp.sum(mean ** 2, axis=(1, 2)).astype(jnp.float32)
    return bx, bxt, key, (loss, consensus, mean_norm)


def _sharded_channel_impl(sim, state, pw, gammas, taus, arrays,
                          horizon: int, tel, mr: MeshReplay
                          ) -> tuple[SimState, SimTrace]:
    final, ys, grad_pos = _sharded_scan(sim, state, pw, gammas, taus,
                                        None, arrays, horizon, tel, mr)
    loss, consensus, mean_norm = ys[:3]
    tcols = None if tel is None else tuple(c[grad_pos].T for c in ys[3:])
    return final, SimTrace(loss[grad_pos].T, consensus[grad_pos].T,
                           mean_norm[grad_pos].T, telemetry=tcols)


def _sharded_defense_impl(sim, state, pw, gammas, dk, arrays,
                          horizon: int, tel, mr: MeshReplay
                          ) -> tuple[SimState, SimTrace]:
    from ..core.defense import DefenseTrace

    final, ys, grad_pos = _sharded_scan(sim, state, pw, gammas, None,
                                        dk, arrays, horizon, tel, mr)
    loss, consensus, mean_norm, tau, rejn, quarn = ys[:6]
    tcols = None if tel is None else tuple(c[grad_pos].T for c in ys[6:])
    return final, SimTrace(
        loss[grad_pos].T, consensus[grad_pos].T, mean_norm[grad_pos].T,
        DefenseTrace(tau[grad_pos].T, rejn[grad_pos].T,
                     quarn[grad_pos].T),
        telemetry=tcols)


# (plain, donating) jit twins, created once per process — grids of worlds
# on one (mesh, lag) share ONE trace exactly like the single-device
# flavors (self, horizon, tel, mr are the static arguments)
_TWINS: dict = {}


def sharded_twin(flavor: str, donate: bool = False):
    """The jitted sharded scan for ``flavor`` in {'channel', 'defense'};
    ``Simulator._twin_fn`` resolves ``"@sharded_*"`` plan names here."""
    if not _TWINS:
        _TWINS["channel"] = _jit_pair(_sharded_channel_impl,
                                      static=(0, 6, 7, 8))
        _TWINS["defense"] = _jit_pair(_sharded_defense_impl,
                                      static=(0, 6, 7, 8))
    return _TWINS[flavor][1 if donate else 0]
