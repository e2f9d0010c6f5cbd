"""Flat-buffer fused gossip-event engine — the one hot path all trainers
share (see DESIGN.md).

The engine owns three ingredients:

  1. a :class:`~repro.core.flatbuf.FlatLayout` packing the replica pytree
     into one contiguous buffer (stacked ``(W, D)`` or local ``(D,)``),
  2. the fused p2p-then-mix kernels from ``repro.kernels.a2cid2_mixing``
     (Pallas on TPU, jnp oracle on CPU),
  3. the *group* pass structure: the exact per-event sequence

         mix(d_0), S_0, mix(d_1), S_1, ..., S_{K-1}, mix(d_K)

     (S_i a fused comm batch or a gradient tick) regrouped as
     ``[mix(d_0)] [S_0, mix(d_1)] ... [S_{K-1}, mix(d_K)]`` — identical
     composition (the mixing flow is a semigroup and zero-dt segments are
     identities), but each bracketed group is ONE fused sweep reading 3
     state-sized buffers and writing 2.  events.coalesced_stream flattens a
     schedule into exactly these groups with every mixing segment
     precomputed host-side; masked schedule slots vanish entirely.

Traffic per coalesced batch: 3 reads + 2 writes of state, vs the per-event
path's 6 reads + 4 writes per event (2 unfused sweeps) — and the per-event
path also sweeps masked slots, which the coalesced stream drops entirely.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.a2cid2_mixing.ops import (channel_event_local,
                                         channel_event_stacked,
                                         channel_event_worlds,
                                         gossip_event_stacked,
                                         gossip_event_worlds, p2p_mix_event)
from ..kernels.a2cid2_mixing.ref import take_partner_rows, take_pool_rows
from .a2cid2 import A2CiD2Params, apply_mixing
from .flatbuf import (FlatLayout, ring_init, ring_init_worlds, ring_push,
                      ring_push_worlds, ring_read, ring_read_worlds)

PyTree = Any


def mix_flat(bx: jax.Array, bxt: jax.Array, eta: float, dt: jax.Array
             ) -> tuple[jax.Array, jax.Array]:
    """Pure mixing pass on flat buffers; dt broadcasts ((W,) against (W, D)
    after the trailing-axis insert, or scalar against (D,)).  A flat buffer
    is a single-leaf pytree, so this is exactly ``a2cid2.apply_mixing``."""
    return apply_mixing(bx, bxt, eta, dt)


def mix_worlds(bx: jax.Array, bxt: jax.Array, eta: jax.Array,
               dt: jax.Array) -> tuple[jax.Array, jax.Array]:
    """World-batched mixing pass: (B, W, D) buffers, (B,) per-world eta,
    (B, W) dt.  The dynamic-eta twin of ``mix_flat`` — it cannot take the
    eta == 0 shortcut (eta is traced), so baseline worlds compute
    ``a + 0 * d`` explicitly; with d finite this is exact up to the sign
    of zero, the same contract as the fused kernels' mixing tail."""
    eta32 = jnp.asarray(eta, jnp.float32)[:, None]
    c = (0.5 * (1.0 - jnp.exp(-2.0 * eta32
                              * jnp.asarray(dt, jnp.float32)))
         ).astype(bx.dtype)[:, :, None]
    d = bxt - bx
    return bx + c * d, bxt - c * d


@dataclasses.dataclass(frozen=True)
class FlatGossipEngine:
    """Fused event engine bound to a layout, A2CiD2 params, and a backend.

    backend: 'auto' (Pallas on TPU, oracle elsewhere), 'ref',
    'pallas_interpret' (tests), or 'pallas'.

    robust_clip + robust_rule engage robust aggregation on the channel
    passes (DESIGN.md §10) — the defense knob against Byzantine partners.
    None = plain m-term.  Rules (tau = robust_clip):

      'trim'  — reject the whole delta when ||m||_2 > tau (m -> 0): the
                garbage-rejection defense; corrupted events become no-ops
                while honest deltas pass untouched.
      'clip'  — rescale to m * min(1, tau / ||m||_2) (ClippedGossip-style
                norm clipping).
      'coord' — clip each coordinate to [-tau, +tau] inside the kernel.

    The norm rules cost one extra fused reduce over (x, xp) to derive the
    per-worker scale; the kernel itself stays 3 reads + 2 writes.
    """

    layout: FlatLayout
    params: A2CiD2Params
    backend: str = "auto"
    robust_clip: float | None = None
    robust_rule: str = "trim"

    def __post_init__(self):
        if self.robust_rule not in ("trim", "clip", "coord"):
            raise ValueError("robust_rule must be 'trim', 'clip', or "
                             f"'coord', got {self.robust_rule!r}")

    @classmethod
    def for_pytree(cls, tree: PyTree, params: A2CiD2Params, *,
                   stacked: bool = True, worlds: bool = False,
                   backend: str = "auto",
                   robust_clip: float | None = None,
                   robust_rule: str = "trim") -> "FlatGossipEngine":
        return cls(FlatLayout.from_pytree(tree, stacked=stacked,
                                          worlds=worlds),
                   params, backend, robust_clip, robust_rule)

    # ------------------------------------------------------------- plumbing
    def pack(self, tree: PyTree) -> jax.Array:
        return self.layout.pack(tree)

    def unpack(self, buf: jax.Array) -> PyTree:
        return self.layout.unpack(buf)

    def pack_local(self, tree: PyTree) -> jax.Array:
        return self.layout.pack_local(tree)

    def unpack_local(self, vec: jax.Array) -> PyTree:
        return self.layout.unpack_local(vec)

    def pack_worlds(self, tree: PyTree) -> jax.Array:
        return self.layout.pack_worlds(tree)

    def unpack_worlds(self, buf: jax.Array) -> PyTree:
        return self.layout.unpack_worlds(buf)

    # -------------------------------------------------------------- passes
    def mix(self, bx: jax.Array, bxt: jax.Array, dt) -> tuple[jax.Array,
                                                              jax.Array]:
        """Standalone mixing sweep (engine prologue; 2 reads + 2 writes)."""
        return mix_flat(bx, bxt, self.params.eta, dt)

    def batch(self, bx: jax.Array, bxt: jax.Array, partner: jax.Array,
              dt_next: jax.Array) -> tuple[jax.Array, jax.Array]:
        """One fused group [p2p(partner), mix(dt_next)] on (W, D) buffers."""
        p = self.params
        return gossip_event_stacked(bx, bxt, partner, dt_next, eta=p.eta,
                                    alpha=p.alpha, alpha_t=p.alpha_tilde,
                                    backend=self.backend)

    def batch_local(self, bx: jax.Array, bxt: jax.Array, xp: jax.Array,
                    dt_next) -> tuple[jax.Array, jax.Array]:
        """One fused group on per-worker (D,) vectors (SPMD path); ``xp`` is
        the partner's current flat x (e.g. from a collective permute)."""
        p = self.params
        return p2p_mix_event(bx, bxt, xp, dt_next, eta=p.eta, alpha=p.alpha,
                             alpha_t=p.alpha_tilde, backend=self.backend)

    # ---------------------------------------------- world-batched passes
    # The many-worlds replay (DESIGN.md §11) runs B worlds on (B, W, D)
    # buffers; the A2CiD2 dynamics are PER-WORLD (B,) f32 arrays ``pw =
    # (eta, alpha, alpha_t)`` passed dynamically, so one trace serves a
    # whole sweep family (baseline + accelerated + every grid point).

    def mix_batch(self, bx: jax.Array, bxt: jax.Array, dt, eta: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
        """World-batched standalone mixing sweep (batched prologue)."""
        return mix_worlds(bx, bxt, eta, dt)

    def batch_worlds(self, bx: jax.Array, bxt: jax.Array,
                     partner: jax.Array, dt_next: jax.Array, pw
                     ) -> tuple[jax.Array, jax.Array]:
        """One fused group [p2p, mix] on (B, W, D) buffers; ``pw`` the
        per-world (eta, alpha, alpha_t) arrays."""
        eta, alpha, alpha_t = pw
        return gossip_event_worlds(bx, bxt, partner, dt_next, eta, alpha,
                                   alpha_t, backend=self.backend)

    def channel_batch_worlds(self, bx: jax.Array, bxt: jax.Array,
                             xp: jax.Array, corrupt: jax.Array,
                             dt_next: jax.Array, pw, taus=None
                             ) -> tuple[jax.Array, jax.Array]:
        """World-batched channel group: pre-gathered (B, W, D) partner
        values, (B, W) corrupt offsets, per-world dynamics; the engine's
        robust rule derives the (B, W) mscale in one fused reduce.  When
        ``taus`` (a traced (B,) threshold array) is given it replaces the
        static ``robust_clip`` per world — tau = inf arms degenerate
        bitwise to the plain m-term for finite deltas (DESIGN.md §11)."""
        eta, alpha, alpha_t = pw
        mscale = self._mscale(bx, xp, corrupt, axes=2, taus=taus)
        return channel_event_worlds(bx, bxt, xp, corrupt, mscale, dt_next,
                                    eta, alpha, alpha_t,
                                    clip=self._coord_clip(),
                                    backend=self.backend)

    def channel_batch_worlds_scaled(self, bx: jax.Array, bxt: jax.Array,
                                    xp: jax.Array, corrupt: jax.Array,
                                    mscale: jax.Array, dt_next: jax.Array,
                                    pw) -> tuple[jax.Array, jax.Array,
                                                 jax.Array]:
        """World-batched channel group with an EXTERNAL (B, W) mscale (the
        self-healing defense derives it from adaptive tau + quarantine);
        also returns the kernel's (B, W) rejection mask for the trust
        loop."""
        eta, alpha, alpha_t = pw
        return channel_event_worlds(bx, bxt, xp, corrupt, mscale, dt_next,
                                    eta, alpha, alpha_t, clip=None,
                                    want_rej=True, backend=self.backend)

    def ring_init_worlds(self, bx: jax.Array, horizon: int) -> jax.Array:
        """(B, H, W, D) per-world snapshot rings seeded with ``bx``."""
        return ring_init_worlds(bx, horizon)

    def ring_push_worlds(self, ring: jax.Array, bx: jax.Array, pos
                         ) -> jax.Array:
        """Rotate every world's ring at the (shared) slot ``pos``."""
        return ring_push_worlds(ring, bx, pos)

    def partner_values_worlds(self, ring: jax.Array, bx: jax.Array,
                              partner: jax.Array, src_slot: jax.Array
                              ) -> jax.Array:
        """Per-world partner reads: fresh rows where src_slot == H, ring
        snapshots otherwise ((B, W) host-resolved indices)."""
        return ring_read_worlds(ring, bx, partner, src_slot)

    # ------------------------------------------- unreliable-channel passes
    def _coord_clip(self) -> float | None:
        return self.robust_clip if self.robust_rule == "coord" else None

    def _norm_scale(self, nrm: jax.Array, taus=None) -> jax.Array:
        """Robust scale from the delta norm (trim rejection or norm clip);
        honest/accepted deltas get exactly 1.0 (a bitwise no-op).  ``taus``
        (a traced per-world (B,) array) overrides the static threshold —
        tau = inf accepts every finite delta."""
        if taus is None:
            tau = self.robust_clip
        else:
            tau = jnp.asarray(taus, jnp.float32)
            tau = jnp.reshape(tau, tau.shape + (1,) * (nrm.ndim - tau.ndim))
        if self.robust_rule == "trim":
            return (nrm <= tau).astype(jnp.float32)
        return jnp.minimum(1.0, tau / jnp.maximum(nrm, 1e-30)
                           ).astype(jnp.float32)

    def delta_norms(self, bx: jax.Array, xp: jax.Array, corrupt: jax.Array,
                    axes) -> jax.Array:
        """f32 L2 norms of the corrupted channel deltas — one fused reduce
        (the same one ``_mscale`` runs; the defense path needs the raw
        norms for its quantile tracker)."""
        cadv = (1.0 + jnp.asarray(corrupt, jnp.float32)).astype(bx.dtype)
        cadv = jnp.reshape(cadv, cadv.shape + (1,) * (bx.ndim - cadv.ndim))
        m32 = (bx - cadv * xp).astype(jnp.float32)
        return jnp.sqrt(jnp.sum(m32 * m32, axis=axes))

    def _mscale(self, bx: jax.Array, xp: jax.Array, corrupt: jax.Array,
                axes, taus=None) -> jax.Array:
        """Per-worker robust scale — one fused reduce over the raw delta
        (the norm never materializes an extra state-sized buffer)."""
        if taus is None and (self.robust_clip is None
                             or self.robust_rule == "coord"):
            return jnp.ones(corrupt.shape, jnp.float32)
        if taus is not None and self.robust_rule == "coord":
            raise ValueError("per-world taus require a norm rule "
                             "('trim' or 'clip'), not 'coord'")
        return self._norm_scale(self.delta_norms(bx, xp, corrupt, axes),
                                taus=taus)

    def channel_batch(self, bx: jax.Array, bxt: jax.Array, xp: jax.Array,
                      corrupt: jax.Array, dt_next: jax.Array
                      ) -> tuple[jax.Array, jax.Array]:
        """One fused channel group on (W, D) buffers: ``xp`` is the
        PRE-GATHERED (W, D) partner-value buffer (fresh rows or ring-buffer
        stale snapshots — see ``partner_values``), ``corrupt`` the (W,)
        received-value multiplier offsets; the engine's
        ``robust_clip``/``robust_rule`` select the plain or robust
        m-term."""
        p = self.params
        mscale = self._mscale(bx, xp, corrupt, axes=1)
        return channel_event_stacked(bx, bxt, xp, corrupt, mscale, dt_next,
                                     eta=p.eta, alpha=p.alpha,
                                     alpha_t=p.alpha_tilde,
                                     clip=self._coord_clip(),
                                     backend=self.backend)

    def channel_batch_scaled(self, bx: jax.Array, bxt: jax.Array,
                             xp: jax.Array, corrupt: jax.Array,
                             mscale: jax.Array, dt_next: jax.Array
                             ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Serial channel group with an EXTERNAL (W,) mscale (the
        self-healing defense derives it from adaptive tau + quarantine);
        also returns the kernel's (W,) rejection mask for the trust
        loop."""
        p = self.params
        return channel_event_stacked(bx, bxt, xp, corrupt, mscale, dt_next,
                                     eta=p.eta, alpha=p.alpha,
                                     alpha_t=p.alpha_tilde, clip=None,
                                     want_rej=True, backend=self.backend)

    def channel_batch_local(self, bx: jax.Array, bxt: jax.Array,
                            xp: jax.Array, corrupt, dt_next
                            ) -> tuple[jax.Array, jax.Array]:
        """Channel group on per-worker (D,) vectors (SPMD path): scalar
        ``corrupt`` offset for this worker's received value."""
        p = self.params
        mscale = self._mscale(bx, xp, jnp.asarray(corrupt, jnp.float32),
                              axes=None)
        return channel_event_local(bx, bxt, xp, corrupt, mscale, dt_next,
                                   eta=p.eta, alpha=p.alpha,
                                   alpha_t=p.alpha_tilde,
                                   clip=self._coord_clip(),
                                   backend=self.backend)

    # --------------------------------------------------- snapshot ring API
    def ring_init(self, bx: jax.Array, horizon: int) -> jax.Array:
        """(H, W, D) snapshot ring seeded with the current buffer."""
        return ring_init(bx, horizon)

    def ring_push(self, ring: jax.Array, bx: jax.Array, pos) -> jax.Array:
        """Rotate: store the post-gradient state at slot ``pos`` (r mod H)."""
        return ring_push(ring, bx, pos)

    def partner_values(self, ring: jax.Array, bx: jax.Array,
                       partner: jax.Array, src_slot: jax.Array) -> jax.Array:
        """Resolve per-worker partner reads: fresh rows of ``bx`` where
        ``src_slot == H``, ring slots otherwise (host-resolved indices)."""
        return ring_read(ring, bx, partner, src_slot)


    # ------------------------------- sharded-replay passes (DESIGN.md §16)
    def publish_rows(self, ring, bx: jax.Array, rows: jax.Array,
                     slots: jax.Array) -> jax.Array:
        """Resolve the (B, nb) boundary rows a shard publishes into their
        (B, nb, D) channel values — fresh rows of ``bx`` at the sentinel
        slot, local snapshot-ring reads otherwise.  The PUBLISHER resolves
        staleness against its own (B, H, Ws, D) ring, so the value that
        crosses the permute ring is bitwise the one the single-device
        ``ring_read_worlds`` gather would have produced."""
        fresh = take_partner_rows(bx, rows)
        if ring is None:
            return fresh
        h = ring.shape[1]
        clamped = jnp.minimum(slots, h - 1)
        b_idx = jnp.arange(bx.shape[0])[:, None]
        stale = ring[b_idx, clamped, rows]
        return jnp.where((slots < h)[:, :, None], stale, fresh)

    def pool_partner_values(self, pool: jax.Array, hop: jax.Array,
                            pos: jax.Array, xp_local: jax.Array,
                            is_cross: jax.Array) -> jax.Array:
        """Merge permute-ring pool reads into the local partner-value
        buffer: cross rows read ``pool[hop, :, pos]`` (the block published
        by the source shard), intra/idle rows keep the shard-local gather
        ``xp_local``."""
        xp_cross = take_pool_rows(pool, hop, pos)
        return jnp.where(is_cross[:, :, None], xp_cross, xp_local)
