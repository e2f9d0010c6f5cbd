"""Flat-buffer layout for worker-stacked pytree state (see DESIGN.md).

The gossip-event loop is the repro's unit of cost: every event touches the
whole replica.  Sweeping a pytree leaf-by-leaf pays one kernel dispatch (and
one HBM round trip boundary) per leaf per event.  `FlatLayout` packs the
replica into ONE contiguous buffer with a static layout spec so an event is a
single fused sweep:

  * stacked form  — leaves (W, *shape) -> one (W, D) buffer, worker-major;
  * local form    — leaves (*shape)    -> one (D,) vector (the shard_map /
    per-worker SPMD path);
  * worlds form   — leaves (B, W, *shape) -> one (B, W, D) buffer: B
    independent worlds' replicas stacked on a leading batch axis (the
    many-worlds batched replay, DESIGN.md §11).  The layout spec is
    identical to the stacked form — the batch axis rides above it.

D is the sum of leaf sizes rounded up to a multiple of ``lane`` (128, the TPU
lane width) so the buffer tiles cleanly into Pallas blocks; padding columns
are zeros and stay zero under mixing/p2p/gradient updates (all updates are
linear with 0 fixed point), so reductions over the buffer need no masking.

Leaves are stored as ``buf_dtype``.  By default the dtype is inferred: a
uniform-dtype pytree packs at its own precision (a bf16 model's gossip
event moves bf16 bytes, not f32), mixed floating dtypes pack at the
narrowest dtype that embeds every leaf losslessly (f32, else f64).
Round-tripping is bit-exact for every floating dtype that embeds in
``buf_dtype``; anything else is rejected loudly rather than silently
truncated.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.a2cid2_mixing.ref import take_rows

PyTree = Any

LANE = 128  # TPU lane width; last-dim tiles are multiples of this

# floating dtypes whose values embed losslessly in each buffer dtype
_EXACT_EMBED = {
    jnp.dtype(jnp.float16): {jnp.dtype(jnp.float16)},
    jnp.dtype(jnp.bfloat16): {jnp.dtype(jnp.bfloat16)},
    jnp.dtype(jnp.float32): {jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16),
                             jnp.dtype(jnp.float16)},
    jnp.dtype(jnp.float64): {jnp.dtype(jnp.float64), jnp.dtype(jnp.float32),
                             jnp.dtype(jnp.bfloat16),
                             jnp.dtype(jnp.float16)},
}


def _infer_buf_dtype(dtypes: set) -> Any:
    """Narrowest buffer dtype that round-trips every leaf dtype exactly."""
    if len(dtypes) == 1:
        (d,) = dtypes
        if d in _EXACT_EMBED:
            return d
        raise TypeError(f"leaf dtype {d} is not a supported buffer dtype")
    for buf in (jnp.dtype(jnp.float32), jnp.dtype(jnp.float64)):
        if dtypes <= _EXACT_EMBED[buf]:
            return buf
    raise TypeError(f"no buffer dtype embeds leaf dtypes {sorted(map(str, dtypes))} exactly")


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Static placement of one pytree leaf inside the flat buffer."""

    offset: int              # start column in the flat axis
    size: int                # number of elements (= prod(shape))
    shape: tuple[int, ...]   # per-worker shape (no leading worker axis)
    dtype: Any               # original leaf dtype, restored on unpack


@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Static pack/unpack spec between a replica pytree and a flat buffer."""

    treedef: Any
    specs: tuple[LeafSpec, ...]
    d: int                   # padded flat width (multiple of ``lane``)
    d_real: int              # sum of leaf sizes (<= d)
    buf_dtype: Any

    # ------------------------------------------------------------ builders
    @classmethod
    def from_pytree(cls, tree: PyTree, *, stacked: bool = False,
                    worlds: bool = False, buf_dtype=None,
                    lane: int = LANE) -> "FlatLayout":
        """Build a layout from a template pytree (shapes/dtypes only — works
        on concrete arrays, ShapeDtypeStructs, and tracers alike).

        stacked=True strips a leading worker axis from every leaf;
        worlds=True strips a leading (batch, worker) axis pair (implies
        stacked — the per-replica layout is the same either way).
        buf_dtype=None infers the narrowest exact buffer dtype (see module
        docstring); passing one explicitly still validates exactness.
        """
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if buf_dtype is None:
            buf_dtype = _infer_buf_dtype({jnp.dtype(a.dtype) for a in leaves})
        buf_dtype = jnp.dtype(buf_dtype)
        lead = 2 if worlds else (1 if stacked else 0)
        specs = []
        off = 0
        for leaf in leaves:
            shape = tuple(leaf.shape[lead:])
            dtype = jnp.dtype(leaf.dtype)
            if dtype not in _EXACT_EMBED.get(buf_dtype, ()):
                raise TypeError(
                    f"leaf dtype {dtype} does not round-trip exactly "
                    f"through buffer dtype {buf_dtype}")
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            specs.append(LeafSpec(off, size, shape, dtype))
            off += size
        d = ((off + lane - 1) // lane) * lane if off else lane
        return cls(treedef=treedef, specs=tuple(specs), d=d, d_real=off,
                   buf_dtype=buf_dtype)

    # ---------------------------------------------------------------- pack
    def pack(self, tree: PyTree) -> jax.Array:
        """Stacked pytree (leaves (W, *shape)) -> (W, D) buffer."""
        leaves = self.treedef.flatten_up_to(tree)
        w = leaves[0].shape[0]
        cols = [leaf.reshape(w, spec.size).astype(self.buf_dtype)
                for leaf, spec in zip(leaves, self.specs)]
        if self.d > self.d_real:
            cols.append(jnp.zeros((w, self.d - self.d_real), self.buf_dtype))
        return jnp.concatenate(cols, axis=1)

    def unpack(self, buf: jax.Array) -> PyTree:
        """(W, D) buffer -> stacked pytree with original shapes/dtypes."""
        w = buf.shape[0]
        leaves = [
            buf[:, s.offset:s.offset + s.size]
            .astype(s.dtype).reshape((w,) + s.shape)
            for s in self.specs
        ]
        return self.treedef.unflatten(leaves)

    def pack_local(self, tree: PyTree) -> jax.Array:
        """Replica pytree (leaves (*shape)) -> (D,) vector."""
        leaves = self.treedef.flatten_up_to(tree)
        cols = [leaf.reshape(spec.size).astype(self.buf_dtype)
                for leaf, spec in zip(leaves, self.specs)]
        if self.d > self.d_real:
            cols.append(jnp.zeros((self.d - self.d_real,), self.buf_dtype))
        return jnp.concatenate(cols, axis=0)

    def unpack_local(self, vec: jax.Array) -> PyTree:
        """(D,) vector -> replica pytree with original shapes/dtypes."""
        leaves = [
            vec[s.offset:s.offset + s.size].astype(s.dtype).reshape(s.shape)
            for s in self.specs
        ]
        return self.treedef.unflatten(leaves)

    def pack_worlds(self, tree: PyTree) -> jax.Array:
        """World-batched pytree (leaves (B, W, *shape)) -> (B, W, D)."""
        leaves = self.treedef.flatten_up_to(tree)
        b, w = leaves[0].shape[:2]
        cols = [leaf.reshape(b, w, spec.size).astype(self.buf_dtype)
                for leaf, spec in zip(leaves, self.specs)]
        if self.d > self.d_real:
            cols.append(jnp.zeros((b, w, self.d - self.d_real),
                                  self.buf_dtype))
        return jnp.concatenate(cols, axis=2)

    def unpack_worlds(self, buf: jax.Array) -> PyTree:
        """(B, W, D) buffer -> world-batched pytree."""
        b, w = buf.shape[:2]
        leaves = [
            buf[:, :, s.offset:s.offset + s.size]
            .astype(s.dtype).reshape((b, w) + s.shape)
            for s in self.specs
        ]
        return self.treedef.unflatten(leaves)


# ---------------------------------------------------------------------------
# snapshot ring buffer (unreliable-channel stale reads; DESIGN.md §10)
# ---------------------------------------------------------------------------
# The delay axis of the channel subsystem reads partner values from past
# flat states.  The replay engines thread an (H, W, D) ring of the last H
# snapshots through the scan carry, rotated at each gradient tick (one
# snapshot per round — "the state at the end of round r").  Slot indices
# are schedule data resolved host-side ((r - staleness) mod H); the jit'd
# loop only gathers and scatters.

def ring_init(buf: jax.Array, horizon: int) -> jax.Array:
    """(H, W, D) ring seeded with the start state (pre-history snapshots
    equal the initial buffer; staleness clamping guarantees no slot is
    read before round r >= 1 has written it anyway)."""
    if horizon <= 0:
        raise ValueError(f"ring_init needs horizon >= 1, got {horizon}")
    return jnp.broadcast_to(buf, (horizon,) + buf.shape)


def ring_push(ring: jax.Array, buf: jax.Array, pos) -> jax.Array:
    """Overwrite slot ``pos`` (= round mod H, host-resolved) with ``buf``."""
    return ring.at[pos].set(buf)


def ring_read(ring: jax.Array, buf: jax.Array, partner: jax.Array,
              src_slot: jax.Array) -> jax.Array:
    """(W, D) partner values under staleness.

    ``src_slot[w]`` selects where worker w's read is served from: the
    sentinel ``H`` (= ring depth) means a fresh read of the partner's
    current row in ``buf``; ``0..H-1`` name a ring slot.  Two row gathers
    plus a select — no (H, W, D)-sized temporaries.
    """
    h = ring.shape[0]
    fresh = jnp.take(buf, partner, axis=0)
    stale = ring[jnp.minimum(src_slot, h - 1), partner]
    return jnp.where((src_slot < h)[:, None], stale, fresh)


# -- world-batched ring (B, H, W, D): one snapshot ring per world in the
# batched replay.  Slot/round alignment is shared across the batch (the
# batched stream aligns gradient ticks), so push positions are one scalar.

def ring_init_worlds(buf: jax.Array, horizon: int) -> jax.Array:
    """(B, H, W, D) ring seeded with each world's start buffer."""
    if horizon <= 0:
        raise ValueError(f"ring_init_worlds needs horizon >= 1, "
                         f"got {horizon}")
    return jnp.broadcast_to(buf[:, None],
                            (buf.shape[0], horizon) + buf.shape[1:])


def ring_push_worlds(ring: jax.Array, buf: jax.Array, pos) -> jax.Array:
    """Overwrite slot ``pos`` (shared scalar, = round mod H) in every
    world's ring with that world's (W, D) buffer."""
    return ring.at[:, pos].set(buf)


def ring_read_worlds(ring: jax.Array, buf: jax.Array, partner: jax.Array,
                     src_slot: jax.Array) -> jax.Array:
    """(B, W, D) partner values under staleness, per world — the batched
    twin of ``ring_read`` (vmapped over the leading world axis; ``partner``
    and ``src_slot`` are (B, W))."""
    return jax.vmap(ring_read)(ring, buf, partner, src_slot)


# -- bounded-staleness permute ring (DESIGN.md §16): the cross-shard half
# of the sharded worlds replay.  Each shard publishes the (B, nb, D) block
# of boundary rows its peers read this step; n_shards - 1 static ring hops
# of lax.ppermute stack every shard's block into an (NS, B, nb, D) pool,
# which readers index by (hop, pool_pos) — hop h holds the block published
# by shard (self - h) mod NS, matching events.ShardPlan.hop.

def ring_pool_exchange(vals: jax.Array, axis_name: str,
                       n_shards: int) -> jax.Array:
    """All-to-all the published boundary blocks along ``axis_name``.

    The pool is HOP-ordered — ``pool[h]`` is the block published by shard
    ``(self - h) mod NS``, the block an ``h``-step ring walk (shard i ->
    i+1 mod NS) would deliver — because the host shard plan
    (``events.shard_partition``) addresses cross reads by hop count, which
    is lag-friendly: a lag-L ring simply serves deeper hops from older
    snapshots.  The exchange itself is ONE fused ``all_gather`` (then a
    local hop-reindex) rather than NS-1 chained ``ppermute`` rounds: the
    values are identical exact copies either way, but a single collective
    per comm step keeps the sharding overhead flat where the chained ring
    cost grew with the mesh (measured 16ms -> 3ms per tiny-world replay at
    8 forced host shards).  The collective schedule stays compile-time
    static — nothing about it depends on which pairs cross a boundary at
    which step — so the whole scan stays ONE trace.  With one shard there
    is no collective and the pool is the local block alone.
    """
    if n_shards == 1:
        return vals[None]
    pool = jax.lax.all_gather(vals, axis_name)    # (NS, ...) by source
    me = jax.lax.axis_index(axis_name)
    hops = (me - jnp.arange(n_shards, dtype=jnp.int32)) % n_shards
    return take_rows(pool, hops)
