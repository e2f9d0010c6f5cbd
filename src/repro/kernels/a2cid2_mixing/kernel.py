"""Fused A2CiD2 gossip-event kernels (Pallas TPU).

One p2p averaging event updates BOTH local buffers from the partner's
parameters (Algo 1 lines 17-19), combined with the lazy continuous mixing
exp(dt*A).  Two fusion orders are provided (see DESIGN.md):

``mixing_p2p`` — mix THEN p2p (xp is the partner's already-mixed x):

    c   = (1 - exp(-2 eta dt)) / 2          # mixing coefficient
    xm  = x  + c * (xt - x)                 # mixed x
    xtm = xt - c * (xt - x)                 # mixed x~
    m   = xm - xp                           # pairwise difference
    out_x  = xm  - alpha   * m
    out_xt = xtm - alpha_t * m

``p2p_mixing`` / ``mixing_gossip_stacked`` — p2p THEN mix-to-next-event.
This is the order the flat-buffer event engine uses: chaining the mixing
segment that precedes event e+1 onto the p2p pass of event e makes xp the
partner's CURRENT (already-mixed) x, so no partner x~ read is needed:

    m   = x - xp
    x1  = x  - alpha   * m
    xt1 = xt - alpha_t * m
    c   = (1 - exp(-2 eta dt_next)) / 2
    out_x  = x1  + c * (xt1 - x1)
    out_xt = xt1 - c * (xt1 - x1)

Unfused, an event is 2 elementwise passes over 3 full parameter-sized
tensors (6 reads + 4 writes of HBM).  The fused kernels read x and x~
once and write both in place.

``channel_gossip_stacked`` is the unreliable-channel variant (DESIGN.md
§10): partner values arrive pre-gathered (fresh row or ring-buffer stale
snapshot — an XLA gather outside the kernel), a per-worker ``corrupt``
multiplier offset models Byzantine messages, and the robust aggregation
rides in two forms — a per-worker ``mscale`` (norm-trim rejection /
norm-clip rescale, derived by the caller from ||m|| in one fused reduce)
and a static coordinate ``clip``:

    m   = clip((x - (1 + corrupt) * xp) * mscale, +-tau)
    ...same p2p-then-mix tail as above...

``mixing_gossip_worlds`` / ``channel_gossip_worlds`` are the world-batched
forms (DESIGN.md §11): B worlds' (B, W, D) banks on one grid, with the
A2CiD2 dynamics per world, so baseline and accelerated worlds, and every
point of a sweep grid, share ONE kernel trace.  The stacked and flat
kernels are these at B = 1 (and W = 1).

Layout (DESIGN.md §17).  Every kernel runs on a (B, W, D) view and tiles
it in (W, block) slabs: all worker rows of one column range per grid
step.  A (W, D) f32 bank is tiled (4, 128) or (8, 128) in HBM, so a
row-blocked (W, D/128, 128) view would force XLA to relayout the whole
bank on every call; the (W, block) slab needs no copy, because its row
dimension is the whole worker axis.  The partner gather is then a
row-to-row copy inside VMEM (each slab holds every partner row), so a
batch reads x and x~ once, writes both, and aliases both in place: no
other grid step touches that column range.  The mixing coefficient c,
the dynamics and the channel scalars arrive as one (W, K) coefficient
block per world, computed by XLA before the call (the TPU's scalar unit
has no exp); the partner indices are scalar-prefetched into SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# bytes of one (W, block) slab of one operand (counted at f32 width)
BLOCK_BYTES = 512 * 1024

# coefficient columns
_C, _ALPHA, _ALPHA_T, _CADV, _MSCALE = range(5)


def _block_cols(w: int, d: int) -> int:
    """Lane-axis block: about BLOCK_BYTES per slab, a multiple of 128, or
    the whole of ``d`` when that is smaller (then it equals the array
    dimension, which the TPU's tiling rule also accepts)."""
    cols = max(LANE, BLOCK_BYTES // (4 * w) // LANE * LANE)
    return d if d <= cols else cols


def _coef_dtype(dtype) -> jnp.dtype:
    """Coefficients keep f64 for f64 banks (x64 runs), else f32 — each is
    cast to the bank dtype in-kernel, as a weak Python scalar would be."""
    return jnp.dtype(jnp.float64 if jnp.dtype(dtype) == jnp.float64
                     else jnp.float32)


def mixing_coef(eta, dt) -> jax.Array:
    """c = (1 - exp(-2 eta dt)) / 2 in f32, shaped like ``dt``."""
    eta = jnp.asarray(eta, jnp.float32)
    eta = jnp.reshape(eta, eta.shape + (1,) * (jnp.ndim(dt) - eta.ndim))
    return 0.5 * (1.0 - jnp.exp(-2.0 * eta * jnp.asarray(dt, jnp.float32)))


def _coefs(dtype, cols) -> jax.Array:
    """Stack per-(world, worker) coefficient columns, each broadcast to
    (B, W), into the (B, W, K) block the kernels read."""
    shape = jnp.broadcast_shapes(*(jnp.shape(c) for c in cols))
    cd = _coef_dtype(dtype)
    return jnp.stack([jnp.broadcast_to(jnp.asarray(c).astype(cd), shape)
                      for c in cols], axis=-1)


def _per_world(v, b: int) -> jax.Array:
    """(B,) or scalar per-world value -> (B, 1) for broadcasting to (B, W)."""
    return jnp.reshape(jnp.broadcast_to(jnp.asarray(v), (b,)), (b, 1))


# ---------------------------------------------------------------------------
# kernel bodies: one (W, block) slab of one world per grid step
# ---------------------------------------------------------------------------

def _col(coef, k: int, x):
    """Coefficient column k broadcast to ``x``'s slab, then cast to its
    dtype (broadcast at 32 bits: Mosaic cannot broadcast a packed 16-bit
    scalar over sublanes and lanes at once)."""
    return jnp.broadcast_to(coef[:, k:k + 1], x.shape).astype(x.dtype)


def _p2p_mix_tail(x, xt, m, coef, out_x_ref, out_xt_ref):
    """out = mix_c(x - alpha m, x~ - alpha_t m), coefficients per row."""
    x1 = x - _col(coef, _ALPHA, x) * m
    xt1 = xt - _col(coef, _ALPHA_T, x) * m
    c = _col(coef, _C, x)
    d = xt1 - x1
    out_x_ref[...] = x1 + c * d
    out_xt_ref[...] = xt1 - c * d


def _gossip_kernel(partner_ref, coef_ref, x_ref, xt_ref, out_x_ref,
                   out_xt_ref, xp_ref, *src32):
    """Plain p2p-then-mix: xp[w] = x[partner[b, w]], copied row by row
    inside VMEM (16-bit banks gather through an f32 copy of the slab, as
    Mosaic's dynamic row loads need 32-bit rows)."""
    b = pl.program_id(0)
    x = x_ref[...]
    src = x_ref
    if src32:
        src = src32[0]
        src[...] = x.astype(jnp.float32)

    def row(w, carry):
        xp_ref[pl.ds(w, 1), :] = src[pl.ds(partner_ref[b, w], 1), :]
        return carry

    jax.lax.fori_loop(0, x.shape[0], row, 0)
    xp = xp_ref[...].astype(x.dtype)
    m = x - xp           # partner==w => xp==x => m==0 (idle worker no-op)
    _p2p_mix_tail(x, xt_ref[...], m, coef_ref[...], out_x_ref, out_xt_ref)


def _channel_kernel(coef_ref, x_ref, xp_ref, xt_ref, out_x_ref, out_xt_ref,
                    *, clip):
    """Channel p2p-then-mix on pre-gathered partner values."""
    x = x_ref[...]
    coef = coef_ref[...]
    m = (x - _col(coef, _CADV, x) * xp_ref[...]) * _col(coef, _MSCALE, x)
    if clip is not None:
        m = jnp.clip(m, -clip, clip)
    _p2p_mix_tail(x, xt_ref[...], m, coef, out_x_ref, out_xt_ref)


def _mix_p2p_kernel(coef_ref, x_ref, xp_ref, xt_ref, out_x_ref,
                    out_xt_ref):
    """Mix-then-p2p order on pre-gathered partner values."""
    x = x_ref[...]
    xt = xt_ref[...]
    coef = coef_ref[...]
    c = _col(coef, _C, x)
    d = xt - x
    xm = x + c * d
    xtm = xt - c * d
    m = xm - xp_ref[...]
    out_x_ref[...] = xm - _col(coef, _ALPHA, x) * m
    out_xt_ref[...] = xtm - _col(coef, _ALPHA_T, x) * m


# ---------------------------------------------------------------------------
# one pallas_call for every kernel
# ---------------------------------------------------------------------------

def _slab_call(kernel, x, x_tilde, x_partner, coef, partner, *,
               name: str, interpret: bool, rows: int | None = None):
    """Run ``kernel`` over (B, W, D) banks in (rows, block) slabs.

    ``rows`` defaults to all of W (the plain kernel's partner gather needs
    every row of its slab).  ``coef`` is (B, W, K), or (B, 1, K) when all
    rows share it.  Operands are (coef, x, x_partner?, x_tilde) or, with a
    scalar-prefetched (B, W) ``partner``, (partner, coef, x, x_tilde).
    Outputs are (x, x~), aliased in place to the x and x~ inputs.
    """
    b_dim, w_dim, d_dim = x.shape
    rows = w_dim if rows is None else min(rows, w_dim)
    block = _block_cols(rows, d_dim)
    grid = (b_dim, pl.cdiv(w_dim, rows), pl.cdiv(d_dim, block))
    n_pre = 0 if partner is None else 1
    slab = pl.BlockSpec((None, rows, block),
                        lambda b, i, j, *_: (b, i, j))
    if coef.shape[1] == 1:
        coef_spec = pl.BlockSpec((None, 1, coef.shape[-1]),
                                 lambda b, i, j, *_: (b, 0, 0))
    else:
        coef_spec = pl.BlockSpec((None, rows, coef.shape[-1]),
                                 lambda b, i, j, *_: (b, i, 0))
    tensors = [x] if x_partner is None else [x, x_partner]
    tensors.append(x_tilde)
    scratch = []
    if partner is not None:
        wide = x.dtype.itemsize < 4
        scratch.append(pltpu.VMEM((rows, block),
                                  jnp.float32 if wide else x.dtype))
        if wide:
            scratch.append(pltpu.VMEM((rows, block), jnp.float32))
    # double-buffered operand and output slabs plus the gather scratch
    vmem_need = (2 * (len(tensors) + 2) + len(scratch)) * rows * block * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre,
        grid=grid,
        in_specs=[coef_spec] + [slab] * len(tensors),
        out_specs=[slab, slab],
        scratch_shapes=scratch,
    )
    args = ([] if partner is None else [partner.astype(jnp.int32)])
    args += [coef] + tensors
    x_pos, xt_pos = n_pre + 1, n_pre + len(tensors)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)] * 2,
        input_output_aliases={x_pos: 0, xt_pos: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=max(32 << 20, 2 * vmem_need)),
        interpret=interpret,
        name=name,
    )(*args)


def _flat_call(kernel, x, x_tilde, x_partner, coef, *, name, interpret):
    """Run a pre-gathered kernel on flat (N,) vectors.  A 128-aligned
    vector is viewed as (N/128, 128) rows — the same bytes as its 1-D
    tiling, so no copy — and tiled in BLOCK_BYTES row slabs."""
    n = x.shape[0]
    shape = (1, n // LANE, LANE) if n % LANE == 0 else (1, 1, n)
    ox, ot = _slab_call(kernel, x.reshape(shape), x_tilde.reshape(shape),
                        x_partner.reshape(shape), coef[None, None],
                        None, name=name, interpret=interpret,
                        rows=BLOCK_BYTES // (4 * LANE))
    return ox.reshape(n), ot.reshape(n)


# ---------------------------------------------------------------------------
# public kernels
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("interpret",))
def mixing_gossip_worlds(x: jax.Array, x_tilde: jax.Array,
                         partner: jax.Array, dt_next: jax.Array,
                         eta: jax.Array, alpha: jax.Array,
                         alpha_t: jax.Array, *, interpret: bool = False
                         ) -> tuple[jax.Array, jax.Array]:
    """One coalesced gossip batch over B worlds' stacked buffers at once.

    x, x_tilde: (B, W, D) same dtype; partner: (B, W) int32 (per-world
    involutions, partner[b, w] == w for idle workers); dt_next: (B, W)
    f32; eta/alpha/alpha_t: (B,) per-world dynamics — the batch mixes
    baseline (eta 0) and accelerated worlds in ONE trace, which is what
    makes a whole sweep family one compile + one dispatch.
    """
    b = x.shape[0]
    coef = _coefs(x.dtype, [mixing_coef(eta, dt_next), _per_world(alpha, b),
                            _per_world(alpha_t, b)])
    return _slab_call(_gossip_kernel, x, x_tilde, None, coef, partner,
                      name="a2cid2_gossip", interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("eta", "alpha", "alpha_t", "interpret"))
def mixing_gossip_stacked(x: jax.Array, x_tilde: jax.Array,
                          partner: jax.Array, dt_next: jax.Array, *,
                          eta: float, alpha: float, alpha_t: float,
                          interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array]:
    """One coalesced gossip batch over a worker-stacked flat buffer.

    x, x_tilde: (W, D) same dtype; partner: (W,) int32 (partner[w] == w for
    idle workers); dt_next: (W,) f32 per-worker mixing horizon to the next
    event (p2p-then-mix order, see module docstring).  The world-batched
    kernel at B = 1.
    """
    ox, ot = mixing_gossip_worlds(
        x[None], x_tilde[None], jnp.asarray(partner)[None],
        jnp.asarray(dt_next)[None], eta, alpha, alpha_t,
        interpret=interpret)
    return ox[0], ot[0]


@functools.partial(jax.jit, static_argnames=("clip", "want_rej",
                                             "interpret"))
def channel_gossip_worlds(x: jax.Array, x_tilde: jax.Array,
                          x_partner: jax.Array, corrupt: jax.Array,
                          mscale: jax.Array, dt_next: jax.Array,
                          eta: jax.Array, alpha: jax.Array,
                          alpha_t: jax.Array, *,
                          clip: float | None = None,
                          want_rej: bool = False,
                          interpret: bool = False):
    """World-batched unreliable-channel gossip batch (robust m-term).

    x, x_tilde, x_partner: (B, W, D) same dtype — partner values arrive
    PRE-GATHERED per world (fresh rows or (B, H, W, D) ring snapshots);
    corrupt, mscale, dt_next: (B, W) f32; eta/alpha/alpha_t: (B,)
    per-world dynamics; ``clip`` the static coordinate-clip rule.
    ``want_rej`` (static) adds a third output: the (B, W) f32 rejection
    mask ``mscale == 0`` the self-healing defense's trust loop consumes.
    """
    b = x.shape[0]
    mscale = jnp.asarray(mscale, jnp.float32)
    coef = _coefs(x.dtype, [
        mixing_coef(eta, dt_next), _per_world(alpha, b),
        _per_world(alpha_t, b),
        1.0 + jnp.asarray(corrupt, jnp.float32), mscale])
    out_x, out_xt = _slab_call(
        functools.partial(_channel_kernel, clip=clip), x, x_tilde,
        x_partner, coef, None, name="a2cid2_channel", interpret=interpret)
    if want_rej:
        return out_x, out_xt, (mscale == 0.0).astype(jnp.float32)
    return out_x, out_xt


@functools.partial(jax.jit,
                   static_argnames=("eta", "alpha", "alpha_t", "clip",
                                    "want_rej", "interpret"))
def channel_gossip_stacked(x: jax.Array, x_tilde: jax.Array,
                           x_partner: jax.Array, corrupt: jax.Array,
                           mscale: jax.Array, dt_next: jax.Array, *,
                           eta: float, alpha: float, alpha_t: float,
                           clip: float | None = None,
                           want_rej: bool = False,
                           interpret: bool = False):
    """One unreliable-channel gossip batch over worker-stacked buffers.

    x, x_tilde, x_partner: (W, D) same dtype; corrupt, mscale, dt_next:
    (W,) f32.  ``x_partner`` arrives PRE-GATHERED: staleness resolution
    (current row vs ring-buffer snapshot) is a data question the engine
    answers with one XLA gather before the sweep.  ``mscale`` is the
    norm-trim/clip robust scale (1.0 = accept); ``clip`` (static) is the
    in-kernel coordinate-clip rule.  ``want_rej`` (static) adds the (W,)
    f32 rejection mask ``mscale == 0``.  The world-batched kernel at
    B = 1.
    """
    outs = channel_gossip_worlds(
        x[None], x_tilde[None], x_partner[None], jnp.asarray(corrupt)[None],
        jnp.asarray(mscale)[None], jnp.asarray(dt_next)[None], eta, alpha,
        alpha_t, clip=clip, want_rej=want_rej, interpret=interpret)
    return tuple(o[0] for o in outs)


@functools.partial(jax.jit,
                   static_argnames=("eta", "alpha", "alpha_t", "interpret"))
def p2p_mixing(x: jax.Array, x_tilde: jax.Array, x_partner: jax.Array,
               dt_next: jax.Array, *, eta: float, alpha: float,
               alpha_t: float, interpret: bool = False
               ) -> tuple[jax.Array, jax.Array]:
    """Fused p2p update followed by mixing for ``dt_next`` (flat vectors).

    x, x_tilde, x_partner: (N,) same dtype; dt_next: scalar f32.  The
    channel kernel at B = W = 1 with an honest, accepted read.
    """
    coef = _coefs(x.dtype, [mixing_coef(eta, dt_next), alpha, alpha_t,
                            1.0, 1.0])
    return _flat_call(functools.partial(_channel_kernel, clip=None), x,
                      x_tilde, x_partner, coef, name="a2cid2_p2p_mixing",
                      interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("eta", "alpha", "alpha_t", "interpret"))
def mixing_p2p(x: jax.Array, x_tilde: jax.Array, x_partner: jax.Array,
               dt: jax.Array, *, eta: float, alpha: float, alpha_t: float,
               interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Apply one fused (mix, p2p) event to flat parameter arrays.

    x, x_tilde, x_partner: (N,) same dtype; dt: scalar f32.
    """
    coef = _coefs(x.dtype, [mixing_coef(eta, dt), alpha, alpha_t])
    return _flat_call(_mix_p2p_kernel, x, x_tilde, x_partner, coef,
                      name="a2cid2_mixing_p2p", interpret=interpret)
