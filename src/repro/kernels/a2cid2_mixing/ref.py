"""Pure-jnp oracle for the fused A2CiD2 gossip-event kernel."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Rows at least this wide are gathered by one dynamic slice per row.  The
# TPU compiler's time for an XLA gather grows faster than its row width
# (v5e: 0.7 s at 2^20 f32 columns, 14.5 s at 2^25, over 5 minutes at
# nano-LM's 128,404,224), while a loop of row copies compiles in about a
# second and moves the same bytes.  Narrower rows keep the gather, whose
# one op beats a W-step loop on small replicas.
WIDE_ROW = 1 << 20


def take_rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``x[idx]`` along axis 0 (whole rows), exact for any row width."""
    if math.prod(x.shape[1:]) < WIDE_ROW:
        return jnp.take(x, idx, axis=0)
    return jax.lax.map(
        lambda i: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
        jnp.asarray(idx, jnp.int32))


def take_partner_rows(x: jax.Array, partner: jax.Array) -> jax.Array:
    """Per-world partner rows of (B, W, ...) ``x``: out[b, w] =
    x[b, partner[b, w]]."""
    partner = jnp.asarray(partner, jnp.int32)
    if math.prod(x.shape[2:]) < WIDE_ROW:
        return jnp.take_along_axis(
            x, partner.reshape(partner.shape + (1,) * (x.ndim - 2)), axis=1)
    return jax.lax.map(lambda xp: take_rows(*xp), (x, partner))


def take_pool_rows(pool: jax.Array, hop: jax.Array, pos: jax.Array
                   ) -> jax.Array:
    """Rows of an (H, B, P, ...) pool: out[b, w] = pool[hop[b, w], b,
    pos[b, w]], with (B, W) ``hop`` and ``pos``."""
    hop = jnp.asarray(hop, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    if math.prod(pool.shape[3:]) < WIDE_ROW:
        return pool[hop, jnp.arange(pool.shape[1])[:, None], pos]

    def row(b, h, p):
        at = jax.lax.dynamic_index_in_dim
        return at(at(at(pool, h, 0, False), b, 0, False), p, 0, False)

    b_idx = jnp.arange(pool.shape[1], dtype=jnp.int32)
    return jax.lax.map(
        lambda bhp: jax.lax.map(lambda hp: row(bhp[0], *hp),
                                (bhp[1], bhp[2])),
        (b_idx, hop, pos))


def mixing_p2p_ref(x: jax.Array, x_tilde: jax.Array, x_partner: jax.Array,
                   dt, *, eta: float, alpha: float, alpha_t: float
                   ) -> tuple[jax.Array, jax.Array]:
    c = (0.5 * (1.0 - jnp.exp(-2.0 * eta * jnp.asarray(dt, jnp.float32)))
         ).astype(x.dtype)
    d = x_tilde - x
    xm = x + c * d
    xtm = x_tilde - c * d
    m = xm - x_partner
    return xm - alpha * m, xtm - alpha_t * m


def p2p_mixing_ref(x: jax.Array, x_tilde: jax.Array, x_partner: jax.Array,
                   dt_next, *, eta: float, alpha: float, alpha_t: float
                   ) -> tuple[jax.Array, jax.Array]:
    """p2p update then mixing for dt_next (the event-engine group order)."""
    m = x - x_partner
    x1 = x - alpha * m
    xt1 = x_tilde - alpha_t * m
    c = (0.5 * (1.0 - jnp.exp(-2.0 * eta
                              * jnp.asarray(dt_next, jnp.float32)))
         ).astype(x.dtype)
    d = xt1 - x1
    return x1 + c * d, xt1 - c * d


def mixing_gossip_stacked_ref(x: jax.Array, x_tilde: jax.Array,
                              partner: jax.Array, dt_next: jax.Array, *,
                              eta: float, alpha: float, alpha_t: float
                              ) -> tuple[jax.Array, jax.Array]:
    """Oracle for the worker-stacked fused batch: x, x~ are (W, D), partner
    (W,) an involution (partner[w]==w for idle workers), dt_next (W,)."""
    xp = take_rows(x, partner)
    m = x - xp
    x1 = x - alpha * m
    xt1 = x_tilde - alpha_t * m
    c = (0.5 * (1.0 - jnp.exp(-2.0 * eta
                              * jnp.asarray(dt_next, jnp.float32)))
         ).astype(x.dtype)[:, None]
    d = xt1 - x1
    return x1 + c * d, xt1 - c * d


def _per_world(v: jax.Array, x: jax.Array) -> jax.Array:
    """(B,) per-world parameter -> broadcastable against (B, W, D) buffers
    at the buffer dtype (mirrors how the serial kernels bind their static
    Python-float params: one conversion straight to the buffer dtype, then
    the multiply — full precision under x64, like a weak scalar)."""
    v = jnp.asarray(v).astype(x.dtype)
    return jnp.reshape(v, v.shape + (1,) * (x.ndim - v.ndim))


def mixing_gossip_worlds_ref(x: jax.Array, x_tilde: jax.Array,
                             partner: jax.Array, dt_next: jax.Array,
                             eta: jax.Array, alpha: jax.Array,
                             alpha_t: jax.Array
                             ) -> tuple[jax.Array, jax.Array]:
    """Oracle for the world-batched fused gossip batch.

    x, x~: (B, W, D); partner, dt_next: (B, W); eta, alpha, alpha_t: (B,)
    f32 per-world dynamics (the batched replay runs baseline AND
    accelerated worlds — different Prop 3.6 params — in one dispatch).
    Per world this is bitwise ``mixing_gossip_stacked_ref``: the f32 param
    pipeline matches the static-scalar binding (rounding to f32 commutes
    with the *2 in the exponent), and idle rows (partner[b, w] == w) stay
    exact no-ops.
    """
    xp = take_partner_rows(x, partner)
    m = x - xp
    x1 = x - _per_world(alpha, x) * m
    xt1 = x_tilde - _per_world(alpha_t, x) * m
    eta32 = jnp.asarray(eta, jnp.float32)[:, None]
    c = (0.5 * (1.0 - jnp.exp(-2.0 * eta32
                              * jnp.asarray(dt_next, jnp.float32)))
         ).astype(x.dtype)[:, :, None]
    d = xt1 - x1
    return x1 + c * d, xt1 - c * d


def channel_gossip_worlds_ref(x: jax.Array, x_tilde: jax.Array,
                              x_partner: jax.Array, corrupt: jax.Array,
                              mscale: jax.Array, dt_next: jax.Array,
                              eta: jax.Array, alpha: jax.Array,
                              alpha_t: jax.Array, *,
                              clip: float | None = None,
                              want_rej: bool = False):
    """Oracle for the world-batched unreliable-channel batch: (B, W, D)
    buffers with PRE-GATHERED partner values (fresh rows or per-world
    ring-buffer snapshots), (B, W) ``corrupt``/``mscale``/``dt_next``, and
    (B,) per-world dynamics; ``clip`` is the static coordinate-clip rule.
    ``want_rej`` adds the (B, W) f32 rejection mask (``mscale == 0``) as a
    third output for the self-healing trust loop.
    """
    m = _robust_m(x, x_partner, corrupt, mscale, clip)
    x1 = x - _per_world(alpha, x) * m
    xt1 = x_tilde - _per_world(alpha_t, x) * m
    eta32 = jnp.asarray(eta, jnp.float32)[:, None]
    c = (0.5 * (1.0 - jnp.exp(-2.0 * eta32
                              * jnp.asarray(dt_next, jnp.float32)))
         ).astype(x.dtype)[:, :, None]
    d = xt1 - x1
    if want_rej:
        rej = (jnp.asarray(mscale, jnp.float32) == 0.0).astype(jnp.float32)
        return x1 + c * d, xt1 - c * d, rej
    return x1 + c * d, xt1 - c * d


def _robust_m(x: jax.Array, x_partner: jax.Array, corrupt: jax.Array,
              mscale: jax.Array | None, clip: float | None) -> jax.Array:
    """Channel m-term: corrupted received value, robustly aggregated.

    ``corrupt`` is the multiplier OFFSET on the received partner value
    (honest = 0 => (1 + 0) * xp == xp bitwise, the exact no-op reduction).
    ``mscale`` is the per-worker robust scale the caller derived from the
    delta's norm (trim: 0/1 rejection; clip: tau/||m|| rescale; 1 = honest
    pass-through, also bitwise exact).  ``clip`` bounds each coordinate
    instead (the in-kernel 'coord' rule).
    """
    cadv = (1.0 + jnp.asarray(corrupt, jnp.float32)).astype(x.dtype)
    cadv = jnp.reshape(cadv, cadv.shape + (1,) * (x.ndim - cadv.ndim))
    m = x - cadv * x_partner
    if mscale is not None:
        s = jnp.asarray(mscale, jnp.float32).astype(x.dtype)
        m = m * jnp.reshape(s, s.shape + (1,) * (x.ndim - s.ndim))
    if clip is not None:
        m = jnp.clip(m, -clip, clip)
    return m


def channel_gossip_stacked_ref(x: jax.Array, x_tilde: jax.Array,
                               x_partner: jax.Array, corrupt: jax.Array,
                               mscale: jax.Array, dt_next: jax.Array, *,
                               eta: float, alpha: float, alpha_t: float,
                               clip: float | None = None,
                               want_rej: bool = False):
    """Oracle for the unreliable-channel fused batch.

    Like ``mixing_gossip_stacked_ref`` but the partner values ``x_partner``
    (W, D) arrive pre-gathered (the engine resolves fresh vs ring-buffer
    stale reads BEFORE the kernel), ``corrupt`` (W,) is the per-worker
    received-value multiplier offset, ``mscale`` (W,) the robust
    trim/clip scale on the delta's norm, and ``clip`` the in-kernel
    coordinate-clip rule.  ``want_rej`` adds the (W,) f32 rejection mask
    (``mscale == 0``) as a third output for the self-healing trust loop.
    """
    m = _robust_m(x, x_partner, corrupt, mscale, clip)
    x1 = x - alpha * m
    xt1 = x_tilde - alpha_t * m
    c = (0.5 * (1.0 - jnp.exp(-2.0 * eta
                              * jnp.asarray(dt_next, jnp.float32)))
         ).astype(x.dtype)[:, None]
    d = xt1 - x1
    if want_rej:
        rej = (jnp.asarray(mscale, jnp.float32) == 0.0).astype(jnp.float32)
        return x1 + c * d, xt1 - c * d, rej
    return x1 + c * d, xt1 - c * d


def channel_p2p_mixing_ref(x: jax.Array, x_tilde: jax.Array,
                           x_partner: jax.Array, corrupt, mscale, dt_next,
                           *, eta: float, alpha: float, alpha_t: float,
                           clip: float | None = None
                           ) -> tuple[jax.Array, jax.Array]:
    """Per-worker (D,) channel variant of ``p2p_mixing_ref`` (SPMD path):
    scalar ``corrupt`` offset, ``mscale``, and ``dt_next``."""
    m = _robust_m(x, x_partner, jnp.asarray(corrupt),
                  jnp.asarray(mscale), clip)
    x1 = x - alpha * m
    xt1 = x_tilde - alpha_t * m
    c = (0.5 * (1.0 - jnp.exp(-2.0 * eta
                              * jnp.asarray(dt_next, jnp.float32)))
         ).astype(x.dtype)
    d = xt1 - x1
    return x1 + c * d, xt1 - c * d
