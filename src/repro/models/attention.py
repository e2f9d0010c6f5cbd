"""Attention mixers: GQA (RoPE, qk-norm, sliding window) and MLA (DeepSeek-V3).

Two entry points per mixer:
  * ``apply_*``        — full-sequence training/prefill forward
  * ``decode_*``       — single-token decode against a KV cache
Caches for windowed attention are ring buffers of size ``window`` (the
long_500k sub-quadratic carve-out: memory O(window), compute O(window)/token).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import sharding
from .config import MLAConfig, ModelConfig
from .layers import dense_init, init_rmsnorm, rmsnorm

NEG_INF = -1e30


# --------------------------------------------------------------------- RoPE

def rope_freqs(head_dim: int, theta: float, fraction: float = 1.0):
    rot = int(head_dim * fraction) // 2 * 2
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float32) / rot))
    return rot, jnp.asarray(inv)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               fraction: float = 1.0) -> jax.Array:
    """x: (..., S, n_heads, head_dim) or (..., S, head_dim); positions (..., S)."""
    hd = x.shape[-1]
    rot, inv = rope_freqs(hd, theta, fraction)
    if rot == 0:
        return x
    ang = positions[..., None].astype(jnp.float32) * inv      # (..., S, rot/2)
    # angles/trig in f32 (positions up to 512k), rotation in the input dtype:
    # upcasting x here makes XLA rewrite convert(x@W) into f32 dots and push
    # an f32 convert onto the sharded residual carry, which then all-gathers
    # at 2x bytes throughout the backward pass (EXPERIMENTS.md §Perf B)
    cos = jnp.cos(ang).astype(x.dtype)
    sin = jnp.sin(ang).astype(x.dtype)
    if x.ndim == cos.ndim + 1:                                 # head axis present
        cos, sin = cos[..., None, :], sin[..., None, :]
    xr = x[..., :rot]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    out = out.reshape(xr.shape)
    return jnp.concatenate([out, x[..., rot:]], axis=-1)


# ---------------------------------------------------------------------- GQA

def init_attention(key, cfg: ModelConfig, dtype):
    hd = cfg.resolved_head_dim
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], D, (D, H * hd), dtype),
        "wk": dense_init(ks[1], D, (D, KV * hd), dtype),
        "wv": dense_init(ks[2], D, (D, KV * hd), dtype),
        "wo": dense_init(ks[3], H * hd, (H * hd, D), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def _qkv(p, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    B, S, D = x.shape
    hd, H, KV = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    q = sharding.hint(q, "batch", None, "heads", None)
    k = sharding.hint(k, "batch", None, "heads", None)
    v = sharding.hint(v, "batch", None, "heads", None)
    return q, k, v


def _sdpa(q, k, v, mask, cfg: ModelConfig):
    """q (B,S,H,hd), k/v (B,T,KV,hd), boolean mask (S,T) or (B,S,T).

    k/v are broadcast to H heads so every tensor keeps a plain H axis —
    splitting the sharded H axis into (KV, G) makes the SPMD partitioner
    fall back to full rematerialization (replicating S x T logits).  XLA
    fuses the broadcast into the dots, so no extra HBM traffic materializes.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    if KV != H and S == 1:
        # decode: grouped-query einsum — materializing the G-fold broadcast
        # of the KV cache would multiply decode HBM traffic by G (no sharded
        # axis is reshaped here, so the train-time partitioner hazard that
        # motivates the broadcast below does not apply at S == 1)
        G = H // KV
        qg = q.reshape(B, 1, KV, G, hd)
        logits = jnp.einsum("bskgh,btkh->bkgst", qg, k)
        logits = sharding.hint_any(
            logits, ("batch", None, None, None, "seq"))
        logits = logits.astype(jnp.float32) / np.sqrt(hd)
        if mask.ndim == 2:
            mask = mask[None]
        logits = jnp.where(mask[:, None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        probs = sharding.hint_any(
            probs, ("batch", None, None, None, "seq"))
        out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
        return out.reshape(B, 1, H * v.shape[-1])
    if KV != H:
        G = H // KV
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    # dot in the activation dtype, softmax in f32: an f32-output qk dot
    # makes its backward upcast k (and transitively the sharded residual
    # carry) to f32, doubling every activation all-gather in the backward
    # pass (EXPERIMENTS.md §Perf B)
    logits = jnp.einsum("bshd,bthd->bhst", q, k)
    # training/prefill: prefer head-sharding; archs whose head count does not
    # divide the model axis (yi 56H, qwen3-14b 40H, musicgen 24H) shard the
    # query sequence instead.  decode (S==1): keep the CACHE-resident layout
    # (kv/T sharded over "model") — otherwise the partitioner reshards the
    # whole KV cache to head-sharded every token (~86 GB/device of all-gather
    # on qwen3-14b decode_32k; see EXPERIMENTS.md §Perf).
    if S == 1:
        cands = (("batch", None, None, "seq"),
                 ("batch", "heads", None, None))
        probs_cands = cands
    elif sharding.is_forward_only():
        # prefill: head-sharding preferred, q-seq fallback for head counts
        # that don't divide the model axis (musicgen 24H, yi 56H) — halves
        # the replicated S x T score footprint
        cands = (("batch", "heads", None, None),
                 ("batch", None, "seq", None))
        probs_cands = cands
    else:
        # training: constrain only when heads divide; a forced q-seq
        # sharding fights the partitioner's partial head sharding in the
        # backward dots and triggers f32 full-remat gathers (yi-34b:
        # +3.5 TB/device/step — EXPERIMENTS.md §Perf B)
        cands = (("batch", "heads", None, None),)
        probs_cands = cands
    logits = sharding.hint_any(logits, *cands)
    logits = logits.astype(jnp.float32) / np.sqrt(hd)
    if mask.ndim == 2:
        mask = mask[None]
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    probs = sharding.hint_any(probs, *probs_cands)
    out = jnp.einsum("bhst,bthd->bshd", probs, v)
    return out.reshape(B, S, H * v.shape[-1])  # v dim may differ (MLA)


def causal_mask(S: int, window: int | None = None) -> jax.Array:
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    m = j <= i
    if window is not None:
        m &= j > i - window
    return m


def apply_attention(p, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                    window: int | None = None) -> jax.Array:
    q, k, v = _qkv(p, cfg, x, positions)
    if cfg.attention_impl == "pallas":
        from ..kernels.flash_attention.ops import flash_attention
        B, S, H, hd = q.shape
        out = flash_attention(q, k, v, causal=True, window=window,
                              force_pallas=True,
                              interpret=jax.default_backend() != "tpu")
        out = out.reshape(B, S, H * hd)
    else:
        mask = causal_mask(x.shape[1], window)
        out = _sdpa(q, k, v, mask, cfg)
    return sharding.hint(out @ p["wo"], "batch", None, None)


# ------------------------------------------------------------- GQA decoding

def decode_positions(pos: jax.Array, batch: int) -> jax.Array:
    """(B,) per-slot positions from a scalar or already-(B,) ``pos``."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (batch,))


def _cache_slots(pos_vec: jax.Array, size: int, window: int | None
                 ) -> jax.Array:
    """(B,) ring/dense cache slot per sequence.  The dense slot is clamped
    to the last entry past capacity — the same semantics the old scalar
    ``dynamic_update_slice`` start-index clamping gave."""
    return pos_vec % size if window else jnp.minimum(pos_vec, size - 1)


def _update_slot(cache: jax.Array, update: jax.Array, slot: jax.Array
                 ) -> jax.Array:
    """Write ``update[b]`` at row ``slot[b]`` of every sequence's cache:
    cache (B, size, ...), update (B, 1, ...), slot (B,)."""
    def one(c, u, s):
        return jax.lax.dynamic_update_slice(c, u, (s,) + (0,) * (c.ndim - 1))
    return jax.vmap(one)(cache, update, slot)


def _slot_mask(spos: jax.Array, pos_vec: jax.Array, window: int | None
               ) -> jax.Array:
    """(B, 1, size) visibility mask from per-sequence slot positions."""
    mask = (spos >= 0) & (spos <= pos_vec[:, None])
    if window:
        mask &= spos > pos_vec[:, None] - window
    return mask[:, None, :]


def init_attn_cache(cfg: ModelConfig, batch: int, length: int,
                    window: int | None, dtype) -> dict:
    """length = full context for dense cache; ring of size window if windowed."""
    size = min(length, window) if window else length
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, size, KV, hd), dtype),
        "v": jnp.zeros((batch, size, KV, hd), dtype),
        # absolute position held by each sequence's slots (-1 = empty);
        # per-sequence so continuous-batching slots decode at their own pos
        "slot_pos": jnp.full((batch, size), -1, jnp.int32),
    }


def decode_attention(p, cfg: ModelConfig, x: jax.Array, pos: jax.Array,
                     cache: dict, window: int | None = None
                     ) -> tuple[jax.Array, dict]:
    """x (B, 1, D), pos scalar int32 or (B,) per-slot positions —
    returns (out (B,1,D), new cache)."""
    B = x.shape[0]
    pos_vec = decode_positions(pos, B)
    q, k, v = _qkv(p, cfg, x, pos_vec[:, None])     # k rope'd at absolute pos
    size = cache["k"].shape[1]
    slot = _cache_slots(pos_vec, size, window)
    ck = _update_slot(cache["k"], k, slot)
    cv = _update_slot(cache["v"], v, slot)
    spos = _update_slot(cache["slot_pos"], pos_vec[:, None], slot)
    out = _sdpa(q, ck, cv, _slot_mask(spos, pos_vec, window), cfg)
    out = sharding.hint(out @ p["wo"], "batch", None, None)
    return out, {"k": ck, "v": cv, "slot_pos": spos}


# ---------------------------------------------------------------------- MLA

def init_mla(key, cfg: ModelConfig, dtype):
    m: MLAConfig = cfg.mla
    D, H = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    if m.q_lora_rank:
        q = {"w_dq": dense_init(ks[0], D, (D, m.q_lora_rank), dtype),
             "q_norm": init_rmsnorm(m.q_lora_rank, dtype),
             "w_uq": dense_init(ks[1], m.q_lora_rank,
                                (m.q_lora_rank, H * qk), dtype)}
    else:
        q = {"w_q": dense_init(ks[0], D, (D, H * qk), dtype)}
    return {
        **q,
        "w_dkv": dense_init(ks[2], D,
                            (D, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_norm": init_rmsnorm(m.kv_lora_rank, dtype),
        "w_uk": dense_init(ks[3], m.kv_lora_rank,
                           (m.kv_lora_rank, H * m.qk_nope_head_dim), dtype),
        "w_uv": dense_init(ks[4], m.kv_lora_rank,
                           (m.kv_lora_rank, H * m.v_head_dim), dtype),
        "wo": dense_init(ks[5], H * m.v_head_dim,
                         (H * m.v_head_dim, D), dtype),
    }


def _mla_qkv(p, cfg: ModelConfig, x, positions):
    """Returns q (B,S,H,qk), latent c (B,S,rank), k_rope (B,S,rope)."""
    m: MLAConfig = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank:
        q = rmsnorm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps) @ p["w_uq"]
    else:
        q = x @ p["w_q"]
    q = q.reshape(B, S, H, qk)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    dkv = x @ p["w_dkv"]
    c = rmsnorm(dkv[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(dkv[..., m.kv_lora_rank:], positions, cfg.rope_theta)
    q = sharding.hint(q, "batch", None, "heads", None)
    return q, c, k_rope


def _mla_expand_kv(p, cfg: ModelConfig, c, k_rope):
    """Up-project cached latents to per-head K, V."""
    m: MLAConfig = cfg.mla
    B, T, _ = c.shape
    H = cfg.num_heads
    k_nope = (c @ p["w_uk"]).reshape(B, T, H, m.qk_nope_head_dim)
    v = (c @ p["w_uv"]).reshape(B, T, H, m.v_head_dim)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, T, H, m.qk_rope_head_dim))], axis=-1)
    k = sharding.hint(k, "batch", None, "heads", None)
    v = sharding.hint(v, "batch", None, "heads", None)
    return k, v


def apply_mla(p, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
              window: int | None = None) -> jax.Array:
    q, c, k_rope = _mla_qkv(p, cfg, x, positions)
    k, v = _mla_expand_kv(p, cfg, c, k_rope)
    mask = causal_mask(x.shape[1], window)
    out = _sdpa(q, k, v, mask, cfg)
    return sharding.hint(out @ p["wo"], "batch", None, None)


def init_mla_cache(cfg: ModelConfig, batch: int, length: int,
                   window: int | None, dtype) -> dict:
    m: MLAConfig = cfg.mla
    size = min(length, window) if window else length
    return {
        "c": jnp.zeros((batch, size, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, size, m.qk_rope_head_dim), dtype),
        "slot_pos": jnp.full((batch, size), -1, jnp.int32),
    }


def decode_mla(p, cfg: ModelConfig, x: jax.Array, pos: jax.Array,
               cache: dict, window: int | None = None
               ) -> tuple[jax.Array, dict]:
    B = x.shape[0]
    pos_vec = decode_positions(pos, B)
    q, c, k_rope = _mla_qkv(p, cfg, x, pos_vec[:, None])
    size = cache["c"].shape[1]
    slot = _cache_slots(pos_vec, size, window)
    cc = _update_slot(cache["c"], c, slot)
    cr = _update_slot(cache["k_rope"], k_rope, slot)
    spos = _update_slot(cache["slot_pos"], pos_vec[:, None], slot)
    k, v = _mla_expand_kv(p, cfg, cc, cr)
    out = _sdpa(q, k, v, _slot_mask(spos, pos_vec, window), cfg)
    out = sharding.hint(out @ p["wo"], "batch", None, None)
    return out, {"c": cc, "k_rope": cr, "slot_pos": spos}
