"""The composable decoder stack: scan-over-layer-groups, train + decode paths.

Parameters are nested dicts; every layer group's params are stacked along a
leading `repeat` axis and the forward pass is a single `lax.scan` per group —
the HLO stays small regardless of depth, which keeps 512-device SPMD
partitioning tractable.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .. import sharding
from . import attention, rglru, ssm
from .config import Block, ModelConfig
from .layers import (apply_lm_head, apply_mlp, apply_moe, dtype_of,
                     embed_inputs, init_embedding, init_lm_head, init_mlp,
                     init_moe, init_rmsnorm, rmsnorm)

PyTree = Any


# ----------------------------------------------------------------- per block

def init_block(key, cfg: ModelConfig, block: Block) -> dict:
    dtype = dtype_of(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    p: dict = {"norm1": init_rmsnorm(cfg.d_model, dtype)}
    if block.mixer == "attn":
        p["mixer"] = attention.init_attention(k1, cfg, dtype)
    elif block.mixer == "mla":
        p["mixer"] = attention.init_mla(k1, cfg, dtype)
    elif block.mixer == "ssd":
        p["mixer"] = ssm.init_ssd(k1, cfg, dtype)
    elif block.mixer == "rglru":
        p["mixer"] = rglru.init_rglru(k1, cfg, dtype)
    else:
        raise ValueError(block.mixer)
    if block.mlp != "none":
        p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
        if block.mlp == "dense":
            p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dtype, cfg.mlp_act)
        else:  # moe / moe+dense
            p["mlp"] = init_moe(k2, cfg.d_model, cfg.moe, dtype, cfg.mlp_act)
    return p


def apply_block(p, cfg: ModelConfig, block: Block, x, positions
                ) -> tuple[jax.Array, jax.Array]:
    """Residual block; returns (x, aux_loss).  Its parts are marked for
    the tracer as ``model.<mixer>``, ``model.mlp`` (a dense MLP) or the
    MoE's ``model.router``/``.experts``/``.shared``, each norm in the
    scope of the part it feeds."""
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope(f"model.{block.mixer}"):
        h = rmsnorm(x, p["norm1"], cfg.norm_eps)
        if block.mixer == "attn":
            h = attention.apply_attention(p["mixer"], cfg, h, positions,
                                          block.window)
        elif block.mixer == "mla":
            h = attention.apply_mla(p["mixer"], cfg, h, positions,
                                    block.window)
        elif block.mixer == "ssd":
            h = ssm.apply_ssd(p["mixer"], cfg, h)
        elif block.mixer == "rglru":
            h = rglru.apply_rglru(p["mixer"], cfg, h)
    x = x + h
    if block.mlp == "dense":
        with jax.named_scope("model.mlp"):
            h = apply_mlp(p["mlp"], rmsnorm(x, p["norm2"], cfg.norm_eps),
                          cfg.mlp_act)
        x = x + h
    elif block.mlp != "none":
        with jax.named_scope("model.router"):
            h = rmsnorm(x, p["norm2"], cfg.norm_eps)
        h, aux = apply_moe(p["mlp"], h, cfg.moe, cfg.mlp_act)
        x = x + h
    return x, aux


def init_block_cache(cfg: ModelConfig, block: Block, batch: int, length: int,
                     dtype) -> dict:
    if block.mixer == "attn":
        return attention.init_attn_cache(cfg, batch, length, block.window,
                                         dtype)
    if block.mixer == "mla":
        return attention.init_mla_cache(cfg, batch, length, block.window,
                                        dtype)
    if block.mixer == "ssd":
        return ssm.init_ssd_cache(cfg, batch, dtype)
    if block.mixer == "rglru":
        return rglru.init_rglru_cache(cfg, batch, dtype)
    raise ValueError(block.mixer)


def decode_block(p, cfg: ModelConfig, block: Block, x, pos, cache
                 ) -> tuple[jax.Array, dict]:
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if block.mixer == "attn":
        h, cache = attention.decode_attention(p["mixer"], cfg, h, pos, cache,
                                              block.window)
    elif block.mixer == "mla":
        h, cache = attention.decode_mla(p["mixer"], cfg, h, pos, cache,
                                        block.window)
    elif block.mixer == "ssd":
        h, cache = ssm.decode_ssd(p["mixer"], cfg, h, pos, cache)
    elif block.mixer == "rglru":
        h, cache = rglru.decode_rglru(p["mixer"], cfg, h, pos, cache)
    x = x + h
    if block.mlp != "none":
        h = rmsnorm(x, p["norm2"], cfg.norm_eps)
        if block.mlp == "dense":
            h = apply_mlp(p["mlp"], h, cfg.mlp_act)
        else:
            h, _ = apply_moe(p["mlp"], h, cfg.moe, cfg.mlp_act)
        x = x + h
    return x, cache


# --------------------------------------------------------------------- model

@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        cfg.validate()
        dtype = dtype_of(cfg.param_dtype)
        k_embed, k_head, k_mtp, *k_groups = jax.random.split(
            key, 3 + len(cfg.blocks))
        params: dict = {
            "embed": init_embedding(k_embed, cfg, dtype),
            "final_norm": init_rmsnorm(cfg.d_model, dtype),
            "head": init_lm_head(k_head, cfg, dtype),
            "groups": [],
        }
        for (unit, repeat), kg in zip(cfg.blocks, k_groups):
            keys = jax.random.split(kg, repeat)

            def init_unit(k):
                uks = jax.random.split(k, len(unit))
                return {f"b{i}": init_block(uk, cfg, b)
                        for i, (uk, b) in enumerate(zip(uks, unit))}

            params["groups"].append(jax.vmap(init_unit)(keys))
        if cfg.mtp:
            from .layers import dense_init
            km1, km2 = jax.random.split(k_mtp)
            params["mtp"] = {
                "proj": dense_init(km1, 2 * cfg.d_model,
                                   (2 * cfg.d_model, cfg.d_model), dtype),
                "norm": init_rmsnorm(2 * cfg.d_model, dtype),
                "block": init_block(km2, cfg,
                                    Block(mixer="attn", mlp="dense")
                                    if cfg.d_ff else cfg.all_blocks()[0]),
            }
        return params

    # --------------------------------------------------------------- forward
    def forward(self, params: dict, inputs: jax.Array, *, remat: bool = False
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """inputs: tokens (B,S) int32 or embeddings (B,S,D).

        Returns (logits, aux_loss, final_hidden)."""
        cfg = self.cfg
        with jax.named_scope("model.embed"):
            x = embed_inputs(params["embed"], cfg, inputs)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        aux_total = jnp.zeros((), jnp.float32)
        # the scan carry (residual stream) is what backward saves per layer —
        # sharding it makes the saved stack 1/TP of the naive size; "embed"
        # (Megatron-SP-style) gathers x per block, "seq" gathers only k/v at
        # attention (see EXPERIMENTS.md §Perf for the measured trade-off)
        carry_axes = {"embed": ("batch", None, "act_embed"),
                      "seq": ("batch", "seq", None),
                      "none": ("batch", None, None)}[cfg.carry_shard]
        x = sharding.hint(x, *carry_axes)

        for (unit, repeat), group_p in zip(cfg.blocks, params["groups"]):

            def unit_fn(x, layer_p, unit=unit):
                aux = jnp.zeros((), jnp.float32)
                for i, b in enumerate(unit):
                    x, a = apply_block(layer_p[f"b{i}"], cfg, b, x, positions)
                    aux = aux + a
                x = sharding.hint(x, *carry_axes)
                return x, aux

            if remat:
                unit_fn = jax.checkpoint(
                    unit_fn, policy=jax.checkpoint_policies.nothing_saveable)
            x, auxs = jax.lax.scan(lambda c, p_: unit_fn(c, p_), x, group_p)
            aux_total = aux_total + jnp.sum(auxs)
        with jax.named_scope("model.head"):
            x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
            logits = apply_lm_head(params["head"], params["embed"], cfg, x)
        return logits, aux_total, x

    def _log_probs(self, logits: jax.Array) -> jax.Array:
        """log_softmax over the vocabulary, in f32: the padding columns
        past ``vocab_size`` (ids no token has) take no probability."""
        cfg = self.cfg
        logits = logits.astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:
            real = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
            logits = jnp.where(real, logits, attention.NEG_INF)
        return jax.nn.log_softmax(logits, axis=-1)

    # ------------------------------------------------------------------ loss
    def loss(self, params: dict, batch: dict, *, remat: bool = False
             ) -> tuple[jax.Array, dict]:
        """batch: {"inputs": tokens/embeddings, "labels": (B,S) or (B,S,C)}."""
        cfg = self.cfg
        logits, aux, h = self.forward(params, batch["inputs"], remat=remat)
        labels = batch["labels"]
        B, S = labels.shape[:2]
        C = cfg.num_codebooks
        logits = logits.reshape(B, S, C, cfg.padded_vocab)
        if labels.ndim == 2:
            labels = labels[..., None]
        with jax.named_scope("model.head"):
            lp = self._log_probs(logits)
            ce = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
            loss = jnp.mean(ce)
        metrics = {"ce": loss, "aux": aux}
        if cfg.mtp and cfg.input_mode == "tokens":
            mtp_loss = self._mtp_loss(params, batch, h)
            metrics["mtp"] = mtp_loss
            loss = loss + 0.3 * mtp_loss
        return loss + aux, metrics

    def _mtp_loss(self, params, batch, h):
        """DeepSeek-V3 multi-token prediction: depth-1 extra block predicting
        token t+2 from [h_t ; emb(tok_{t+1})]."""
        cfg = self.cfg
        tok = batch["inputs"]
        B, S = tok.shape
        emb_next = jnp.take(params["embed"]["tok"], tok[:, 1:], axis=0)
        hh = jnp.concatenate([h[:, :-1], emb_next.astype(h.dtype)], axis=-1)
        hh = rmsnorm(hh, params["mtp"]["norm"], cfg.norm_eps)
        hh = hh @ params["mtp"]["proj"]
        positions = jnp.broadcast_to(jnp.arange(S - 1, dtype=jnp.int32),
                                     (B, S - 1))
        block = (Block(mixer="attn", mlp="dense") if cfg.d_ff
                 else cfg.all_blocks()[0])
        hh, _ = apply_block(params["mtp"]["block"], cfg, block, hh, positions)
        logits = apply_lm_head(params["head"], params["embed"], cfg, hh)
        logits = logits.reshape(B, S - 1, cfg.num_codebooks, cfg.padded_vocab)
        labels = batch["labels"]
        if labels.ndim == 2:
            labels = labels[..., None]
        # labels are already inputs shifted by 1 => use labels shifted by 1
        tgt = labels[:, 1:]
        lp = self._log_probs(logits)
        ce = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
        return jnp.mean(ce)

    # ---------------------------------------------------------------- decode
    def init_cache(self, batch: int, length: int, dtype=None) -> list:
        cfg = self.cfg
        dtype = dtype or dtype_of(cfg.compute_dtype)
        caches = []
        for (unit, repeat) in cfg.blocks:
            def one(_):
                return {f"b{i}": init_block_cache(cfg, b, batch, length, dtype)
                        for i, b in enumerate(unit)}
            stacked = jax.vmap(one)(jnp.arange(repeat))
            caches.append(stacked)
        return caches

    def decode_step(self, params: dict, inputs: jax.Array, pos: jax.Array,
                    caches: list) -> tuple[jax.Array, list]:
        """inputs: tokens (B,1) or embeddings (B,1,D); pos scalar int32 or
        (B,) per-sequence positions (continuous batching: each slot decodes
        at its own offset — RoPE, cache index, and visibility mask are all
        per-sequence).

        Returns (logits (B,1,V*C), new caches)."""
        cfg = self.cfg
        x = embed_inputs(params["embed"], cfg, inputs)
        new_caches = []
        for (unit, repeat), group_p, cache in zip(cfg.blocks, params["groups"],
                                                  caches):

            def unit_fn(x, pc):
                layer_p, c = pc
                new_c = {}
                for i, b in enumerate(unit):
                    x, nc = decode_block(layer_p[f"b{i}"], cfg, b, x, pos,
                                         c[f"b{i}"])
                    new_c[f"b{i}"] = nc
                return x, new_c

            x, nc = jax.lax.scan(unit_fn, x, (group_p, cache))
            new_caches.append(nc)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = apply_lm_head(params["head"], params["embed"], cfg, x)
        return logits, new_caches

    def prefill(self, params: dict, inputs: jax.Array, caches: list,
                pos0: jax.Array = 0) -> tuple[jax.Array, list]:
        """Chunked prefill: feed a whole (B, P) prompt through the decode
        path in ONE dispatch — a ``lax.scan`` over ``decode_step`` instead
        of P separate device round-trips.  Scanning the decode path (rather
        than running ``forward`` and scattering K/V) keeps prefill exact for
        every mixer family: ssd/rglru carry recurrent caches whose decode
        recurrence IS the definition the full-sequence kernels re-derive.

        inputs: tokens (B, P) or embeddings (B, P, D); positions are
        ``pos0 .. pos0 + P - 1``.  Returns (logits (B,1,V*C) at the LAST
        position, filled caches) — exactly what step ``P - 1`` of the
        token-by-token loop returned.
        """
        P = inputs.shape[1]

        def body(c, t):
            tok = jax.lax.dynamic_slice_in_dim(inputs, t, 1, axis=1)
            _, c = self.decode_step(params, tok, pos0 + t, c)
            return c, None

        if P > 1:
            caches, _ = jax.lax.scan(body, caches,
                                     jnp.arange(P - 1, dtype=jnp.int32))
        return self.decode_step(params, inputs[:, P - 1:P],
                                pos0 + jnp.int32(P - 1), caches)

    # ------------------------------------------------------------------ misc
    def param_count(self, params: dict) -> int:
        return sum(int(p.size) for p in jax.tree.leaves(params))
