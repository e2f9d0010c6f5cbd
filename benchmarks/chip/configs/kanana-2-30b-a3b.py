"""Kanana-2-30B-A3B, one chip's share of each layer: the system's own build
of the decoder, its data, its work counts, and a plain float32 reference
of its loss.

The program side builds ``repro.models.transformer.Model`` at the sizes of
``kanana-2-30b-a3b.json``: DeepSeek-V3 blocks (MLA without a query LoRA;
a dense layer, then layers whose router scores all 128 experts and whose
dropless expert layer computes the held experts' part), with per-layer
rematerialisation.  Each replica goes to the simulator as one flat (D,)
vector, unpacked inside the gradient.

The reference side imports nothing of the program and is written from the
sizes and the DeepSeek-V3 equations (arXiv:2412.19437 §2.1), one sequence
and one layer at a time so that it fits beside its two banks.
"""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np

# the benchmark's training rate is ``train_images_per_s``, in examples a
# second: this model's example is one packed row of seq_len tokens, as
# ResNet's is one image (tokens a second are seq_len times the rate)
UNIT = "images"

# the sizes the CPU tests run this configuration at: every width cut so
# that a whole run takes seconds, the structure (a dense layer, then MoE
# layers holding part of the routed experts, MLA without a query LoRA,
# a vocabulary padded past its size) the configuration's
TEST_SIZE = dict(hidden_size=32, intermediate_size=48,
                 moe_intermediate_size=16, kv_lora_rank=16,
                 qk_nope_head_dim=8, qk_rope_head_dim=4, qk_head_dim=12,
                 v_head_dim=8, num_attention_heads=2, num_key_value_heads=2,
                 num_hidden_layers=2, n_routed_experts=4,
                 routed_over_experts=16, first_held_expert=4,
                 vocab_size=200, max_position_embeddings=16)

EOD = 0            # the end-of-document id
DOC_MEDIAN = 512   # document lengths: log-normal, median and sigma
DOC_SIGMA = 1.0
DOC_CAP = 8192
ZIPF = 1.1
BIAS_STD = 1e-3


def seq_len(cfg: dict, traffic: dict) -> int:
    return min(traffic["seq_len"], cfg["max_position_embeddings"])


def units_per_example(cfg: dict, traffic: dict) -> int:
    """Packed rows one worker trains on in one gradient tick."""
    return traffic["batch"]


def flops_per_unit(cfg: dict, traffic: dict) -> float:
    """Model FLOPs per packed row of seq_len tokens."""
    return seq_len(cfg, traffic) * flops_per_token(cfg, traffic)


def flops_per_token(cfg: dict, traffic: dict) -> float:
    """Model FLOPs per token of this share, forward and backward (3x the
    forward), 2 per multiply-add: every projection; causal attention,
    (S + 1) / 2 keys per query; the dense and shared MLPs; the router; the
    held experts at their expected load, top-k x held / routed-over expert
    passes per token; the head.  Rematerialised recompute does not
    count."""
    d, h, s = cfg["hidden_size"], cfg["num_attention_heads"], seq_len(
        cfg, traffic)
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    r, f, e = cfg["kv_lora_rank"], cfg["intermediate_size"], cfg[
        "moe_intermediate_size"]
    proj = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v) \
        + h * v * d
    mla = 2 * proj + 2 * h * (nope + rope + v) * (s + 1) / 2
    dense = 3 * 2 * d * f
    passes = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["routed_over_experts"]
    moe = (2 * d * cfg["routed_over_experts"] + passes * 3 * 2 * d * e
           + 3 * 2 * d * cfg["n_shared_experts"] * e)
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    head = 2 * d * cfg["vocab_size"]
    return 3.0 * (cfg["num_hidden_layers"] * mla + n_dense * dense
                  + n_moe * moe + head)


# -------------------------------------------------------------------- data

def _tables(v: int):
    """The fixed successor table over ids 1..v-1 and the Zipf CDF over
    them (rank k is id k)."""
    rng = np.random.default_rng(7)
    succ = rng.integers(1, v, size=v).astype(np.int32)
    w = np.arange(1, v, dtype=np.float64) ** -ZIPF
    cdf = np.cumsum(w) / w.sum()
    return succ, cdf.astype(np.float32)


def example_batch(key: jax.Array, cfg: dict, traffic: dict) -> dict:
    """One worker's batch: ``batch`` rows of seq_len + 1 ids from seeded
    documents packed back to back.  A document's length is log-normal
    (median 512, sigma 1, at most 8,192) and its last id is EOD; within it
    each id is the fixed successor of the one before with probability 1/2,
    else a Zipf(1.1) draw (as is its first id).  Computed without a loop
    over positions: an id is the j-th successor of the last fresh draw at
    or before it, with j taken bit by bit from the successor table's
    powers."""
    b, n = traffic["batch"], seq_len(cfg, traffic) + 1
    v, total = cfg["vocab_size"], traffic["batch"] * (seq_len(cfg, traffic)
                                                      + 1)
    succ, cdf = _tables(v)
    k_len, k_keep, k_zipf = jax.random.split(key, 3)
    docs = total // 128 + 8
    length = jnp.clip(jnp.round(DOC_MEDIAN * jnp.exp(
        DOC_SIGMA * jax.random.normal(k_len, (docs,)))), 1, DOC_CAP)
    ends = jnp.cumsum(length.astype(jnp.int32)) - 1
    pos = jnp.arange(total, dtype=jnp.int32)
    at = jnp.minimum(jnp.searchsorted(ends, pos), docs - 1)
    eod = ends[at] == pos
    start = jnp.concatenate([jnp.ones((1,), bool), eod[:-1]])
    fresh = start | ~jax.random.bernoulli(k_keep, 0.5, (total,))
    last = jax.lax.cummax(jnp.where(fresh, pos, 0))
    hops = pos - last
    draw = 1 + jnp.searchsorted(jnp.asarray(cdf), jax.random.uniform(
        k_zipf, (total,)))
    ids = jnp.minimum(draw, v - 1)[last]
    table = jnp.asarray(succ)
    for bit in range(max(1, int(total).bit_length())):
        ids = jnp.where((hops >> bit) & 1 == 1, table[ids], ids)
        table = table[table]
    ids = jnp.where(eod, EOD, ids)
    return {"ids": ids.reshape(b, n).astype(jnp.int32)}


# ----------------------------------------------------------------- program

def model_config(cfg: dict):
    """The repo's ``ModelConfig`` of this share."""
    from repro.models.config import Block, MLAConfig, MoEConfig, ModelConfig

    dense = cfg["first_k_dense_replace"]
    return ModelConfig(
        name=cfg["name"], family="moe", d_model=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"],
        blocks=(((Block("mla", "dense"),), dense),
                ((Block("mla", "moe"),), cfg["num_hidden_layers"] - dense)),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        rope_theta=float(cfg["rope_theta"]), d_ff=cfg["intermediate_size"],
        mlp_act=cfg["hidden_act"],
        moe=MoEConfig(
            num_experts=cfg["routed_over_experts"],
            top_k=cfg["num_experts_per_tok"],
            d_expert=cfg["moe_intermediate_size"], shared_expert=True,
            d_shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            scoring=cfg["scoring_func"],
            routed_scale=cfg["routed_scaling_factor"], dropless=True,
            held=(cfg["first_held_expert"], cfg["n_routed_experts"])),
        mla=MLAConfig(q_lora_rank=cfg["q_lora_rank"] or 0,
                      kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"])


def init_params(key: jax.Array, shapes) -> dict:
    """Weights drawn by the benchmark in the program's pytree layout:
    the embedding N(0, 1), every matrix (stacked or per expert) N(0,
    1/fan_in), the router's selection bias N(0, 1e-3^2), norm scales 0
    (the (1 + scale) form)."""
    paths = jax.tree_util.tree_leaves_with_path(shapes)
    keys = jax.random.split(key, len(paths))
    out = []
    for (path, leaf), k in zip(paths, keys):
        name = jax.tree_util.keystr(path)
        draw = jax.random.normal(k, leaf.shape, leaf.dtype)
        if "'tok'" in name:
            out.append(draw)
        elif "router_bias" in name:
            out.append(draw * BIAS_STD)
        elif "norm" in name:
            out.append(jnp.zeros(leaf.shape, leaf.dtype))
        else:
            out.append(draw / np.sqrt(leaf.shape[-2]))
    return jax.tree_util.tree_unflatten(jax.tree.structure(shapes), out)


def program(cfg: dict, traffic: dict, batch):
    """The system under test: shapes of one replica, ``pack`` to the flat
    vector, and the per-worker ``grad_fn`` the simulator vmaps, which
    trains on ``batch(key)`` with per-layer rematerialisation."""
    from repro.core.flatbuf import FlatLayout
    from repro.models.transformer import Model

    model = Model(model_config(cfg))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layout = FlatLayout.from_pytree(shapes)

    def grad_fn(vec, key, wid):
        ids = batch(jax.random.fold_in(key, wid))["ids"]
        data = {"inputs": ids[:, :-1], "labels": ids[:, 1:]}

        def loss_fn(v):
            loss, _ = model.loss(layout.unpack_local(v), data, remat=True)
            return loss
        return jax.value_and_grad(loss_fn)(vec)

    return types.SimpleNamespace(shapes=shapes, pack=layout.pack_local,
                                 grad_fn=grad_fn, d=layout.d)


# ---------------------------------------------------------------- reference

def _rms(x, scale, eps):
    """RMSNorm with the weight stored as (1 + scale)."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1 + scale)


def _rope(x, theta):
    """Interleaved rotary embedding as the published deepseek_v3 code
    applies it with rope_interleave: pairs (2i, 2i + 1) regrouped to halves,
    then rotated by position times theta^(-2i/d)."""
    s, d = x.shape[0], x.shape[-1]
    half = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv = theta ** -(jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1).astype(x.dtype)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1).astype(x.dtype)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    rot = jnp.concatenate([-half[..., d // 2:], half[..., :d // 2]], -1)
    return half * cos + rot * sin


def _swiglu(x, w):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def _mla(x, p, cfg):
    """Latent attention of one sequence x (S, D): q = x W_q; the latent
    c = RMSNorm(x W_dkv[:, :r]) and the shared rotary key x W_dkv[:, r:];
    per head keys [c W_uk, rope key] and values c W_uv; causal softmax at
    1/sqrt(nope + rope); the heads' outputs through W_o."""
    h, nope, rope, v = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    r, theta, eps = cfg["kv_lora_rank"], float(cfg["rope_theta"]), \
        cfg["rms_norm_eps"]
    s = x.shape[0]
    q = (x @ p["w_q"]).reshape(s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kv = x @ p["w_dkv"]
    c = _rms(kv[:, :r], p["kv_norm"], eps)
    k_rope = _rope(kv[:, r:], theta)
    k = jnp.concatenate([(c @ p["w_uk"]).reshape(s, h, nope),
                         jnp.broadcast_to(k_rope[:, None], (s, h, rope))], -1)
    val = (c @ p["w_uv"]).reshape(s, h, v)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    return jnp.einsum("hqk,khd->qhd", probs, val).reshape(s, h * v) @ p["wo"]


def _moe(x, p, cfg):
    """The held experts' part and the shared experts of tokens x (T, D):
    affinities s = sigmoid(x W_r) over every routed-over expert, the top k
    of s + b chosen, weights s / sum(s) over the chosen times the scaling
    factor; each held expert applied to every token, its SwiGLU's hidden
    activations weighted by the token's weight where chosen (0
    elsewhere): no token is dropped.  The held experts are taken one at
    a time, by a scan."""
    first = cfg["first_held_expert"]
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ p["router"].astype(
        jnp.float32))
    _, chosen = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32),
                              cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / jnp.sum(w, -1, keepdims=True) * cfg["routed_scaling_factor"]

    @jax.checkpoint
    def weighted(gate, gate_w, up_w, down_w):
        # the weight scales the hidden activations, (gate h) W_down =
        # gate (h W_down): no (T, D) output is saved per expert
        h = jax.nn.silu(x @ gate_w) * (x @ up_w)
        return (gate[:, None].astype(x.dtype) * h) @ down_w

    def expert(out, e):
        i, gate_w, up_w, down_w = e
        gate = jnp.sum(jnp.where(chosen == first + i, w, 0), -1)
        return out + weighted(gate, gate_w, up_w, down_w), None
    out, _ = jax.lax.scan(expert, _swiglu(x, p["shared"]), (
        jnp.arange(cfg["n_routed_experts"]), p["moe_gate"], p["moe_up"],
        p["moe_down"]))
    return out


def reference_loss(params: dict, data: dict, cfg: dict, dtype) -> jax.Array:
    """Mean next-token cross-entropy of the share, in ``dtype``.  Each
    layer x += MLA(RMSNorm(x)), then x += FFN(RMSNorm(x)), FFN the dense
    SwiGLU in the leading layers and the MoE after; the final RMSNorm and
    the head over the vocabulary slice.  Each layer is one checkpointed
    step of a scan over the group's stacked layers, so the backward pass
    holds one layer's activations and the compiled tick one copy of each
    kind of layer; within it each sequence's attention, each expert and
    each sequence's head are rematerialised too, so that the reference
    fits beside its two banks and the stacked gradients.

    Departures from the published model: this chip's share of each layer
    (8 of 32 heads, experts 0-7 of 128 with routing over all 128, ids
    0-16,031 of the vocabulary) and 5 of its 48 layers; random weights;
    the selection bias held fixed; the two shared experts as one SwiGLU of
    width 1,536 (their sum)."""
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    ids = data["ids"]
    eps, vocab = cfg["rms_norm_eps"], cfg["vocab_size"]
    x = p["embed"]["tok"][ids[:, :-1]]
    b, s, d = x.shape

    def layer(x, lp, moe):
        y = jax.lax.map(jax.checkpoint(lambda xs: _mla(
            _rms(xs, lp["norm1"], eps), lp["mixer"], cfg)), x)
        x = x + y
        h = _rms(x, lp["norm2"], eps).reshape(b * s, d)
        ffn = _moe(h, lp["mlp"], cfg) if moe else _swiglu(h, lp["mlp"])
        return x + ffn.reshape(b, s, d)

    for g, group in enumerate(p["groups"]):
        step = jax.checkpoint(lambda x, lp, moe=g > 0: (layer(x, lp, moe),
                                                        None))
        x, _ = jax.lax.scan(step, x, group["b0"])

    @jax.checkpoint
    def nll(args):
        hs, labels = args
        logits = (_rms(hs, p["final_norm"], eps)
                  @ p["head"]["w"][:, :vocab]).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold
    return jnp.mean(jax.lax.map(nll, (x, ids[:, 1:])))
