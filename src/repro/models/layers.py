"""Common layers: norms, MLPs, embeddings, MoE — pure JAX (no flax)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import custom_batching

from .. import sharding
from .config import ModelConfig, MoEConfig


def dtype_of(name: str):
    return jnp.dtype(name)


# ------------------------------------------------------------------- inits

def dense_init(key, fan_in: int, shape, dtype) -> jax.Array:
    scale = 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def embed_init(key, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# -------------------------------------------------------------------- norms

@jax.custom_vjp
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with a hand-written VJP.

    Forward: f32 accumulation of x.x as a dot (preferred_element_type) — an
    explicit x.astype(f32) gets hoisted by XLA out of the backward layer scan
    into a full f32 copy of the saved residual stack.
    Backward: custom VJP keeping every (B,S,D) cotangent in the input dtype —
    the autodiff rule of the f32-output variance dot produces f32 cotangents
    for x, which the partitioner then all-gathers at 2x bytes throughout the
    backward pass (measured on yi-34b; EXPERIMENTS.md §Perf B)."""
    out, _ = _rmsnorm_fwd(x, scale, eps)
    return out


def _rms_inv(x, eps):
    sq = jnp.einsum("...d,...d->...", x, x,
                    preferred_element_type=jnp.float32)
    var = sq[..., None] / x.shape[-1]
    return jax.lax.rsqrt(var + eps)


def _rmsnorm_fwd(x, scale, eps):
    dt = x.dtype
    inv = _rms_inv(x, eps).astype(dt)
    out = (x * inv) * (1.0 + scale.astype(dt))
    return out, (x, inv, scale, eps)


def _rmsnorm_bwd(res, g):
    x, inv, scale, eps = res
    dt = x.dtype
    sp = (1.0 + scale.astype(dt))
    gs = g * sp                                           # (..., D)
    # row scalar sum(g*s'*x) in f32 via a dot — no f32 (B,S,D) materializes
    dot = jnp.einsum("...d,...d->...", gs, x,
                     preferred_element_type=jnp.float32)
    coef = (dot[..., None] / x.shape[-1]).astype(dt) * (inv * inv * inv)
    gx = gs * inv - x * coef
    gscale = jnp.sum((g * x * inv).astype(jnp.float32),
                     axis=tuple(range(g.ndim - 1)))
    return gx, gscale.astype(scale.dtype), None


rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def init_rmsnorm(dim: int, dtype) -> jax.Array:
    # stored as deviation from 1 (gemma-style) for clean wd behaviour
    return jnp.zeros((dim,), dtype)


# --------------------------------------------------------------------- MLPs

def init_mlp(key, d_model: int, d_ff: int, dtype, act: str = "silu"):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(k1, d_model, (d_model, d_ff), dtype),
        "w_down": dense_init(k2, d_ff, (d_ff, d_model), dtype),
    }
    if act == "silu":  # gated (swiglu)
        p["w_gate"] = dense_init(k3, d_model, (d_model, d_ff), dtype)
    return p


def apply_mlp(p, x: jax.Array, act: str = "silu") -> jax.Array:
    up = x @ p["w_up"]
    up = sharding.hint(up, "batch", None, "ffn")
    if act == "silu":
        h = jax.nn.silu(x @ p["w_gate"]) * up
    elif act == "gelu":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(act)
    out = h @ p["w_down"]
    return sharding.hint(out, "batch", None, None)


# ---------------------------------------------------------------------- MoE

# sorted (token, choice) rows a dropless expert layer takes at a time:
# this many times the rows an even routing sends to the held experts, and
# at most DROPLESS_ROWS
DROPLESS_CAPACITY = 2.0
DROPLESS_ROWS = 16384


def init_moe(key, d_model: int, cfg: MoEConfig, dtype, act: str = "silu"):
    """The router over all ``num_experts``; expert weights of the held
    experts only (``cfg.held_range``)."""
    d_e = cfg.d_expert or d_model * 4
    n_held = cfg.held_range[1]
    keys = jax.random.split(key, 8)
    p = {
        "router": dense_init(keys[0], d_model, (d_model, cfg.num_experts),
                             jnp.float32),  # router in fp32 for stable softmax
        "moe_up": dense_init(keys[1], d_model,
                             (n_held, d_model, d_e), dtype),
        "moe_down": dense_init(keys[2], d_e,
                               (n_held, d_e, d_model), dtype),
    }
    if cfg.scoring == "sigmoid":
        # selection bias: moved by a balancing rule outside the gradient
        p["router_bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
    if act == "silu":
        p["moe_gate"] = dense_init(keys[3], d_model,
                                   (n_held, d_model, d_e), dtype)
    if cfg.shared_expert:
        d_s = cfg.d_shared or d_e
        p["shared"] = init_mlp(keys[4], d_model, d_s, dtype, act)
    if cfg.dense_d_ff:
        p["dense"] = init_mlp(keys[5], d_model, cfg.dense_d_ff, dtype, act)
    return p


def _dispatch_group(xt, topi, topw, E: int, C: int, dtype):
    """Per-group dispatch: xt (T,D), topi/topw (T,K) -> buffer (E,C,D),
    dest (T,K), keep (T,K).  Pure local ops — vmapped over data-sharded
    groups so dispatch never crosses the data shards.

    Implemented as K unique scatter-SETs of (T, D): no (T*K, D) intermediate
    (whose repeat-transpose reduce promotes to f32), no accumulation (a bf16
    scatter-ADD promotes to f32 and XLA hoists the convert onto the saved
    residual stack of the backward layer scan)."""
    T, D = xt.shape
    K = topi.shape[1]
    flat_e = topi.reshape(-1)                                  # (T*K,)
    # rank of each (token, k) within its expert via stable sort
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    rank_sorted = jnp.arange(T * K) - starts[sorted_e]
    slot = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    keep = (slot < C).reshape(T, K)                            # overflow drops
    dest = (flat_e * C + slot).reshape(T, K)
    buf = jnp.zeros((E * C + 1, D), dtype)
    for k in range(K):
        sdest = jnp.where(keep[:, k], dest[:, k], E * C + 1)   # OOB = dropped
        buf = buf.at[sdest].set(xt, mode="drop", unique_indices=True)
    return buf[: E * C].reshape(E, C, D), dest, keep


def _combine_group(out_e, dest, keep, topw, T: int, D: int, dtype):
    """K unique gathers of (T, D), weighted-summed.  Kept dests are unique;
    drops gather-fill 0 via out-of-bounds indices."""
    K = topw.shape[1]
    E_C = out_e.shape[0] * out_e.shape[1]
    flat_out = out_e.reshape(E_C, D)
    out = jnp.zeros((T, D), dtype)
    for k in range(K):
        sdest = jnp.where(keep[:, k], dest[:, k], E_C + 1)
        g = flat_out.at[sdest].get(mode="fill", fill_value=0,
                                   unique_indices=True)        # (T, D)
        out = out + g * (topw[:, k:k + 1] * keep[:, k:k + 1]).astype(dtype)
    return out


def _route(p, x: jax.Array, cfg: MoEConfig
           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing of x (B, S, D) over all ``num_experts``: (weights
    (B, S, K) f32, expert ids (B, S, K), auxiliary loss)."""
    E, K = cfg.num_experts, cfg.top_k
    if cfg.scoring == "sigmoid":
        # DeepSeek-V3: affinities s = sigmoid(x W_r) in f32 at full
        # precision (the top-k is discontinuous in them); the top k of s + b
        # are chosen, weighted by their s; the bias only selects
        s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), p["router"],
                                   precision=jax.lax.Precision.HIGHEST))
        bias = jax.lax.stop_gradient(p["router_bias"])
        _, topi = jax.lax.top_k(s + bias, K)
        topw = jnp.take_along_axis(s, topi, axis=-1)
        aux = jnp.zeros((), jnp.float32)
    elif cfg.scoring == "softmax":
        # router matmul fully in the activation dtype — any f32
        # operand/cotangent on x makes XLA hoist an f32 copy of the whole
        # saved residual stack out of the backward layer scan; softmax
        # still runs in f32 on the (small) logits tensor
        logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, K)                   # (B, S, K)
        # load-balance aux loss (Switch-style): E * sum_e f_e * p_e
        B, S = x.shape[:2]
        me = jnp.mean(probs, axis=(0, 1))                      # (E,)
        counts = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(1.0)
        aux = cfg.router_aux_weight * E * jnp.sum(
            me * counts / (B * S * K))
    else:
        raise ValueError(cfg.scoring)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)        # renormalize
    return topw * cfg.routed_scale, topi, aux


def apply_moe(p, x: jax.Array, cfg: MoEConfig, act: str = "silu"
              ) -> tuple[jax.Array, jax.Array]:
    """Top-k MoE of x (B, S, D): the routed experts' weighted part (the
    held experts' only) plus the shared expert and the parallel dense MLP.
    Returns (out, aux_loss).

    Dispatch is dropless (``cfg.dropless``, ``_dropless_experts``) or
    GShard-style by capacity: tokens are grouped by batch row (G = B);
    dispatch scatter/gather is vmapped over groups, so with the batch
    data-sharded every scatter is a *local* op — the only cross-shard
    traffic is the (G, E, C, D) buffer resharding from (G: data) to
    (E: model) at the expert einsum, which XLA lowers to an all-to-all:
    exactly the traffic a hand-written EP MoE does.
    """
    with jax.named_scope("model.router"):
        topw, topi, aux = _route(p, x, cfg)
    with jax.named_scope("model.experts"):
        if cfg.dropless:
            out = _dropless_experts(p, x, topw, topi, cfg, act)
        else:
            out = _capacity_experts(p, x, topw, topi, cfg, act)
    if "shared" in p:
        with jax.named_scope("model.shared"):
            out = out + apply_mlp(p["shared"], x, act)
    if "dense" in p:
        with jax.named_scope("model.mlp"):
            out = out + apply_mlp(p["dense"], x, act)
    return out, aux


def _capacity_experts(p, x, topw, topi, cfg: MoEConfig, act: str):
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    # per-group capacity (statistical balance within each row of S tokens)
    C = max(1, int(np.ceil(S * K / E * cfg.capacity_factor)))
    # combine weights participate in (T*K, D)-sized products — keep them in
    # the activation dtype so their cotangents don't promote those to f32
    topw = topw.astype(x.dtype)
    buf, dest, keep = jax.vmap(
        lambda xt, ti, tw: _dispatch_group(xt, ti, tw, E, C, x.dtype)
    )(x, topi, topw)                                           # (B,E,C,D)...
    buf = sharding.hint(buf, "batch", "expert", None, None)

    up = jnp.einsum("gecd,edf->gecf", buf, p["moe_up"])
    if act == "silu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["moe_gate"])) * up
    else:
        h = jax.nn.gelu(up)
    out_e = jnp.einsum("gecf,efd->gecd", h, p["moe_down"])     # (B,E,C,D)
    out_e = sharding.hint(out_e, "batch", "expert", None, None)

    return jax.vmap(
        lambda oe, de, ke, tw: _combine_group(oe, de, ke, tw, S, D, x.dtype)
    )(out_e, dest, keep, topw)                                 # (B, S, D)


def _dropless_experts(p, x, topw, topi, cfg: MoEConfig, act: str):
    """The held experts' weighted part of the output, with no token
    dropped: the (token, choice) pairs are sorted by expert, those routed
    to a held expert first, and go through ``held_experts``."""
    if act != "silu":
        raise ValueError(f"the dropless expert layer is SwiGLU, not {act}")
    shape, D = x.shape, x.shape[-1]
    xt = x.reshape(-1, D)
    K = cfg.top_k
    first, count = cfg.held_range
    local = topi.reshape(-1) - first
    group = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(group, stable=True)                    # (T*K,)
    sizes = jnp.sum(group[None, :] == jnp.arange(count)[:, None], axis=1,
                    dtype=jnp.int32)                           # (count,)
    w = topw.reshape(-1)[order].astype(x.dtype)
    rows = int(np.ceil(DROPLESS_CAPACITY * xt.shape[0] * K * count
                       / cfg.num_experts))
    out = held_experts(xt, w, order // K, sizes, p["moe_gate"],
                       p["moe_up"], p["moe_down"], rows)
    return out.reshape(shape)


# ------------------------------------------- held experts, dropless (MoE)
# The rows of the sorted (token, choice) pairs routed to held experts come
# first, ``sizes[g]`` of them for held expert g.  Each stretch of ``rows``
# rows goes through its experts' SwiGLU by ``jax.lax.ragged_dot``, native
# on the TPU, which skips the rows past the last group; the loops run over
# the stretches that hold such rows only, however many that is, so no
# routing overflows.  A stretch holds twice an even routing's rows
# (``DROPLESS_CAPACITY``), so one stretch takes a layer's rows whatever
# the seed's router sends here, and the layer's gathers, scatter-adds and
# passes over the expert weights are the same from seed to seed; only an
# overflow past it adds a stretch.  The TPU's ragged dot
# takes no batch dimension, and a loop of data-dependent length only stays
# one under vmap if the batch runs as one call: the forward and the
# backward are ``custom_vmap`` functions whose rule folds the batch (the
# workers, as the simulator vmaps the gradient) into one call, every
# element's groups one after another, and the VJP is written by hand,
# recomputing each stretch.

def _fold(axis_size, in_batched, args):
    """``held_experts``' arguments batched over ``axis_size`` elements as
    the arguments of one call, and where each element's rows went: the
    elements' tokens side by side, their groups in turn (element 0's,
    then element 1's, ...), the routed rows of every element first in
    that order and the rest after."""
    xt, w, tok, sizes, *weights = (
        a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
        for a, b in zip(args, in_batched))
    n, t = xt.shape[:2]
    routed = jnp.sum(sizes, axis=1)[:, None]                   # (n, 1)
    before = jnp.cumsum(routed, axis=0) - routed
    row, elem = jnp.arange(tok.shape[1]), jnp.arange(n)[:, None]
    pos = jnp.where(row < routed, before + row,
                    jnp.sum(routed) + elem * tok.shape[1] - before + row
                    - routed).reshape(-1)

    def rows(a):
        return jnp.zeros_like(a.reshape(-1)).at[pos].set(a.reshape(-1),
                                                         unique_indices=True)
    folded = (xt.reshape(n * t, -1), rows(w), rows(tok + elem * t),
              sizes.reshape(-1)) + tuple(
                  a.reshape((-1,) + a.shape[2:]) for a in weights)
    return folded, pos


def _folded_rows(rows, axis_size):
    """A folded call's stretch: the elements' stretches end to end."""
    return None if rows is None else rows * axis_size


def _fwd_rule(rows, axis_size, in_batched, *args):
    folded, _ = _fold(axis_size, in_batched, args)
    out = _experts(_folded_rows(rows, axis_size))[0](*folded)
    return out.reshape(axis_size, -1, out.shape[-1]), True


def _bwd_rule(rows, axis_size, in_batched, *args):
    *args, dout = args
    folded, pos = _fold(axis_size, in_batched[:-1], args)
    if not in_batched[-1]:
        dout = jnp.broadcast_to(dout, (axis_size,) + dout.shape)
    dxt, dw, *dweights = _experts(_folded_rows(rows, axis_size))[1](
        *folded, dout.reshape(-1, dout.shape[-1]))
    out = (dxt.reshape(axis_size, -1, dxt.shape[-1]),
           dw[pos].reshape(axis_size, -1)) + tuple(
               d.reshape((axis_size, -1) + d.shape[1:]) for d in dweights)
    return out, (True,) * len(out)


def _tgmm(x, dy, sizes):
    """Per group g, x_g^T dy_g: (G, K, N) from x (M, K), dy (M, N)."""
    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=(0,), rhs_group_dimensions=())
    return jax.lax.ragged_dot_general(x, dy, sizes, dims)


def _stretches(rows, tok, w, sizes):
    """Rows a stretch takes (``rows``, None for DROPLESS_ROWS), how many
    stretches hold the groups' rows, and tok and w padded to whole
    stretches."""
    rows = min(rows or DROPLESS_ROWS, DROPLESS_ROWS, tok.shape[0])
    pad = -tok.shape[0] % rows
    n = jnp.sum(sizes)
    return (rows, (n + rows - 1) // rows, jnp.pad(tok, (0, pad)),
            jnp.pad(w, (0, pad)))


def _stretch(i, rows, xt, tok, w, sizes):
    """Stretch i: its token ids, the ids its results are added at (out of
    range past the groups, so a scatter-add in ``drop`` mode skips those
    rows), its weights, which rows are the groups', the rows of xt (0 past
    the groups) and each group's rows in it."""
    lo = i * rows
    ends = jnp.cumsum(sizes)
    part = jnp.clip(ends, lo, lo + rows) - jnp.clip(ends - sizes, lo,
                                                     lo + rows)
    idx = jax.lax.dynamic_slice(tok, (lo,), (rows,))
    ws = jax.lax.dynamic_slice(w, (lo,), (rows,))
    valid = (lo + jnp.arange(rows) < ends[-1])[:, None]
    into = jnp.where(valid[:, 0], idx, xt.shape[0])
    return idx, into, ws, valid, jnp.where(valid, xt[idx], 0), part


def _experts_fwd(rows, xt, w, tok, sizes, gate, up, down):
    rows, steps, tok, w = _stretches(rows, tok, w, sizes)

    def body(i, out):
        _, into, ws, _, xr, part = _stretch(i, rows, xt, tok, w, sizes)
        h = jax.nn.silu(jax.lax.ragged_dot(xr, gate, part)) \
            * jax.lax.ragged_dot(xr, up, part)
        y = jax.lax.ragged_dot(h, down, part)
        # what the ragged dot leaves past the groups is dropped
        return out.at[into].add(y * ws[:, None], mode="drop")
    return jax.lax.fori_loop(0, steps, body, jnp.zeros_like(xt))


def _experts_bwd(rows, xt, w, tok, sizes, gate, up, down, dout):
    n_w = w.shape[0]
    rows, steps, tok, w = _stretches(rows, tok, w, sizes)

    def body(i, ct):
        dxt, dw, dgate, dup, ddown = ct
        idx, into, ws, valid, xr, part = _stretch(i, rows, xt, tok, w,
                                                  sizes)
        g = jax.lax.ragged_dot(xr, gate, part)
        u = jax.lax.ragged_dot(xr, up, part)
        sig = jax.nn.sigmoid(g)
        h = g * sig * u
        do = jnp.where(valid, dout[idx], 0)
        dy = do * ws[:, None]
        # the row's output is w h W_down: d/dw = h . (do W_down^T), and
        # d/dh = w (do W_down^T)
        dhw = jnp.where(valid, jax.lax.ragged_dot(
            do, jnp.swapaxes(down, 1, 2), part), 0)
        dh = dhw * ws[:, None]
        du = dh * g * sig
        dg = dh * u * sig * (1 + g * (1 - sig))
        dx = (jax.lax.ragged_dot(du, jnp.swapaxes(up, 1, 2), part)
              + jax.lax.ragged_dot(dg, jnp.swapaxes(gate, 1, 2), part))
        return (dxt.at[into].add(dx, mode="drop"),
                jax.lax.dynamic_update_slice(
                    dw, jnp.sum(jnp.where(valid, h * dhw, 0), -1),
                    (i * rows,)),
                dgate + _tgmm(xr, dg, part), dup + _tgmm(xr, du, part),
                ddown + _tgmm(h, dy, part))
    ct = (jnp.zeros_like(xt), jnp.zeros_like(w), jnp.zeros_like(gate),
          jnp.zeros_like(up), jnp.zeros_like(down))
    dxt, dw, dgate, dup, ddown = jax.lax.fori_loop(0, steps, body, ct)
    return dxt, dw[:n_w], dgate, dup, ddown


@functools.cache
def _experts(rows):
    """``held_experts``' forward and backward with stretches of ``rows``
    rows, each a ``custom_vmap`` whose rule folds the batch into one call
    of the elements' stretches end to end."""
    fwd = custom_batching.custom_vmap(functools.partial(_experts_fwd, rows))
    bwd = custom_batching.custom_vmap(functools.partial(_experts_bwd, rows))
    fwd.def_vmap(functools.partial(_fwd_rule, rows))
    bwd.def_vmap(functools.partial(_bwd_rule, rows))
    return fwd, bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def held_experts(xt, w, tok, sizes, gate, up, down, rows=None):
    """sum over the rows r of token tok[r]: w[r] SwiGLU_g(xt[tok[r]]) for
    held expert g of row r; rows [0, sizes[0]) are expert 0's, the next
    sizes[1] expert 1's, and so on; rows past sum(sizes) add nothing.
    xt (T, D), w (R,), tok (R,), sizes (G,), gate and up (G, D, F), down
    (G, F, D); returns (T, D).  The sorted rows are taken ``rows`` at a
    time (None: DROPLESS_ROWS)."""
    return _experts(rows)[0](xt, w, tok, sizes, gate, up, down)


def _held_experts_fwd(xt, w, tok, sizes, gate, up, down, rows):
    args = (xt, w, tok, sizes, gate, up, down)
    return _experts(rows)[0](*args), args


def _held_experts_bwd(rows, args, dout):
    dxt, dw, dgate, dup, ddown = _experts(rows)[1](*args, dout)
    return dxt, dw, None, None, dgate, dup, ddown


held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


# --------------------------------------------------------------- embeddings

def init_embedding(key, cfg: ModelConfig, dtype):
    p = {}
    if cfg.input_mode == "tokens":
        p["tok"] = embed_init(key, (cfg.padded_vocab, cfg.d_model), dtype)
    else:  # stubbed frontend provides embeddings; learn an input projection
        p["in_proj"] = dense_init(key, cfg.d_model,
                                  (cfg.d_model, cfg.d_model), dtype)
    return p


def embed_inputs(p, cfg: ModelConfig, inputs: jax.Array) -> jax.Array:
    if cfg.input_mode == "tokens":
        x = jnp.take(p["tok"], inputs, axis=0)
    else:
        x = inputs.astype(dtype_of(cfg.param_dtype)) @ p["in_proj"]
    return sharding.hint(x.astype(dtype_of(cfg.compute_dtype)),
                         "batch", None, None)


def init_lm_head(key, cfg: ModelConfig, dtype):
    if cfg.tie_embeddings and cfg.input_mode == "tokens":
        return {}
    out = cfg.padded_vocab * cfg.num_codebooks
    return {"w": dense_init(key, cfg.d_model, (cfg.d_model, out), dtype)}


def apply_lm_head(head_p, embed_p, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """x (..., D) -> logits (..., num_codebooks*vocab) [codebooks folded]."""
    if cfg.tie_embeddings and cfg.input_mode == "tokens":
        logits = x @ embed_p["tok"].T.astype(x.dtype)
    else:
        logits = x @ head_p["w"]
    return sharding.hint(logits, "batch", None, "vocab")
