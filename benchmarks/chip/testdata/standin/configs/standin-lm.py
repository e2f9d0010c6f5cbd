"""A stand-in for a language model, for the benchmark's tests only: token
ids through an embedding, one residual MLP layer and an output head,
trained on next-token cross-entropy.  It shows what a configuration that
is not ResNet brings by files alone: tokens as its unit of work, its own
``model.<name>`` scopes, its own test size.

The program side marks its layers with ``jax.named_scope("model.mlp")``
and ``jax.named_scope("model.head")``; each replica goes to the simulator
as one flat (D,) vector, unpacked inside the gradient.  The reference side
is written again from the sizes, one worker at a time.
"""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np

UNIT = "tokens"

TEST_SIZE = dict(vocab_size=32, d_model=16, d_ff=32, seq_len=8)


def units_per_example(cfg: dict, traffic: dict) -> int:
    """Tokens one worker trains on in one gradient tick."""
    return traffic["batch"] * cfg["seq_len"]


def flops_per_unit(cfg: dict, traffic: dict) -> float:
    """Model FLOPs per token of the forward and backward passes: the MLP's
    two matmuls and the head's, each 2 per multiply-add, three times (the
    forward, the weight gradient, the input gradient)."""
    d, f, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    return 3 * (2.0 * d * f * 2 + 2.0 * d * v)


def example_batch(key: jax.Array, cfg: dict, traffic: dict) -> dict:
    """One worker's batch: token ids that follow a fixed random successor
    table with probability one half, and are uniform otherwise."""
    k0, k1, k2 = jax.random.split(key, 3)
    shape = (traffic["batch"], cfg["seq_len"] + 1)
    v = cfg["vocab_size"]
    succ = jax.random.randint(jax.random.PRNGKey(7), (v,), 0, v)
    start = jax.random.randint(k0, shape[:1], 0, v)
    noise = jax.random.randint(k1, shape, 0, v)
    keep = jax.random.bernoulli(k2, 0.5, shape)

    def step(tok, xs):
        n, k = xs
        nxt = jnp.where(k, succ[tok], n)
        return nxt, nxt
    _, rest = jax.lax.scan(step, start, (noise[:, 1:].T, keep[:, 1:].T))
    ids = jnp.concatenate([start[:, None], rest.T], axis=1)
    return {"ids": ids.astype(jnp.int32)}


def _shapes(cfg: dict) -> dict:
    d, f, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    s = jax.ShapeDtypeStruct
    return {"embed": s((v, d), jnp.float32),
            "mlp": {"w_in": s((d, f), jnp.float32),
                    "w_out": s((f, d), jnp.float32)},
            "head": s((d, v), jnp.float32)}


def init_params(key: jax.Array, shapes) -> dict:
    """Embedding N(0, 1), every matrix N(0, 1/fan_in)."""
    paths = jax.tree_util.tree_leaves_with_path(shapes)
    keys = jax.random.split(key, len(paths))
    out = []
    for (path, leaf), k in zip(paths, keys):
        scale = 1.0 if "embed" in jax.tree_util.keystr(path) \
            else 1.0 / np.sqrt(leaf.shape[0])
        out.append(jax.random.normal(k, leaf.shape, leaf.dtype) * scale)
    return jax.tree_util.tree_unflatten(jax.tree.structure(shapes), out)


def program(cfg: dict, traffic: dict, batch):
    """The system under test: shapes of one replica, ``pack`` to the flat
    vector, and the per-worker ``grad_fn`` the simulator vmaps, which
    trains on ``batch(key)``."""
    from repro.core.flatbuf import FlatLayout

    shapes = _shapes(cfg)
    layout = FlatLayout.from_pytree(shapes)

    def loss(p, ids):
        x = jnp.take(p["embed"], ids[:, :-1], axis=0)
        with jax.named_scope("model.mlp"):
            x = x + jnp.tanh(x @ p["mlp"]["w_in"]) @ p["mlp"]["w_out"]
        with jax.named_scope("model.head"):
            logits = x @ p["head"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        gold = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
        return -jnp.mean(gold)

    def grad_fn(vec, key, wid):
        ids = batch(jax.random.fold_in(key, wid))["ids"]
        return jax.value_and_grad(
            lambda v: loss(layout.unpack_local(v), ids))(vec)

    return types.SimpleNamespace(shapes=shapes, pack=layout.pack_local,
                                 grad_fn=grad_fn, d=layout.d)


def reference_loss(params: dict, data: dict, cfg: dict, dtype) -> jax.Array:
    """Mean next-token cross-entropy in ``dtype``: each position's
    embedding, plus tanh(e W_in) W_out, times the head, against the next
    token."""
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    ids = data["ids"]
    e = p["embed"][ids[:, :-1]]
    h = e + jnp.tanh(e @ p["mlp"]["w_in"]) @ p["mlp"]["w_out"]
    logits = h @ p["head"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - gold).astype(jnp.float32)
