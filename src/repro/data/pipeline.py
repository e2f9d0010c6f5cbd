"""Synthetic, shardable data pipelines.

Two properties the paper's setup requires:
  * every worker sees the whole dataset, shuffled with its own seed
    (Sec 4.1 — the asynchronous methods do not re-shard per epoch), which we
    realize with per-worker PRNG streams (`WorkerStream`);
  * deterministic, learnable structure, so the convergence comparisons in
    EXPERIMENTS.md measure optimization (not data noise).  The LM stream is a
    order-k Markov chain over the vocabulary; the image stream is a Gaussian
    class-prototype mixture — both have known Bayes losses.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------- LM streams

@dataclasses.dataclass(frozen=True)
class LMTaskStream:
    """Order-1 Markov-chain token stream (fixed random transition matrix).

    Row ``t`` of the transition logits is i.i.d. Gumbel noise over the
    vocabulary, drawn from ``fold_in(PRNGKey(seed), t)`` and divided by
    ``concentration``.  Rows are drawn when a token needs them, so the
    stream holds no (V, V) matrix: at a 32,000-token vocabulary that
    matrix alone would be 4.1 GB of f32 on the device.
    """

    vocab_size: int
    seq_len: int
    batch_size: int
    concentration: float = 0.3  # lower = more predictable
    seed: int = 1234

    def row_logits(self, tok: jax.Array) -> jax.Array:
        """Transition logits out of token(s) ``tok``: shape tok.shape + (V,)."""
        base = jax.random.PRNGKey(self.seed)

        def row(t):
            k = jax.random.fold_in(base, t)
            return jax.random.gumbel(k, (self.vocab_size,), jnp.float32)

        flat = jnp.reshape(jnp.asarray(tok, jnp.int32), (-1,))
        rows = jax.vmap(row)(flat) / self.concentration
        return jnp.reshape(rows, jnp.shape(tok) + (self.vocab_size,))

    def transition_logits(self) -> jax.Array:
        """The whole (V, V) matrix — for small vocabularies and analysis."""
        return self.row_logits(jnp.arange(self.vocab_size))

    def sample(self, key: jax.Array) -> dict:
        """Returns {"inputs": (B,S) int32, "labels": (B,S) int32}."""
        k0, k1 = jax.random.split(key)
        first = jax.random.randint(k0, (self.batch_size,), 0, self.vocab_size)

        def step(tok, k):
            nxt = jax.random.categorical(k, self.row_logits(tok), axis=-1)
            return nxt, nxt

        keys = jax.random.split(k1, self.seq_len)
        _, toks = jax.lax.scan(step, first, keys)
        toks = jnp.moveaxis(toks, 0, 1)                       # (B, S)
        seq = jnp.concatenate([first[:, None], toks], axis=1)  # (B, S+1)
        return {"inputs": seq[:, :-1].astype(jnp.int32),
                "labels": seq[:, 1:].astype(jnp.int32)}

    def bayes_ce(self) -> float:
        """Entropy rate of the chain = minimum achievable CE."""
        logits = np.asarray(self.transition_logits(), np.float64)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        # stationary distribution via power iteration
        pi = np.full(self.vocab_size, 1.0 / self.vocab_size)
        for _ in range(200):
            pi = pi @ p
        h = -np.sum(pi[:, None] * p * np.log(np.maximum(p, 1e-12)))
        return float(h)


def make_lm_stream(cfg, seq_len: int, batch_size: int, seed: int = 1234
                   ) -> LMTaskStream:
    return LMTaskStream(vocab_size=cfg.vocab_size, seq_len=seq_len,
                        batch_size=batch_size, seed=seed)


def lm_batch_specs(vocab: int, batch: int, seq: int) -> dict:
    return {"inputs": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
            "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}


# ------------------------------------------------------------ image streams

@dataclasses.dataclass(frozen=True)
class SyntheticCIFAR:
    """CIFAR-like stream: Gaussian class prototypes + noise (32x32x3)."""

    num_classes: int = 10
    batch_size: int = 128
    noise: float = 0.6
    seed: int = 7

    def prototypes(self) -> jax.Array:
        rng = np.random.default_rng(self.seed)
        return jnp.asarray(rng.normal(size=(self.num_classes, 32, 32, 3)),
                           jnp.float32)

    def sample(self, key: jax.Array) -> dict:
        k0, k1 = jax.random.split(key)
        labels = jax.random.randint(k0, (self.batch_size,), 0,
                                    self.num_classes)
        protos = self.prototypes()
        imgs = protos[labels] + self.noise * jax.random.normal(
            k1, (self.batch_size, 32, 32, 3))
        return {"images": imgs, "labels": labels}


# ------------------------------------------------------------- worker views

@dataclasses.dataclass(frozen=True)
class WorkerStream:
    """Per-worker data stream: same task, worker-specific PRNG stream.

    Mirrors the paper's protocol: all workers access the same dataset but
    shuffle with different seeds — i.i.d. in distribution, independent in
    realization.  ``heterogeneity`` optionally skews class/token frequencies
    per worker (for the FL-style heterogeneous setting the paper defers to
    future work — kept here as a framework feature)."""

    base_seed: int = 0

    def key(self, worker_id, step) -> jax.Array:
        k = jax.random.PRNGKey(self.base_seed)
        k = jax.random.fold_in(k, worker_id)
        return jax.random.fold_in(k, step)
