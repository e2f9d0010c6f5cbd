"""Kanana-2-30B-A3B's share (DeepSeek-V3 blocks) against its plain float32
reference, on the CPU at the benchmark configuration's ``TEST_SIZE``.

The reference (``benchmarks/chip/configs/kanana-2-30b-a3b.py``) imports
nothing of the program.  Weights are seeded random draws in the program's
layout, drawn by the configuration's own ``init_params``.  On the CPU an
f32 matmul is f32, so the program and the reference differ only by the
order of their sums: tolerances are a few f32 ulps of the values'
scale, named at each comparison.
"""
from __future__ import annotations

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model
from repro.models.attention import apply_mla, init_mla
from repro.models import layers
from repro.models.layers import apply_moe, init_moe

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))

import catalog  # noqa: E402

CONFIG = "kanana-2-30b-a3b"
MOD = catalog.config_module(CONFIG)
CFG = dict(catalog.config(CONFIG), **MOD.TEST_SIZE)
TRAFFIC = dict(catalog.traffic("ring2.s2048.b4"), batch=2)


def highest():
    """f32 matmuls at full precision, as the reference states them."""
    return jax.default_matmul_precision("highest")


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _program():
    return MOD.program(CFG, TRAFFIC, lambda k: MOD.example_batch(
        k, CFG, TRAFFIC))


def test_program_matches_reference_loss_and_gradient():
    """The program's loss and flat gradient (per-layer remat, dropless
    held experts, masked vocabulary padding) against the reference's loss
    and ``jax.grad``; 1e-5 is about a hundred f32 ulps, room for the
    different order of the sums through five layers."""
    prog = _program()
    tree = MOD.init_params(jax.random.PRNGKey(3), prog.shapes)
    key = jax.random.PRNGKey(5)
    loss, grad = jax.jit(prog.grad_fn)(prog.pack(tree), key, 1)
    data = MOD.example_batch(jax.random.fold_in(key, 1), CFG, TRAFFIC)
    with highest():
        ref_loss, ref_grad = jax.value_and_grad(MOD.reference_loss)(
            tree, data, CFG, jnp.float32)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert _rel(grad, prog.pack(ref_grad)) < 1e-5
    # every leaf the reference moves, the program moves alike
    for g, r in zip(jax.tree.leaves(prog.shapes), jax.tree.leaves(ref_grad)):
        assert g.shape == r.shape


def test_mla_without_query_lora_matches_reference():
    """``MLAConfig(q_lora_rank=0)``: a direct W_q of (d, H * (nope +
    rope)), no query norm; one sequence through the repo's MLA and the
    reference's, 1e-5 relative (f32 sums in another order)."""
    cfg = MOD.model_config(CFG)
    assert cfg.mla.q_lora_rank == 0
    p = init_mla(jax.random.PRNGKey(0), cfg, jnp.float32)
    assert set(p) == {"w_q", "w_dkv", "kv_norm", "w_uk", "w_uv", "wo"}
    assert p["w_q"].shape == (CFG["hidden_size"], CFG["num_attention_heads"]
                              * CFG["qk_head_dim"])
    p = dict(p, kv_norm=0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                                p["kv_norm"].shape))
    s = 12
    x = jax.random.normal(jax.random.PRNGKey(2), (1, s, CFG["hidden_size"]))
    pos = jnp.arange(s)[None]
    with highest():
        got = apply_mla(p, cfg, x, pos)[0]
        want = MOD._mla(x[0], p, CFG)
    assert _rel(got, want) < 1e-5


def _moe_cfg(held=None, experts=8, top_k=3):
    base = MOD.model_config(CFG).moe
    return dataclasses.replace(base, num_experts=experts, top_k=top_k,
                               held=held)


def _ref_moe(p, x, moe, first, count):
    """The configuration's reference MoE with this layer's sizes."""
    cfg = dict(CFG, routed_over_experts=moe.num_experts,
               num_experts_per_tok=moe.top_k, first_held_expert=first,
               n_routed_experts=count)
    return MOD._moe(x, p, cfg)


def test_dropless_layer_keeps_every_token_on_one_held_expert():
    """A bias that sends every token to held expert 5 (ids 4-7 held):
    capacity dispatch would drop most of them, the dropless layer computes
    all, equal to the reference to 1e-5 (f32 sums in another order)."""
    moe = _moe_cfg(held=(4, 4))
    d = CFG["hidden_size"]
    p = init_moe(jax.random.PRNGKey(0), d, moe, jnp.float32)
    p["router_bias"] = jnp.zeros((8,)).at[5].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, d))
    with highest():
        out, aux = apply_moe(p, x, moe)
        want = _ref_moe(p, x.reshape(-1, d), moe, 4, 4)
    assert float(aux) == 0.0
    assert _rel(out.reshape(-1, d), want) < 1e-5
    # expert 5 (local 1) alone, for every token: nothing was dropped
    s = jax.nn.sigmoid(x.reshape(-1, d) @ p["router"])
    top = jax.lax.top_k(s + p["router_bias"], moe.top_k)[1]
    assert bool(jnp.all(jnp.any(top == 5, axis=-1)))
    w = jnp.take_along_axis(s, top, -1)
    w = w / w.sum(-1, keepdims=True) * moe.routed_scale
    gate = jnp.sum(jnp.where(top == 5, w, 0), -1)
    e = {k: p["moe_" + k[2:]][1] for k in ("w_gate", "w_up", "w_down")}
    from repro.models.layers import apply_mlp
    with highest():
        alone = gate[:, None] * apply_mlp(e, x.reshape(-1, d))
        held_part = out.reshape(-1, d) - apply_mlp(p["shared"],
                                                   x.reshape(-1, d))
        others = jnp.sum(jnp.where((top >= 4) & (top < 8) & (top != 5), w,
                                   0), -1)
    assert bool(jnp.any(others > 0))
    mask = others == 0
    assert _rel(held_part[mask], alone[mask]) < 1e-5


@pytest.mark.parametrize("rows", [None, 7])
def test_held_shares_add_up_to_the_whole_layer(rows, monkeypatch):
    """Expert parallelism's share: disjoint held ranges covering all 8
    experts of a small layer, each routing over all 8; their outputs,
    with the shared expert counted once, sum to the uncut reference layer
    (1e-5: f32 sums in another order).  Also with the sorted rows taken 7
    at a time, so that stretches split the groups."""
    if rows:
        monkeypatch.setattr(layers, "DROPLESS_ROWS", rows)
    whole = _moe_cfg()
    d = CFG["hidden_size"]
    p = init_moe(jax.random.PRNGKey(0), d, whole, jnp.float32)
    p["router_bias"] = 1e-2 * jax.random.normal(jax.random.PRNGKey(4), (8,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, d))
    ranges = [(0, 3), (3, 2), (5, 3)]
    with highest():
        parts = []
        for first, count in ranges:
            share = dict(p, **{k: p[k][first:first + count]
                               for k in ("moe_up", "moe_gate", "moe_down")})
            parts.append(apply_moe(share, x, _moe_cfg((first, count)))[0])
        from repro.models.layers import apply_mlp
        shared = apply_mlp(p["shared"], x)
        total = sum(parts) - (len(ranges) - 1) * shared
        want = _ref_moe(p, x.reshape(-1, d), whole, 0, 8)
        full = apply_moe(p, x, whole)[0]
    assert _rel(total.reshape(-1, d), want) < 1e-5
    assert _rel(full.reshape(-1, d), want) < 1e-5


@pytest.mark.parametrize("rows", [None, 5])
def test_held_experts_vmapped_gradient_is_per_worker(rows, monkeypatch):
    """The dropless block's hand-written VJP under the simulator's
    per-worker vmap, which folds the workers into one call: value and
    gradients equal a dense per-group SwiGLU (1e-5: the same f32 products
    summed in another order), with the workers routing different numbers
    of rows, also with stretches of 5 rows that split the groups and the
    workers' rows; no ragged dot gets a batch dimension (the TPU's takes
    none)."""
    if rows:
        monkeypatch.setattr(layers, "DROPLESS_ROWS", rows)
    t, d, f, g, r = 6, 4, 3, 3, 12
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    xt = jax.random.normal(ks[0], (2, t, d))
    w = jax.random.uniform(ks[1], (2, r))
    tok = jax.random.randint(ks[2], (2, r), 0, t)
    sizes = jnp.array([[2, 0, 5], [4, 4, 3]], jnp.int32)
    gate, up = (jax.random.normal(k, (2, g, d, f)) for k in ks[3:5])
    down = jax.random.normal(ks[5], (2, g, f, d))

    def dense(xt, w, tok, sizes, gate, up, down):
        group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(r),
                                 side="right")
        live = group < g
        e = jnp.minimum(group, g - 1)
        x = xt[tok]
        h = jax.nn.silu(jnp.einsum("rd,rdf->rf", x, gate[e])) \
            * jnp.einsum("rd,rdf->rf", x, up[e])
        y = jnp.einsum("rf,rfd->rd", h, down[e])
        y = jnp.where(live[:, None], y * w[:, None], 0)
        return jnp.zeros_like(xt).at[tok].add(y)

    def grad(f):
        def loss(xt, w, tok, sizes, gate, up, down):
            return jnp.sum(jnp.sin(f(xt, w, tok, sizes, gate, up, down)))
        return jax.vmap(jax.value_and_grad(loss, argnums=(0, 1, 4, 5, 6)))
    args = (xt, w, tok, sizes, gate, up, down)
    with highest():
        (v, gs), (v0, gs0) = grad(layers.held_experts)(*args), \
            grad(dense)(*args)
    np.testing.assert_allclose(v, v0, rtol=1e-5)
    for a, b in zip(gs, gs0):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    jaxpr = str(jax.make_jaxpr(grad(layers.held_experts))(*args))
    dims = [line for line in jaxpr.splitlines()
            if "RaggedDotDimensionNumbers(" in line]
    assert dims
    for line in dims:
        # dot_dimension_numbers=((contracting), (batch)): no batch
        assert "((),())),lhs_ragged" in line.replace(" ", ""), line


def test_documents_are_packed_with_end_ids():
    """The data law at the configuration's own sizes: rows of seq_len + 1
    ids in the vocabulary slice, documents ending in id 0, the same draw
    from the same key, another from another."""
    cfg = catalog.config(CONFIG)
    traffic = catalog.traffic("ring2.s2048.b4")
    draw = jax.jit(lambda k: MOD.example_batch(k, cfg, traffic)["ids"])
    ids = np.asarray(draw(jax.random.PRNGKey(1)))
    assert ids.shape == (traffic["batch"], traffic["seq_len"] + 1)
    assert ids.min() >= 0 and ids.max() < cfg["vocab_size"]
    flat = ids.reshape(-1)
    ends = np.flatnonzero(flat == MOD.EOD)
    assert 2 <= len(ends) and np.diff(ends).max() <= MOD.DOC_CAP
    np.testing.assert_array_equal(ids, np.asarray(draw(jax.random.PRNGKey(1))))
    assert not np.array_equal(ids, np.asarray(draw(jax.random.PRNGKey(2))))
    # half the ids within a document follow the successor table
    succ, _ = MOD._tables(cfg["vocab_size"])
    inside = (flat[:-1] != MOD.EOD) & (flat[1:] != MOD.EOD)
    follow = np.mean(succ[flat[:-1]][inside] == flat[1:][inside])
    assert 0.4 < follow < 0.6


def test_loss_ignores_the_heads_padding_columns():
    """A vocabulary that is not a multiple of 256 is padded; no token has
    a padding id, so the padding logits take no probability: the loss and
    its gradient stay the same when the head's padding columns (here the
    tied embedding's padding rows) are set to large values."""
    cfg = get_config("mamba2-780m", reduced=True).with_updates(
        vocab_size=500)
    assert cfg.padded_vocab == 512 and cfg.tie_embeddings
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 500)
    batch = {"inputs": ids[:, :-1], "labels": ids[:, 1:]}
    loaded = jax.tree.map(lambda a: a, params)
    loaded["embed"]["tok"] = params["embed"]["tok"].at[500:].set(30.0)

    def value_and_grad(p):
        return jax.value_and_grad(lambda p: model.loss(p, batch)[0])(p)
    (l0, g0), (l1, g1) = value_and_grad(params), value_and_grad(loaded)
    assert float(l1) == float(l0)
    assert float(l0) == pytest.approx(np.log(500), rel=0.05)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_array_equal(a, b)
