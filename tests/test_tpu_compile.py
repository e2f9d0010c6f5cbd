"""AOT compiles of the gossip kernels for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled for one chip of a
``v5e:2x2`` topology, at nano-LM's full flat width (W = 4 workers,
D = 128,404,224 f32 parameters; B = 2 worlds for the batched kernels).
The compiler refuses what interpret mode accepts — block shapes off the
(8, 128) tiling, scalar loads from HBM, more VMEM than a kernel may
use — so these tests guard the chip path without a chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file.

A last case compiles one gradient tick of a small flat model, vmapped
over 16 workers, and reads the optimised HLO: the flat gradient must not
come back as a sum of full-width pads, one per leaf.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import FlatLayout, mix_flat
from repro.kernels.a2cid2_mixing import kernel as K

W, D, B = 4, 128_404_224, 2


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cases(s):
    """(name, fn, args) for the six kernels; the x and x~ banks are the
    first two arguments and are donated, as the replay's scan donates its
    carry."""
    stacked = (W, D)
    worlds = (B, W, D)
    kw = dict(eta=0.37, alpha=0.5, alpha_t=1.4)
    f32 = jnp.float32
    return [
        ("mixing_p2p",
         lambda x, xt, xp, dt: K.mixing_p2p(x, xt, xp, dt, **kw),
         [_spec(s, (D,)), _spec(s, (D,)), _spec(s, (D,)), _spec(s, (), f32)]),
        ("p2p_mixing",
         lambda x, xt, xp, dt: K.p2p_mixing(x, xt, xp, dt, **kw),
         [_spec(s, (D,)), _spec(s, (D,)), _spec(s, (D,)), _spec(s, (), f32)]),
        ("mixing_gossip_stacked",
         lambda x, xt, p, dt: K.mixing_gossip_stacked(x, xt, p, dt, **kw),
         [_spec(s, stacked), _spec(s, stacked), _spec(s, (W,), jnp.int32),
          _spec(s, (W,))]),
        ("channel_gossip_stacked",
         lambda x, xt, xp, c, m, dt: K.channel_gossip_stacked(
             x, xt, xp, c, m, dt, clip=0.5, **kw),
         [_spec(s, stacked), _spec(s, stacked), _spec(s, stacked),
          _spec(s, (W,)), _spec(s, (W,)), _spec(s, (W,))]),
        ("mixing_gossip_worlds",
         K.mixing_gossip_worlds,
         [_spec(s, worlds), _spec(s, worlds), _spec(s, (B, W), jnp.int32),
          _spec(s, (B, W)), _spec(s, (B,)), _spec(s, (B,)),
          _spec(s, (B,))]),
        ("channel_gossip_worlds",
         lambda *a: K.channel_gossip_worlds(*a, want_rej=True),
         [_spec(s, worlds), _spec(s, worlds), _spec(s, worlds),
          _spec(s, (B, W)), _spec(s, (B, W)), _spec(s, (B, W)),
          _spec(s, (B,)), _spec(s, (B,)), _spec(s, (B,))]),
    ]


NAMES = ["mixing_p2p", "p2p_mixing", "mixing_gossip_stacked",
         "channel_gossip_stacked", "mixing_gossip_worlds",
         "channel_gossip_worlds"]


@pytest.mark.parametrize("name", NAMES)
def test_gossip_kernel_compiles_for_v5e(one_chip, name):
    (fn, args), = [(f, a) for n, f, a in _cases(one_chip) if n == name]
    compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the banks are updated in place: no relayout or padded copy of a bank
    bank = args[0].size * 4
    assert mem.alias_size_in_bytes >= 2 * bank
    assert mem.temp_size_in_bytes < bank // 64


def _odd_model():
    """Thirty odd-sized f32 leaves: d_real 1,635, D 1,664."""
    return {f"l{i:02d}": jax.ShapeDtypeStruct(
        (2 * i + 1, 3) if i % 2 else (i + 2,), jnp.float32)
        for i in range(30)}


def _computations(hlo):
    """{name: body} of every computation in an HLO module's text."""
    return dict(re.findall(r"^(?:ENTRY )?%?([\w.-]+) [^\n]*\{\n(.*?)^\}",
                           hlo, re.M | re.S))


def test_flat_gradient_tick_has_no_pad_sum(one_chip):
    n_workers = 16
    layout = FlatLayout.from_pytree(_odd_model())
    bank = (n_workers, layout.d)

    def loss(vec, data):
        leaves = jax.tree.leaves(layout.unpack_local(vec))
        return sum(jnp.sum(jnp.tanh(leaf * data[k]))
                   for k, leaf in enumerate(leaves))

    def tick(bx, bxt, data, dt):
        g = jax.vmap(jax.grad(loss))(bx, data)
        return mix_flat(bx - 0.05 * g, bxt - 0.05 * g, 0.7, dt)

    compiled = jax.jit(tick, donate_argnums=(0, 1)).lower(
        _spec(one_chip, bank), _spec(one_chip, bank),
        _spec(one_chip, (n_workers, 30)),
        _spec(one_chip, (n_workers,))).compile()
    full_pad = re.compile(
        rf"= f32\[{n_workers},{layout.d}\]\S* pad\(")
    bodies = _computations(compiled.as_text())
    assert any(n.startswith("fused") for n in bodies)
    pads = {name: len(full_pad.findall(body))
            for name, body in bodies.items()}
    assert max(pads.values()) <= 2, {n: c for n, c in pads.items() if c}


def test_dropless_moe_gradient_compiles_for_v5e(one_chip):
    """The gradient of a dropless expert layer at Kanana-2-30B-A3B's widths
    (d 2,048, 8 held of 128 routed-over experts of width 768, top 6, two
    shared experts as one SwiGLU of 1,536), vmapped over two workers as
    the simulator does: the TPU's ragged dot takes no batch dimension, so
    the grouped matmuls must reach the compiler one worker at a time."""
    from repro.models.config import MoEConfig
    from repro.models.layers import apply_moe, init_moe

    moe = MoEConfig(num_experts=128, top_k=6, d_expert=768,
                    shared_expert=True, d_shared=1536, scoring="sigmoid",
                    routed_scale=2.448, dropless=True, held=(0, 8))
    params = jax.eval_shape(lambda k: init_moe(k, 2048, moe, jnp.float32),
                            jax.random.PRNGKey(0))

    def grad(p, x):
        return jax.grad(lambda p: jnp.sum(apply_moe(p, x, moe)[0]))(p)

    def spec(leaf):
        return _spec(one_chip, (2,) + leaf.shape, leaf.dtype)
    compiled = jax.jit(jax.vmap(grad)).lower(
        jax.tree.map(spec, params),
        _spec(one_chip, (2, 1, 512, 2048))).compile()
    hlo = compiled.as_text()
    assert "ragged-dot" in hlo or "ragged_dot" in hlo
