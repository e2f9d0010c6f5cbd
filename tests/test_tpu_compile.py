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
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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
