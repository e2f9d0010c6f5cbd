"""ResNet-18 for CIFAR-10: the system's own build of the model, its data, its
work counts, and a plain float32 reference of its loss.

The program side builds ``repro.models.resnet`` at the sizes of
``resnet18-cifar10.json``; each replica goes to the simulator as one flat
(D,) vector, unpacked inside the gradient.  The simulator vmaps the
gradient over workers, so the per-worker convolutions run as grouped
convolutions.

The reference side imports nothing of the program: a pre-activation
ResNet-18 (He et al. 2016) with GroupNorm in place of BatchNorm, written from
the sizes alone, taking each worker's images one worker at a time.
"""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np

UNIT = "images"

# the sizes the CPU tests run this configuration at: widths cut so that a
# whole run takes seconds; the blocks (pre-activation, projection where
# the width changes) stay the configuration's
TEST_SIZE = dict(stage_sizes=[1, 1], width=8, norm_groups=4, image_size=8)


def units_per_example(cfg: dict, traffic: dict) -> int:
    """Images one worker trains on in one gradient tick."""
    return traffic["batch"]


def _taps(n: int, k: int, stride: int) -> int:
    """(output, kernel tap) pairs of a SAME-padded 1-D convolution over n
    inputs whose tap lands on an input and not on the padding."""
    out = -(-n // stride)
    pad = max((out - 1) * stride + k - n, 0) // 2
    return sum(0 <= o * stride - pad + t < n
               for o in range(out) for t in range(k))


def _convs(cfg: dict):
    """(input size, kernel, stride, c_in, c_out) of every convolution, in
    forward order, and the width the head reads."""
    size, c_in = cfg["image_size"], cfg["width"]
    out = [(size, 3, 1, cfg["channels"], c_in)]
    for si, n_blocks in enumerate(cfg["stage_sizes"]):
        c_out = cfg["width"] * 2 ** si
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            out.append((size, 3, stride, c_in, c_out))
            if stride != 1 or c_in != c_out:
                out.append((size, 1, stride, c_in, c_out))
            size = -(-size // stride)
            out.append((size, 3, 1, c_out, c_out))
            c_in = c_out
    return out, c_in


def flops_per_unit(cfg: dict, traffic: dict) -> float:
    """Model FLOPs per image of the forward and backward passes, from the
    shapes: 2 per multiply-add of each convolution whose input is not
    padding, and of the head; the same again for the weight gradient, and
    again for the input gradient, which the stem does not need."""
    convs, c_last = _convs(cfg)
    fwd = [2.0 * _taps(n, k, s) ** 2 * ci * co for n, k, s, ci, co in convs]
    head = 2.0 * c_last * cfg["num_classes"]
    return 3 * (sum(fwd) + head) - fwd[0]


def example_batch(key: jax.Array, cfg: dict, traffic: dict) -> dict:
    """One worker's batch: class prototypes from a fixed key plus noise."""
    k0, k1 = jax.random.split(key)
    shape = (cfg["image_size"], cfg["image_size"], cfg["channels"])
    labels = jax.random.randint(k0, (traffic["batch"],), 0,
                                cfg["num_classes"], dtype=jnp.int32)
    protos = jax.random.normal(jax.random.PRNGKey(7),
                               (cfg["num_classes"],) + shape)
    noise = jax.random.normal(k1, (traffic["batch"],) + shape)
    return {"images": protos[labels] + 0.5 * noise, "labels": labels}


def init_params(key: jax.Array, shapes) -> dict:
    """Weights drawn by the benchmark in the program's pytree layout:
    convolutions N(0, 2/fan_in), the head's matrix N(0, 1/fan_in), norm
    scales 1 and every bias 0."""
    paths = jax.tree_util.tree_leaves_with_path(shapes)
    keys = jax.random.split(key, len(paths))
    out = []
    for (path, leaf), k in zip(paths, keys):
        name = jax.tree_util.keystr(path)
        if leaf.ndim == 4:
            fan_in = int(np.prod(leaf.shape[:-1]))
            out.append(jax.random.normal(k, leaf.shape, leaf.dtype)
                       * np.sqrt(2.0 / fan_in))
        elif leaf.ndim == 2:
            out.append(jax.random.normal(k, leaf.shape, leaf.dtype)
                       / np.sqrt(leaf.shape[0]))
        elif name.endswith("[0]") and "gn" in name:
            out.append(jnp.ones(leaf.shape, leaf.dtype))
        else:
            out.append(jnp.zeros(leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(jax.tree.structure(shapes), out)


def program(cfg: dict, traffic: dict, batch):
    """The system under test: shapes of one replica, ``pack`` to the flat
    vector, and the per-worker ``grad_fn`` the simulator vmaps, which
    trains on ``batch(key)``, the harness's draw of ``example_batch``."""
    from repro.core.flatbuf import FlatLayout
    from repro.models.resnet import ResNetConfig, init_resnet, resnet_loss

    rcfg = ResNetConfig(cfg["name"], tuple(cfg["stage_sizes"]), cfg["width"],
                        cfg["num_classes"], groups=cfg["norm_groups"])
    shapes = jax.eval_shape(lambda k: init_resnet(k, rcfg),
                            jax.random.PRNGKey(0))
    layout = FlatLayout.from_pytree(shapes)

    def grad_fn(vec, key, wid):
        data = batch(jax.random.fold_in(key, wid))

        def loss_fn(v):
            loss, _ = resnet_loss(layout.unpack_local(v), rcfg, data)
            return loss
        return jax.value_and_grad(loss_fn)(vec)

    return types.SimpleNamespace(shapes=shapes, pack=layout.pack_local,
                                 grad_fn=grad_fn, d=layout.d)


# ---------------------------------------------------------------- reference

def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_norm(x, scale, bias, groups, eps=1e-5):
    b, h, w, c = x.shape
    g = x.reshape(b, h, w, groups, c // groups)
    mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean((g - mean) ** 2, axis=(1, 2, 4), keepdims=True)
    g = (g - mean) / jnp.sqrt(var + eps)
    return g.reshape(b, h, w, c) * scale + bias


def reference_loss(params: dict, data: dict, cfg: dict, dtype) -> jax.Array:
    """Mean cross-entropy of the pre-activation ResNet, in ``dtype``:
    each block is GN-ReLU, a 3x3 convolution (stride 2 where the stage
    widens), GN-ReLU, a 3x3 convolution, added to the input or, where the
    width changes, to a 1x1 projection of the first GN-ReLU's output."""
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    groups = cfg["norm_groups"]
    h = _conv(data["images"].astype(dtype), p["stem"])
    for stage in p["stages"]:
        for blk in stage:
            stride = 2 if "proj" in blk else 1
            y = jax.nn.relu(_group_norm(h, *blk["gn1"], groups))
            short = _conv(y, blk["proj"], stride) if "proj" in blk else h
            y = _conv(y, blk["conv1"], stride)
            y = jax.nn.relu(_group_norm(y, *blk["gn2"], groups))
            h = short + _conv(y, blk["conv2"])
    feat = jnp.mean(jax.nn.relu(h), axis=(1, 2))
    w, b = p["head"]
    logits = feat @ w + b
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, data["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold).astype(jnp.float32)
