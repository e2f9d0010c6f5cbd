"""Production meshes.

Functions, not module-level constants — importing this module never touches
jax device state (jax locks the device count on first init, and only
launch/dryrun.py is allowed to fake 512 host devices).
"""
from __future__ import annotations

import jax

from .. import sharding


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_gossip_mesh(n_workers: int = 8, data: int = 8, model: int = 8):
    """Decentralized mesh: `n_workers` pod-slices on a gossip graph, each an
    FSDP(data) x TP(model) synchronous island.  Default (8, 8, 8) = 512 chips,
    8 workers — a ring of 8 has chi1 ~ 3.5 >> chi2 ~ 0.9, so A2CiD2 bites."""
    return jax.make_mesh((n_workers, data, model), ("worker", "data", "model"))


def make_replay_mesh(n_shards: int | None = None, *, axis: str = "worker"):
    """Host-aware 1-D replay mesh: the sharded worlds replay
    (``launch/mesh_replay.py``) splits the worker axis of the flat
    (B, W, D) gossip banks over this mesh's devices.

    Sized from ``jax.local_device_count()`` — never a hardcoded chip
    count like ``make_gossip_mesh``'s 512 — so the same call works on one
    CPU, a TPU host, or a forced-host-device test process.  Only
    ``launch/dryrun.py`` may fake the device count; this function always
    reports what the runtime actually has."""
    avail = jax.local_device_count()
    if n_shards is None:
        n_shards = avail
    if not 1 <= n_shards <= avail:
        raise ValueError(f"make_replay_mesh needs 1 <= n_shards <= "
                         f"{avail} local devices, got {n_shards}")
    return jax.make_mesh((n_shards,), (axis,),
                         devices=jax.local_devices()[:n_shards])


def rules_for(mesh) -> dict:
    axes = mesh.axis_names
    if "pod" in axes:
        return dict(sharding.MULTI_POD_RULES)
    if "worker" in axes:
        # a pure replay mesh (worker axis only) shards the flat worker
        # banks and replicates everything else; a (worker, data, model)
        # gossip mesh keeps the model-sharding rules
        if axes == ("worker",):
            return dict(sharding.REPLAY_RULES)
        return dict(sharding.GOSSIP_RULES)
    return dict(sharding.SINGLE_POD_RULES)


def mesh_devices(mesh) -> int:
    return mesh.devices.size
