"""Production mesh factories.

Functions, not module-level constants: importing this module never touches
jax device state (required for smoke tests that must see 1 device).  Every
mesh has explicit ``AxisType.Auto`` axes; enter one with ``jax.set_mesh``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_worker_mesh(devices=None):
    """One decentralized worker per device, for the devices present.

    Axes ``("data", "model")`` with ``model`` of size 1, so the
    decentralized ``ShardingRules`` put the stacked worker axis on
    ``data`` and every tensor-parallel dim resolves to a trivial axis.
    """
    devices = list(jax.devices() if devices is None else devices)
    return _make_mesh((len(devices), 1), ("data", "model"), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Target fleet: TPU v5e, 16x16 = 256 chips/pod; 2 pods multi-pod.

    Axes: ``data`` (decentralized workers / FSDP), ``model`` (tensor
    parallel), plus ``pod`` across pods.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_two_tier_mesh(inter: int = 8, intra: int = 4, model: int = 8):
    """Two-tier gossip fleet: the decentralized worker dimension split into
    a fast ``intra`` axis (ICI inside a node) and a slow ``inter`` axis
    (the oversubscribed cross-node fabric).  Worker ``w = g * intra + j``
    — the intra index varies fastest, matching ``HierarchicalTopology``'s
    flat worker ordering and the engine's ``reshape(n_inter, n_intra)``
    staging view, so the TieredPlan's intra reduce lowers to collectives
    on the ``intra`` axis and the shard gossip to collective-permutes on
    ``inter``.
    """
    return _make_mesh((inter, intra, model), ("inter", "intra", "model"))


def make_host_mesh(data: int = 4, model: int = 2, pod: int = 0):
    """Small mesh for subprocess tests (requires forced host devices)."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def mesh_shape_dict(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
