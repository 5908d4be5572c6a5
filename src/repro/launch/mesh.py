"""Production mesh construction (TPU v5e).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``xla_force_host_platform_device_count`` before any jax init, and smoke
tests must keep seeing one device.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def _auto(axes):
    """Auto axis types: ``jax.make_mesh`` would default to Explicit."""
    return (AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (one 256-chip v5e pod) or 2×16×16 (two pods; the leading
    ``pod`` axis carries data-parallel replication across the DCN/ICI
    boundary)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_host_mesh():
    """A 1×1 mesh over the single real device (tests / examples)."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto(("data", "model")))


def make_fleet_mesh(replicas: int, *, devices=None):
    """The serving-fleet mesh: ``("replica", "data", "model")`` with the
    leading axis indexing engine replicas (each replica tensor-parallels its
    engine over its ``model`` slice; ``data`` is kept for API symmetry with
    the training meshes and is 1 in serving). Replicas must divide the
    device count — a ragged fleet would strand devices silently."""
    devs = np.asarray(jax.devices() if devices is None else devices)
    n = devs.size
    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")
    if n % replicas:
        raise ValueError(
            f"{replicas} replicas do not divide {n} devices: every replica "
            "gets an identical mesh slice (identical compiled programs), so "
            "a ragged split would strand devices. Pick a replica count that "
            f"divides {n}."
        )
    # an explicit device array: jax.make_mesh always takes every device
    axes = ("replica", "data", "model")
    return Mesh(devs.reshape(replicas, 1, n // replicas), axes, axis_types=_auto(axes))


def replica_meshes(fleet_mesh):
    """One ``("data", "model")`` submesh per replica — what each
    :class:`~repro.serve.engine.ServeEngine` shards itself over. Submeshes
    are disjoint by construction: replica i's engine CANNOT address replica
    j's devices, which is what makes per-replica pool isolation physical."""
    devs = fleet_mesh.devices  # (replica, data, model)
    axes = ("data", "model")
    return [Mesh(devs[i], axes, axis_types=_auto(axes)) for i in range(devs.shape[0])]


def disagg_submeshes(mesh):
    """Split one replica's ``("data", "model")`` mesh into a
    (prefill, decode) pair of disjoint halves along the model axis — the
    compute-bound and bandwidth-bound programs each get their own devices
    and the sealed-page handoff is the only traffic between them. A
    single-device replica colocates (both halves are the same mesh): the
    disaggregated PROGRAM split still applies, only the device split
    degenerates."""
    devs = mesh.devices
    m = devs.shape[-1]
    if m < 2:
        return mesh, mesh
    half = m // 2
    axes = mesh.axis_names
    prefill = Mesh(devs[..., :half], axes, axis_types=_auto(axes))
    decode = Mesh(devs[..., half:], axes, axis_types=_auto(axes))
    return prefill, decode
