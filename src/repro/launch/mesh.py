"""Mesh construction: the one place a ``jax.sharding.Mesh`` is built.

Production meshes (TPU v5e):
Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model").

The ``model`` axis carries PRISM's P: activations (and KV caches) are
sharded over it on the sequence dimension, and the per-block Segment-Means
exchange is an all-gather over it.  ``data`` carries batch + FSDP.  ``pod``
is pure data parallelism — PRISM's sequence exchange never crosses the
(slow) pod boundary, matching the paper's premise.

Every axis is ``AxisType.Auto``: the runtime places its arrays with
``NamedSharding`` and hand-written ``shard_map`` specs, and leaves the
rest to the partitioner.  ``jax.make_mesh`` defaults to Explicit axes,
under which sharding-in-types rejects the runtime's cache splices, so no
code calls it without going through :func:`make_mesh`.

Functions, not module constants: importing this module must not touch jax
device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """A mesh of ``shape`` named ``axes``, every axis Auto.
    ``jax.make_mesh`` takes the first ``prod(shape)`` devices in its
    topology-aware order and refuses a shape larger than the host."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    # host-device stand-ins may exceed the mesh size (512 forced for the
    # dry-run; the single-pod mesh takes the first 256)
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_host_mesh(model: int = 4, data: int = 2):
    """A ``(data, model)`` mesh over the local devices (tests, examples,
    launchers)."""
    return make_mesh((data, model), ("data", "model"))


def mesh_axes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_axes(mesh) -> tuple:
    """Axes the global batch is sharded over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
