"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def _auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes: the model code places
    activations with ``with_sharding_constraint`` (``repro.nn.sharding``),
    which accepts only Auto axes, whereas ``make_mesh`` defaults to
    Explicit ones."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """1×1 mesh over the local device — smoke tests / CPU examples."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_cells_mesh(n_devices: int | None = None) -> Mesh:
    """1-D ``("cells",)`` mesh for sharding independent edge cells across
    devices (``repro.core.t2drl.run_training_sharded``, DESIGN.md §13).

    ``n_devices`` defaults to every visible device; on CPU, multiple
    devices are obtained with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    n = len(jax.devices()) if n_devices is None else n_devices
    return _auto_mesh((n,), ("cells",))


def batch_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_sharding(mesh: Mesh, *rest) -> NamedSharding:
    ba = batch_axes(mesh)
    lead = ba if len(ba) != 1 else ba[0]
    return NamedSharding(mesh, P(lead, *rest))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
