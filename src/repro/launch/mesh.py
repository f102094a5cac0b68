"""Production meshes.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (jax locks the device count on first backend
init, and the dry-run needs to set XLA_FLAGS first).

  single pod : (16, 16)    ("data", "model")   = 256 chips
  multi-pod  : (2, 16, 16) ("pod", "data", "model") = 512 chips

The graph engine flattens every axis into one "graph" axis (the paper's
n_FPGA): 256- or 512-way vertex sharding.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh

__all__ = ["make_production_mesh", "make_graph_mesh", "make_local_mesh",
           "make_serving_mesh", "auto_mesh"]


def auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (XLA SPMD picks the
    collectives)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_graph_mesh(*, multi_pod: bool = False) -> Mesh:
    """All chips on one 'graph' axis for the GraVF-M engine."""
    n = 512 if multi_pod else 256
    return auto_mesh((n,), ("graph",))


def make_local_mesh(axes=("graph",)) -> Mesh:
    """Whatever devices exist locally (tests / reduced runs)."""
    n = len(jax.devices())
    return auto_mesh((n,), axes)


def make_serving_mesh(num_shards: int) -> Mesh:
    """The service's explicit 1-D graph mesh: ``num_shards`` devices on
    the ``"graph"`` axis, one partition per device. Requires at least
    ``num_shards`` visible devices (real accelerators, or host-platform
    devices via ``--xla_force_host_platform_device_count=N`` set before
    jax's first backend init) — shard classes are a multi-device
    feature, and failing loudly here beats shard_map's late error."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    have = len(jax.devices())
    if have < num_shards:
        raise RuntimeError(
            f"serving mesh wants {num_shards} devices on the 'graph' "
            f"axis but only {have} are visible; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={num_shards} "
            "before importing jax (or run on a platform with enough "
            "devices)")
    return auto_mesh((num_shards,), ("graph",))
