"""Production meshes.

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import; unit tests run single-device).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the models place arrays
    through ``with_sharding_constraint`` (repro.sharding.api), which only
    accepts Auto axes, while ``make_mesh`` defaults to Explicit ones."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Small mesh for CI dry-run tests (8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_serving_mesh(n_shards: int):
    """1-D data-parallel mesh for the mesh-sharded serving engine: each
    shard owns a full model replica plus its own slot pool / page pool /
    free stack, so the only mesh axis is the fleet axis. CPU test runs
    force host devices via --xla_force_host_platform_device_count."""
    if n_shards > jax.device_count():
        raise ValueError(
            f"serving mesh needs {n_shards} devices, have "
            f"{jax.device_count()} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards} before the "
            "first jax import for CPU testing)")
    return _auto_mesh((n_shards,), ("data",))
