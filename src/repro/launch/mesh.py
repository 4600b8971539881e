"""Mesh construction for the production TPU v5e deployment and CPU tests.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dryrun sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init
and everything else must see the single real CPU device.

Every mesh in the repo is built by ``make_mesh`` here.  Its axes are
``Auto``: GSPMD propagates shardings through the model's plain jnp code
(``jnp.take`` on a vocab-sharded embedding, for one), which JAX's default
``Explicit`` axes reject with a ``ShardingTypeError``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """``jax.make_mesh`` with Auto axis types.  ``devices`` defaults to
    ``jax.devices()``; pass a slice to build a mesh over fewer devices."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 4):
    """Small host-device mesh for CPU integration tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count >= n_data*n_model)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
