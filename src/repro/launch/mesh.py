"""Production meshes.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init,
while tests and benches see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the installed JAX defaults to
    Explicit axes, whose sharding-in-types refuses the shard_map + slice
    and gather code of the sharded serving and indexing paths."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Small mesh over the actually-available devices (tests/examples)."""
    n = len(jax.devices())
    return _mesh((n // model, model), ("data", "model"))


def make_data_mesh(n_dev: int | None = None):
    """1-D ("data",) mesh over the first ``n_dev`` devices (default: all).

    The serving tile shard path (dist.shard_batch / StemmerWorkload
    ``data_devices=N``) splits one [n_dev * block_b, 16] super-tile per
    launch along this axis.
    """
    avail = len(jax.devices())
    if n_dev is None:
        n_dev = avail
    if not 1 <= n_dev <= avail:
        raise ValueError(
            f"data mesh needs 1 <= n_dev <= {avail} devices, got {n_dev}")
    return _mesh((n_dev,), ("data",))
