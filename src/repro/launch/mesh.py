"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
--xla_force_host_platform_device_count=512 before any jax init, and smoke
tests/benches must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(*, data: int | None = None, model: int = 1):
    """Small mesh over the actually-present devices (tests/examples).

    Validates the requested shape against the visible device count so a
    bad --mesh-model fails with an actionable message instead of
    jax.make_mesh's opaque reshape error.
    """
    n = len(jax.devices())
    if model < 1:
        raise ValueError(f"mesh model axis must be >= 1, got {model}")
    if data is None:
        data = max(n // model, 1)
    if data < 1:
        raise ValueError(f"mesh data axis must be >= 1, got {data}")
    if data * model > n:
        raise ValueError(
            f"mesh ({data} data x {model} model = {data * model} devices) "
            f"exceeds the {n} visible {jax.default_backend()} device(s); "
            f"shrink the mesh, or force host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N (set "
            f"before jax initializes)")
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """jax.make_mesh with Auto axes: the sharding rules here place arrays
    with NamedSharding / with_sharding_constraint, which Explicit axes
    (make_mesh's default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
