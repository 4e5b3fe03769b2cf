"""Production mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.

`make_mesh` here is the one mesh constructor the repo uses: every axis is
explicitly `Auto` (GSPMD-propagated) sharding.
"""

from __future__ import annotations

from typing import Sequence

import jax


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    """`jax.make_mesh` with Auto axis types."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes, axis_types=_auto(len(axes)))


def mesh_from_devices(devices, axes: Sequence[str]) -> jax.sharding.Mesh:
    """`jax.sharding.Mesh` over an explicit device array (the elastic-rescale
    path, where the surviving devices are hand-picked)."""
    axes = tuple(axes)
    return jax.sharding.Mesh(devices, axes, axis_types=_auto(len(axes)))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, data: int | None = None, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples / serving).

    `model` is the tensor-parallel degree; `data` defaults to using every
    remaining device. Raises if the host doesn't have enough devices.
    """
    n = jax.device_count()
    if data is None:
        data = max(1, n // model)
    if data * model > n:
        raise ValueError(
            f"mesh ({data}, {model}) needs {data * model} devices, "
            f"host has {n} (set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            f"for CPU testing)"
        )
    return make_mesh((data, model), ("data", "model"))
