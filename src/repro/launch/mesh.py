"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; everything else
sees the real device count).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np


def _axis_types_kwargs(n_axes: int) -> dict:
    """Every mesh axis is auto-sharded."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_types_kwargs(len(axes)))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (elastic reshapes, tests on small host counts)."""
    return jax.make_mesh(shape, axes, **_axis_types_kwargs(len(axes)))


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (CPU tests: 1 device)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"), **_axis_types_kwargs(2))


def activate(mesh):
    """Context manager entering `mesh` (`jax.set_mesh`)."""
    return jax.set_mesh(mesh)


def describe(mesh) -> str:
    return (f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
            f"({mesh.devices.size} devices)")
