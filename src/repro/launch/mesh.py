"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state — required because the dry-run must
set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips/pod (single pod) or 2x16x16 = 512 chips (2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    # the dry-run forces 512 host devices for both meshes: take the first n
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n])


def make_mesh(shape, axes) -> Mesh:
    n = int(np.prod(shape))
    devs = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(devs, axes)


def describe(mesh: Mesh) -> str:
    return (f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} on "
            f"{mesh.devices.size} devices ({mesh.devices.flat[0].platform})")
