"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module does not touch JAX device state — smoke tests see 1 CPU device,
while the dry-run sets ``xla_force_host_platform_device_count=512`` before
its first JAX import and gets the full meshes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (v5e pod), or 2x16x16 = 512 chips for 2 pods.

    Axes: ``data`` (DP + FSDP shard axis), ``model`` (TP/EP), and ``pod``
    (outer DP + FSDP axis) when multi-pod.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests / elastic re-meshing)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def single_device_mesh():
    return make_mesh((1, 1), ("data", "model"))
