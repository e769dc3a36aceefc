"""Production meshes. Functions, not module constants — importing this
module never touches jax device state (dry-run sets XLA_FLAGS first)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod slice: 16x16 = 256 chips single pod; 2 pods = 512 chips.

    Axes: "pod" carries only data-parallel (DCN-friendly) traffic;
    "data" is in-pod DP/FSDP/SP; "model" is TP/EP.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/elastic restore."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
