"""Device meshes for the launchers, tests and examples.

Functions, not module-level constants: importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh

try:  # jax >= 0.5 names explicit/auto axis types; older jax has Auto only
    from jax.sharding import AxisType
except ImportError:  # pragma: no cover - depends on installed jax
    AxisType = None


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    if AxisType is not None:
        return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
    return jax.make_mesh(shape, axes)


def make_mesh_for(devices: int, model_parallel: int = 0) -> Mesh:
    """Small meshes for tests/examples: (data, model) over available devices."""
    model = model_parallel or (2 if devices % 2 == 0 and devices > 1 else 1)
    if model <= 0 or devices % model != 0:
        usable = [m for m in range(1, devices + 1) if devices % m == 0]
        shapes = [f"({devices // m}, {m})" for m in usable]
        raise ValueError(
            f"make_mesh_for({devices}, model_parallel={model}): {devices} "
            f"devices are not divisible by model_parallel={model}; usable "
            f"(data, model) shapes for {devices} devices: {', '.join(shapes)}")
    data = devices // model
    return make_mesh((data, model), ("data", "model"))

