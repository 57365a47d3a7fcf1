"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state.  Single pod: 16x16 = 256 chips ("data", "model"); multi-pod:
2x16x16 = 512 chips ("pod", "data", "model") — the "pod" axis crosses DCN.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """``jax.make_mesh`` with auto axis types (GSPMD propagates shardings).

    ``devices`` defaults to ``jax.devices()``; pass described devices to
    compile for a chip that is not attached.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def axis_sizes(mesh) -> dict[str, int]:
    return dict(mesh.shape)
