"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state. The single-pod production mesh is
16x16 = 256 chips (TPU v5e pod, (data, model)); the multi-pod mesh adds a
leading pod axis: 2 x 16 x 16 = 512 chips.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape=None, axes=("data", "model")):
    """Mesh over whatever devices exist (tests / elastic restarts).
    Shape defaults to (n_devices, 1)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
