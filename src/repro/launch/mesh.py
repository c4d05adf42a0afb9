"""Production mesh construction (spec-mandated shapes).

Functions, not module-level constants: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax
initialization; tests run on 1 device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    # Auto axes: the model code places activations with
    # ``with_sharding_constraint``, which only refers to Auto axes.
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1, devices: int = 0):
    """Mesh over the first ``devices`` local devices (0 = all of them)."""
    n = devices or len(jax.devices())
    assert n % model == 0
    return _mesh((n // model, model), ("data", "model"), jax.devices()[:n])
