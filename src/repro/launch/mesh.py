"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init and only then calls it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the engines annotate
    shardings and let the compiler propagate them (``make_mesh`` itself
    defaults to ``Explicit`` axes, under which those programs do not
    type-check)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 ("data","model") single pod; 2x16x16 ("pod","data","model")
    for the 512-chip two-pod configuration."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Mesh over whatever devices exist (CPU smoke / single host)."""
    n = len(jax.devices())
    if model_axis <= 0 or n % model_axis != 0:
        raise ValueError(
            f"make_local_mesh: {n} visible device(s) cannot be factored "
            f"into a model axis of {model_axis} (need model_axis >= 1 and "
            f"{n} % model_axis == 0)")
    return auto_mesh((n // model_axis, model_axis), ("data", "model"))
