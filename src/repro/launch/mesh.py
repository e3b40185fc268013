"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: (data=16, model=16) = 256 chips;
multi-pod: (pod=2, data=16, model=16) = 512 chips. The dry-run host forces
512 CPU placeholder devices before any jax import (see dryrun.py)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, model: int = 1):
    """Whatever-devices-we-have mesh for CPU smoke runs."""
    n = jax.device_count()
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
