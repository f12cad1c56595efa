"""Mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — required for the smoke tests, which must see
one CPU device, while the dry-run sets xla_force_host_platform_device_count
before first jax init.

Every mesh in the repo goes through :func:`make_mesh`: ``jax.make_mesh``
defaults to ``Explicit`` axes, under which the sharding-in-types rules
refuse the model's unannotated gathers; the rules in repro.parallel are
written for ``Auto`` axes.
"""
from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (over
    ``devices``, default all visible devices)."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_serving_mesh(tp: int):
    """One host's serving mesh: (data=1, model=tp) over the first ``tp``
    local devices."""
    return make_mesh((1, tp), ("data", "model"),
                     devices=jax.local_devices()[:tp])


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_from_spec(spec: str):
    """e.g. "8x16" -> (data=8, model=16); "2x8x16" -> (pod, data, model).
    Used by elastic-resume (--mesh) in the launchers."""
    dims = tuple(int(x) for x in spec.split("x"))
    if len(dims) == 2:
        axes = ("data", "model")
    elif len(dims) == 3:
        axes = ("pod", "data", "model")
    else:
        raise ValueError(f"bad mesh spec {spec!r}")
    return make_mesh(dims, axes)
