"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: 16×16 = 256 chips ("data","model"); the multi-pod
    variant stacks 2 pods on a leading "pod" axis (512 chips).

    The dry-run process exposes 512 host devices; the single-pod mesh uses
    the first 256 (device id // 256 == pod id, which the HLO collective
    analysis relies on)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(jax.devices())} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import (launch/dryrun.py does this)")
    return make_mesh(shape, axes, devices=devices)


def make_mesh(shape, axes, devices=None):
    """A mesh whose axes are all ``Auto`` (GSPMD-partitioned)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(tuple(axes)),
                         devices=devices)


def describe(mesh) -> str:
    return " × ".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names)
