"""Elastic re-meshing: resume on the largest surviving fsync domain.

Recovery flow (exercised end-to-end in tests/test_elastic.py on host
devices):

  1. ``HostMonitor`` reports failed hosts → failed mesh tiles.
  2. ``surviving_domain`` (fault_tolerance) picks the largest complete
     synchronization subtree with no failed member — the paper's fsync
     domains make this a *structural* choice, not an ad-hoc one: the domain
     is exactly a node of the H-tree, so the surviving collective schedule
     is the same fractal schedule at a lower level.
  3. A new (smaller, power-of-two) mesh is built from the surviving devices;
     parameters are restored from the latest checkpoint into the new
     shardings; the data pipeline is re-sharded (global batch preserved by
     raising per-rank accumulation).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Iterable, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.tree import FractalTree
from repro.runtime.fault_tolerance import surviving_domain

Coord = Tuple[int, ...]


@dataclass(frozen=True)
class ElasticPlan:
    level: int                    # fsync level of the surviving domain
    tiles: Tuple[Coord, ...]      # surviving mesh coordinates
    mesh_shape: Tuple[int, ...]
    grad_accum_scale: int         # × gradient accumulation to keep batch

    @property
    def world(self) -> int:
        return len(self.tiles)


def plan_recovery(tree: FractalTree, failed: Iterable[Coord],
                  old_world: Optional[int] = None) -> ElasticPlan:
    level, tiles = surviving_domain(tree, failed)
    world = len(tiles)
    old_world = old_world or tree.num_tiles
    # keep global batch: each survivor takes old_world/world × the work
    scale = max(1, old_world // max(world, 1))
    # shape the new mesh as square-ish powers of two (data × model kept by
    # caller; here we only report the domain geometry)
    rows = 1 << (int(math.log2(world)) // 2)
    cols = world // rows
    return ElasticPlan(level=level, tiles=tiles, mesh_shape=(rows, cols),
                       grad_accum_scale=scale)


def build_mesh_from_tiles(tree: FractalTree, tiles: Sequence[Coord],
                          axis_names: Tuple[str, ...] = ("data", "model"),
                          devices=None,
                          mesh_shape: Optional[Tuple[int, ...]] = None):
    """Mesh over the surviving devices (device order follows tile order).

    ``mesh_shape`` overrides the square-ish default — e.g. ``(world, 1)``
    keeps all survivors on the data axis so the BSP sync domain stays the
    whole surviving fsync subtree (the train-soak recovery path).
    """
    devices = list(devices if devices is not None else jax.devices())
    flat_ids = []
    shape = tree.shape
    for t in tiles:
        flat = 0
        for c, d in zip(t, shape):
            flat = flat * d + c
        flat_ids.append(flat)
    world = len(tiles)
    if mesh_shape is None:
        plan = plan_recovery(tree,
                             [t for t in tree.tiles() if t not in set(tiles)])
        mesh_shape = plan.mesh_shape
    if math.prod(mesh_shape) != world:
        raise ValueError(f"mesh_shape {mesh_shape} does not cover "
                         f"{world} surviving tiles")
    if len(mesh_shape) != len(axis_names):
        raise ValueError(f"mesh_shape {mesh_shape} needs one entry per axis "
                         f"name {axis_names}")
    dev = np.array([devices[i] for i in flat_ids]).reshape(mesh_shape)
    return jax.sharding.Mesh(
        dev, axis_names=axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def reshard_state(state, mesh, spec_tree):
    """Re-place a (restored) host-side state onto the new mesh."""
    from repro.models.sharding import named
    shardings = named(mesh, spec_tree)
    return jax.device_put(state, shardings)
