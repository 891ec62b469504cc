"""Bulk Synchronous Parallel superstep runtime (paper §1, Valiant's BSP).

BSP structures parallel execution as *supersteps*: (1) local computation,
(2) communication, (3) global barrier.  The paper's whole point is making (3)
cheap and scalable; its only synchronization primitive is the barrier.

This module gives the training/serving stack a BSP-shaped API whose
communication phase runs one of the FractalSync-family schedules:

  * ``sync_gradients``  — flatten a gradient pytree, pad, all-reduce with the
    configured schedule (fractal | ring | xy | naive | hierarchical | xla),
    optionally compressing exchanged payloads, then mean + unflatten.
  * ``superstep``       — compute → communicate → fsync barrier, with the
    barrier token tied into the outputs (``barrier_tie``) so XLA cannot blur
    the superstep boundary.

Everything here runs *inside* ``shard_map`` over the sync axes; the "model"
axis stays in GSPMD's hands (``auto``), which is how per-rank local compute
keeps its tensor parallelism.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Optional, Sequence, Tuple, Union

import jax


from . import collectives
from .barrier import barrier_tie
from .collectives import fractal_barrier
from .cost_model import LinkParams


@dataclass(frozen=True)
class BSPConfig:
    """How a BSP step synchronizes.

    sync_axes   : mesh axes forming the synchronization tree, outermost first
                  (e.g. ("pod","data")); their product is the BSP world.
    schedule    : gradient all-reduce schedule (see collectives.SCHEDULES),
                  or "auto" — the cost-model autotuner picks at trace/build
                  time (core.autotune), per bucket when bucketing is on.
    compression : payload codec for the fractal schedule ("none"|"bf16"|"int8").
    fsync_level : barrier scope (None = root = whole world); lower levels
                  synchronize only a subtree (paper §3.2 domains).
    pad_align   : flat gradient vector padded to lcm(world, pad_align) so the
                  halving steps stay lane-aligned on TPU (128 lanes).
    bucket_mb   : partition the gradient pytree into ~this many MB per
                  bucket (reverse-layer order) and pipeline one collective
                  per bucket (core.superstep.SuperstepEngine); None → one
                  monolithic bucket (the pre-engine behavior); "auto" →
                  bucket boundaries searched by dynamic programming over
                  leaf prefix sums against the overlap-aware cost model
                  (greedy packing kept as the DP's upper bound/fallback).
    overlap     : the bucketing A/B switch — False disables bucketing even
                  when bucket_mb is set, collapsing the superstep back to
                  the monolithic single-collective baseline.
    bucket_codec: per-bucket wire-compression policy.  None → every bucket
                  uses the uniform ``compression`` codec (the historical
                  behavior); "auto" → the autotuner picks a codec PER
                  BUCKET through the cost model (large bandwidth-bound
                  buckets compress harder, small latency-bound tail buckets
                  skip compression); an explicit codec name forces it on
                  every fractal-scheduled bucket (no other lowering has a
                  wire-codec path — non-fractal buckets stay uncompressed).
    link        : cost-model link parameters the autotuner prices with;
                  None → the analytic TPU_V5E_ICI defaults.  Pass fitted
                  params from ``core.calibrate.fit_link_params`` (the train
                  CLI's ``--calibrate``) to tune against measured platform
                  numbers.
    """

    sync_axes: Tuple[str, ...] = ("data",)
    schedule: str = "fractal"
    compression: str = "none"
    fsync_level: Optional[int] = None
    pad_align: int = 128
    bucket_mb: Union[float, str, None] = None
    overlap: bool = True
    bucket_codec: Optional[str] = None
    link: Optional[LinkParams] = None

    def __post_init__(self):
        if self.schedule != "auto" and \
                self.schedule not in collectives.SCHEDULES:
            raise ValueError(f"unknown schedule {self.schedule!r}")
        if isinstance(self.bucket_mb, str):
            if self.bucket_mb != "auto":
                raise ValueError(f"bucket_mb must be a positive size in MB, "
                                 f"None, or 'auto'; got {self.bucket_mb!r}")
        elif self.bucket_mb is not None and self.bucket_mb <= 0:
            raise ValueError(f"bucket_mb must be positive, "
                             f"got {self.bucket_mb}")
        if self.bucket_codec not in (None, "auto", "none", "bf16", "int8"):
            raise ValueError(f"unknown bucket_codec {self.bucket_codec!r}")


def _world(sizes: Sequence[int]) -> int:
    return math.prod(sizes)


def make_codec(name: str):
    if name in (None, "none"):
        return None
    from repro.optim.compression import Bf16Codec, Int8Codec
    if name == "bf16":
        return Bf16Codec()
    if name == "int8":
        return Int8Codec()
    raise ValueError(f"unknown compression {name!r}")


def resolve_schedule(cfg: BSPConfig, sizes: Sequence[int],
                     payload_bytes: float) -> str:
    """Concrete schedule name for this config: "auto" → autotuner pick.

    Everything involved is host-static (mesh shape, padded flat length), so
    this is safe to call at trace/build time.
    """
    if cfg.schedule != "auto":
        return cfg.schedule
    from .autotune import pick_schedule
    if cfg.link is not None:
        return pick_schedule(tuple(sizes), payload_bytes, link=cfg.link)
    return pick_schedule(tuple(sizes), payload_bytes)


def sync_gradients(grads, cfg: BSPConfig, sizes: Sequence[int],
                   mean: bool = True):
    """All-reduce a gradient pytree with the configured schedule.

    Must be called inside ``shard_map`` over ``cfg.sync_axes``.  Returns the
    synchronized pytree (mean over the BSP world by default).

    Routed through the SuperstepEngine (``core.superstep``): with
    ``cfg.bucket_mb`` unset this is one monolithic bucket (the historical
    behavior); with it set, one pipelined collective per size-bounded
    bucket, schedule autotuned per bucket when ``schedule="auto"``.
    """
    world = _world(sizes)
    if world == 1:
        return grads
    from .superstep import engine_for
    return engine_for(grads, cfg, sizes).sync(grads, mean=mean)


def superstep(compute: Callable, communicate: Callable, cfg: BSPConfig,
              sizes: Sequence[int]):
    """Build one BSP superstep: local compute → communicate → fsync barrier.

    ``compute(*args)`` runs rank-local work; ``communicate(result)`` runs the
    communication phase (e.g. ``sync_gradients``); the returned callable ties
    the fsync token into every output leaf so the barrier orders supersteps.
    """

    def step(*args):
        local = compute(*args)
        exchanged = communicate(local)
        token = fractal_barrier(cfg.sync_axes, sizes, level=cfg.fsync_level)
        return jax.tree.map(lambda leaf: barrier_tie(leaf, token), exchanged)

    return step


def bsp_shard_map(fn: Callable, mesh: jax.sharding.Mesh,
                  in_specs, out_specs, sync_axes: Tuple[str, ...],
                  auto_axes: Tuple[str, ...] = ("model",)):
    """shard_map over the sync axes with the remaining axes left to GSPMD.

    This is the composition that lets the paper's explicit synchronization
    schedule coexist with XLA-managed tensor parallelism inside each rank.
    In jax 0.8 ``axis_names`` lists the axes shard_map handles *manually*;
    every other mesh axis (e.g. "model") stays auto (GSPMD).
    """
    del auto_axes  # everything not in sync_axes is auto by construction
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names=frozenset(sync_axes))
