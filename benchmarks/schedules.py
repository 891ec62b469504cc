"""Measured wall-time of the JAX collective schedules (16 host devices).

The container's empirical analogue of Table 1: the same payload all-reduced
through fractal / ring / xy / naive / xla schedules, timed.  Host-device
collectives go through shared memory, so ratios are indicative (latency
structure), not ICI-accurate — the ICI numbers come from the dry-run +
cost model.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.core import collectives as C


def _bench(fn, x, iters=20):
    fn(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def run() -> None:
    n_dev = len(jax.devices())
    if n_dev < 16:
        print(f"schedules,skip,needs 16 devices (have {n_dev})")
        return
    mesh = make_mesh((4, 4), ("a", "b"))
    axes, sizes = ("a", "b"), (4, 4)
    world = 16

    for elems in (2**14, 2**20):
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(world * elems // 16, 16)).astype(np.float32))
        spec = P(("a", "b"))

        def make(schedule):
            def f(v):
                return C.all_reduce(v, schedule, axes, sizes)
            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=spec, out_specs=spec,
                check_vma=False, axis_names=frozenset(axes)))

        base = None
        for sched in ("xla", "fractal", "ring", "xy", "naive"):
            us = _bench(make(sched), x)
            if sched == "fractal":
                base = us
            ratio = f";vs_fractal={us/base:.2f}x" if base else ""
            print(f"schedules/allreduce_{elems*4//1024}KiB/{sched},"
                  f"{us:.0f},{ratio[1:] if ratio else ''}")

    # pure barrier (the paper's regime: payload → 0)
    tok = jnp.ones((16, 16), jnp.float32)

    def barrier(schedule):
        def f(v):
            if schedule == "fractal":
                t = C.fractal_barrier(axes, sizes).astype(jnp.float32)
            else:
                tok = jnp.ones((world, 1), jnp.float32)  # world-divisible
                t = C.all_reduce(tok, schedule, axes, sizes)[0, 0]
            return v + t * 0
        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P(("a", "b")), out_specs=P(("a", "b")),
            check_vma=False, axis_names=frozenset(axes)))

    for sched in ("fractal", "ring", "naive", "xla"):
        us = _bench(barrier(sched), tok, iters=50)
        print(f"schedules/barrier/{sched},{us:.0f},")
