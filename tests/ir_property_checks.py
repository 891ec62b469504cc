"""Randomized multi-device check: ir_all_reduce == dense reference reduction.

Run standalone (spawned by tests/test_schedule_properties.py as a subprocess
so the rest of the suite keeps a single-device jax):

    PYTHONPATH=src python tests/ir_property_checks.py

For a fixed-seed sweep of (schedule × mesh shape × payload shape) draws,
every generated Program is validated and its ``shard_map`` + ``ppermute``
lowering is compared against the dense reference: each shard of the output
must equal the sum of all input shards.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import collectives as C  # noqa: E402
from repro.core import schedule_ir as IR  # noqa: E402

SHAPES = ((8,), (2, 4), (4, 2), (2, 2, 2))
AXIS_POOL = ("a", "b", "c")

PASS = []


def lower(prog, mesh, axes, x):
    spec = P(axes)
    fn = jax.shard_map(lambda v: C.ir_all_reduce(v, prog, axes),
                       mesh=mesh, in_specs=spec, out_specs=spec,
                       check_vma=False, axis_names=frozenset(axes))
    return jax.jit(fn)(x)


def main():
    rng = np.random.default_rng(0xF5A1)
    for shape in SHAPES:
        world = int(np.prod(shape))
        axes = AXIS_POOL[:len(shape)]
        mesh = jax.make_mesh(shape, axes)
        for name in IR.SCHEDULES:
            prog = IR.build_program(name, shape)     # validates
            # randomized payload: leading dim a multiple of n_chunks
            mult = int(rng.integers(1, 4))
            width = int(rng.integers(1, 5))
            lead = prog.n_chunks * mult * world
            x = jnp.asarray(
                rng.integers(-8, 9, size=(lead, width)).astype(np.float32))
            out = lower(prog, mesh, tuple(axes), x)
            got = np.asarray(out).reshape(world, -1, width)
            want = np.asarray(x).reshape(world, -1, width).sum(0)
            for d in range(world):
                np.testing.assert_allclose(
                    got[d], want, rtol=1e-5, atol=1e-5,
                    err_msg=f"{name} on {shape}, shard {d}")
            PASS.append(f"{name}/{shape}")
            print(f"ok  ir_all_reduce {name} {shape} "
                  f"payload=({lead},{width})", flush=True)
    print(f"ALL OK ({len(PASS)} cases)")


if __name__ == "__main__":
    main()
