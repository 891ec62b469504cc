"""Schedule × payload autotuning matrix — the Schedule IR end to end.

For each (mesh shape, payload) cell: rank every IR schedule with the
cost-model backend, replay the winner's IR on the NoC simulator, and (when
enough host devices exist) measure the jitted JAX lowering — the three
backends of the same IR program side by side.  The sweep demonstrates the
expected crossover: the latency-optimal butterfly wins small payloads, the
bandwidth-optimal ring wins large ones, and ``BSPConfig(schedule="auto")``
picks accordingly.

Results are persisted machine-readably to ``BENCH_schedules.json``
(predicted rankings, NoC replay cycles, measured refinements, speedup of
the auto pick vs the serial Naïve baseline) so the perf trajectory is
tracked across PRs.

Standalone: PYTHONPATH=src python -m benchmarks.schedule_matrix [--out F]
Harness:    PYTHONPATH=src python -m benchmarks.run --only schedule_matrix
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import autotune, cost_model as CM, schedule_ir as IR
from repro.core.simulator import schedule_on_noc

SHAPES = ((2, 2), (4, 4), (8, 8), (16, 16))
PAYLOADS_B = (256, 4e5, 4e7)   # near-pure-control, 100K and 10M f32 grads
CROSSOVER_SHAPES = SHAPES[1:]  # on 2×2 ring≡butterfly (all links adjacent)
MEASURE_SHAPE = (4, 4)                 # 16 host devices when available


def _measure_fn(mesh, axes, sizes, n_bytes):
    """measure(schedule) → seconds for the jitted IR lowering (host devs)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import collectives as C

    world = int(np.prod(sizes))
    # per-device shard's leading dim must divide by the chunk count (world)
    unit = world * world * 16
    elems = max(unit, int(n_bytes) // 4 // unit * unit)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(elems // 16, 16)).astype(np.float32))
    spec = P(axes)

    def measure(schedule: str) -> float:
        fn = jax.jit(jax.shard_map(
            lambda v: C.all_reduce(v, schedule, axes, sizes),
            mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False,
            axis_names=frozenset(axes)))
        fn(x).block_until_ready()
        t0 = time.perf_counter()
        iters = 10
        for _ in range(iters):
            out = fn(x)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters

    return measure


def run(out: str = "BENCH_schedules.json") -> None:
    link = CM.MAGIA
    flit_bytes = 4  # 32-bit NoC flits
    print("schedule_matrix/mesh,payload_B,auto_pick,cost_ranking,"
          "noc_cycles_winner")
    crossover = {}
    results = {"cells": [], "measured": []}
    for shape in SHAPES:
        for vol in PAYLOADS_B:
            result = autotune.autotune(shape, vol, link=link)
            ranking = " ".join(f"{n}:{c * 1e6:.2f}us"
                               for n, c in result.ranking[:3])
            prog = IR.build_program(result.schedule, shape)
            flits = max(1, int(vol / flit_bytes))
            replay = schedule_on_noc(prog, payload_flits=min(flits, 4096))
            print(f"schedule_matrix/{shape[0]}x{shape[1]},{vol:.0e},"
                  f"{result.schedule},{ranking},{replay.overhead}")
            crossover[(shape, vol)] = result.schedule
            costs = dict(result.ranking)
            results["cells"].append({
                "shape": list(shape), "payload_B": vol,
                "chosen": result.schedule,
                "predicted_s": dict(result.ranking),
                "noc_cycles_chosen": int(replay.overhead),
                "speedup_vs_naive": (costs["naive"] / costs[result.schedule]
                                     if costs.get(result.schedule)
                                     else None),
            })

    # the sweep's headline claim, asserted so regressions are loud
    small = [crossover[(s, PAYLOADS_B[0])] for s in CROSSOVER_SHAPES]
    large = [crossover[(s, PAYLOADS_B[-1])] for s in CROSSOVER_SHAPES]
    assert all(p == "fractal" for p in small), \
        f"latency regime should pick the butterfly, got {small}"
    assert all(p == "ring" for p in large), \
        f"bandwidth regime should pick the ring, got {large}"
    print("schedule_matrix/crossover,ok,"
          "small→fractal large→ring as predicted")

    # measured refinement on real host devices (skipped when too few)
    try:
        import jax
        if len(jax.devices()) >= int(np.prod(MEASURE_SHAPE)):
            mesh = jax.make_mesh(MEASURE_SHAPE, ("a", "b"))
            measure = _measure_fn(mesh, ("a", "b"), MEASURE_SHAPE, 4e5)
            tuned = autotune.autotune(MEASURE_SHAPE, 4e5, link=link,
                                      measure=measure, measure_top_k=3)
            rows = " ".join(f"{n}:{t * 1e6:.0f}us" for n, t in tuned.measured)
            print(f"schedule_matrix/measured_{MEASURE_SHAPE[0]}x"
                  f"{MEASURE_SHAPE[1]},4e5,{tuned.schedule},{rows},")
            results["measured"].append({
                "shape": list(MEASURE_SHAPE), "payload_B": 4e5,
                "chosen": tuned.schedule,
                "predicted_s": dict(tuned.ranking),
                "measured_s": dict(tuned.measured),
            })
        else:
            print("schedule_matrix/measured,skip,"
                  f"needs {np.prod(MEASURE_SHAPE)} devices,")
    except Exception as e:  # measurement is optional refinement, not gating
        print(f"schedule_matrix/measured,error,{type(e).__name__},")

    if out:
        with open(out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"schedule_matrix/json,written,{out}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_schedules.json",
                    help="machine-readable results path ('' disables)")
    args = ap.parse_args(argv)
    run(out=args.out)


if __name__ == "__main__":
    import os
    if "device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=16 "
            + os.environ.get("XLA_FLAGS", ""))
    main()
