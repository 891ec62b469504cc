"""Benchmark harness — one function per paper table/figure + framework
benches.  Prints ``name,us_per_call,derived`` CSV rows.

  table1     paper Table 1: sync overhead, 4 schemes × 5 meshes (+ vs-paper)
  area       paper §4.2: tile/system area, NoC + FS shares
  scaling    beyond-paper: schedule scaling 2×2 → 64×64 (+ TPU projection)
  schedules  measured wall-time of the JAX collective schedules (16 host dev)
  schedule_matrix  Schedule-IR autotuning sweep: cost ranking × NoC replay ×
             measured lowering; asserts the butterfly↔ring payload crossover
  overlap    bucketed-superstep sweep: bucket size × per-bucket schedule vs
             monolithic; asserts overlap-aware predicted time < serial sum
  serve_bench  continuous-batching engine vs wave baseline on ragged output
             lengths; asserts the occupancy + tokens/step win
  probes     XLA cost_analysis while-loop probe (motivates hlo_analysis)

Usage: PYTHONPATH=src python -m benchmarks.run [--only NAME]
"""

import argparse
import os
import sys

# `schedules` executes real collectives: give this process 16 host devices
# BEFORE jax initializes (benchmarks only — tests/examples see 1 device).
if "XLA_FLAGS" not in os.environ or "device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=16 "
                               + os.environ.get("XLA_FLAGS", ""))

BENCHES = ("table1", "area", "scaling", "schedules", "schedule_matrix",
           "overlap", "serve_bench", "probes")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=BENCHES, default=None)
    args = ap.parse_args(argv)
    selected = [args.only] if args.only else list(BENCHES)

    print("name,us_per_call,derived")
    for name in selected:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        try:
            mod.run()
        except Exception as e:  # keep the harness going; record the failure
            print(f"{name},error,{type(e).__name__}:{str(e)[:120]}")
        sys.stdout.flush()


if __name__ == "__main__":
    main()
