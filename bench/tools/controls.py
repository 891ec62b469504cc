"""Readings that set a cell's correctness limits, on the chip at the cell's
own size, all seeds in one process.

  python bench/tools/controls.py --workload serve.phi4.chat \
      --seeds 11,12,13 --seconds 30
  python bench/tools/controls.py --workload train.qwen.dp4 \
      --seeds 11,12,13 --faults half_batch,exchange_left_out

For every seed: a sound run of the cell's timed path (its compared
numbers: the lower readings) and the control, the plain reference in the
next lower precision than the configuration states, put in the program's
place (the configuration's ``correct.control_precision``: fp8 for a
model that states bfloat16, bf16 for one that states float32 at the
default matmul precision), judged by the same check: its numbers are the
upper readings, and it has to come out not correct.  With ``--faults``,
each named fault of ``bench/harness/faults.py`` is planted under the
timed path and read too.  One JSON line per reading.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--faults", default="")
    ap.add_argument("--control-seeds", type=int, default=99)
    a = ap.parse_args(argv)

    from bench import run as bench_run
    from bench.harness import common as H
    from bench.harness import faults as FL
    from bench.harness import reference as R
    from bench.harness import spec

    cell = spec.resolve(a.workload)
    devices = bench_run.check_devices(cell.chips)
    bench_run.use_cache()
    drv = spec.driver(cell.config)
    c = cell.config
    seeds = [int(s) for s in a.seeds.split(",")]
    memo = {}
    real_train = R.train

    def train_memo(c_, seed, batches, precision="f32", **kw):
        key = (seed, precision)
        if key not in memo:
            memo[key] = real_train(c_, seed, batches, precision, **kw)
        return memo[key]
    R.train = train_memo

    def emit(**row):
        print(json.dumps(row), flush=True)

    for k, seed in enumerate(seeds):
        t0 = time.monotonic()
        run = drv.run(cell, devices=devices, seed=seed, seconds=a.seconds,
                      trace=False, process_start=t0, workdir=ROOT)
        emit(seed=seed, kind="program", correct=run.correct,
             readings={n: v["value"] for n, v in run.checks.items()},
             peak=run.memory_peak_bytes, extra=run.extra,
             seconds=time.monotonic() - t0)
        if k >= a.control_seeds:
            continue
        o = run.outputs
        low = c["correct"]["control_precision"]
        if c["driver"] == "serve":
            checks = drv.check_outputs(c, seed, o["finished"], o["prompts"],
                                       low)
        else:
            ctrl = real_train(c, seed, o["batches"], low, devices=devices)
            checks = drv.compare(c, ctrl["losses"], ctrl["grad_norms"],
                                 ctrl["delta_norms"], o["reference"])
        emit(seed=seed, kind="control_" + low, correct=H.all_ok(checks),
             readings={n: v["value"] for n, v in checks.items()})
        del run, o
        for name in filter(None, a.faults.split(",")):
            hook = (FL.SERVE if c["driver"] == "serve" else FL.TRAIN)[name]
            run = drv.run(cell, devices=devices, seed=seed,
                          seconds=a.seconds, trace=False,
                          process_start=time.monotonic(), workdir=ROOT,
                          fault=hook)
            emit(seed=seed, kind="fault:" + name, correct=run.correct,
                 readings={n: v["value"] for n, v in run.checks.items()})
            del run


if __name__ == "__main__":
    main()
