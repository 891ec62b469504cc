"""Readings that set a DeepSeek-V3 serving cell's correctness limits, on
the chip at the cell's own size, all seeds in one process, each judged by
the limits the configuration file holds.

  python bench/tools/controls_mla_moe.py --workload serve.dsv3.reason \
      --seeds 11,12 --seconds 51 --faults token_altered --fault-seconds 10

``controls.py``'s readings for the ``serve_mla_moe`` driver: for every
seed a sound run of the cell's timed path (the lower readings) and the
control (the plain reference at the configuration's
``correct.control_precision`` in the program's place, judged by the same
check: the upper readings); the check's lines on standard error name
each tie-judged token.  With ``--faults``, each named fault of
``bench/harness/faults.py`` is planted under the timed path of a run of
``--fault-seconds`` and read too.  One JSON line per reading.
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
    ap.add_argument("--fault-seconds", type=float, default=None)
    a = ap.parse_args(argv)

    from bench import run as bench_run
    from bench.harness import common as H
    from bench.harness import faults as FL
    from bench.harness import spec

    cell = spec.resolve(a.workload)
    c = cell.config
    if c["driver"] != "serve_mla_moe":
        raise SystemExit(f"{a.workload}: driver {c['driver']!r}; "
                         "bench/tools/controls.py reads it")
    devices = bench_run.check_devices(cell.chips)
    bench_run.use_cache()
    drv = spec.driver(c)

    def emit(**row):
        print(json.dumps(row), flush=True)

    def readings(checks):
        return {n: v["value"] for n, v in checks.items()}

    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.monotonic()
        run = drv.run(cell, devices=devices, seed=seed, seconds=a.seconds,
                      trace=False, process_start=t0, workdir=ROOT)
        emit(seed=seed, kind="program", correct=run.correct,
             readings=readings(run.checks), metrics=run.metrics,
             peak=run.memory_peak_bytes, extra=run.extra,
             seconds=time.monotonic() - t0)
        o = run.outputs
        del run
        low = c["correct"]["control_precision"]
        checks = drv.check_outputs(c, seed, o["finished"], o["prompts"], low)
        emit(seed=seed, kind="control_" + low, correct=H.all_ok(checks),
             readings=readings(checks))
        del o
        for name in filter(None, a.faults.split(",")):
            run = drv.run(cell, devices=devices, seed=seed,
                          seconds=a.fault_seconds or a.seconds, trace=False,
                          process_start=time.monotonic(), workdir=ROOT,
                          fault=FL.SERVE[name])
            emit(seed=seed, kind="fault:" + name, correct=run.correct,
                 readings=readings(run.checks))
            del run


if __name__ == "__main__":
    main()
