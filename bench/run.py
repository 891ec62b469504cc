"""Run one benchmark cell once, on the chips of this machine.

  python bench/run.py --workload serve.phi4.chat --seed 7 --seconds 40 \
      --trace 0

Reads BENCHMARK.json at the checkout's root, finds the cell's
configuration, traffic mix and driver by name (``bench/harness/spec.py``),
makes inputs and weights from ``--seed``, warms up every shape the cell
uses (that is set-up), measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints one JSON object as
the last line of standard output:

  {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
   "device": {...}, ["breakdown": {...},] "checks": {...}}

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of the window gives its per-layer metrics,
``device.busy_s``/``window_s`` and the breakdown.  ``checks`` holds each
number the correctness check compared, beside its limit; the same lines
end standard error.  Without a TPU, or with fewer chips than the cell
asks for, or outside a checkout of the program, it exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


class NoDevice(SystemExit):
    """The machine cannot run the cell: no result is printed."""


def check_checkout(root: Path = ROOT) -> None:
    if not (root / "src" / "repro").is_dir():
        raise NoDevice(f"bench: {root} holds no program (src/repro is "
                       "missing); run from a checkout of the repository")
    sys.path.insert(0, str(root / "src"))


def check_devices(chips: int, devices=None):
    """The cell's chips, or exit: a TPU of a known kind, at least
    ``chips`` of them.  Never falls back to another platform."""
    import jax

    from bench.harness.flops import peaks

    devices = devices if devices is not None else jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoDevice(f"bench: no TPU found (JAX's devices are "
                       f"{platform!r}); this benchmark runs on TPU chips")
    if len(devices) < chips:
        raise NoDevice(f"bench: the cell needs {chips} chips, JAX sees "
                       f"{len(devices)}")
    peaks(devices[0].device_kind)          # unknown kind → error
    return list(devices[:chips])


def use_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path
    (``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``); every
    program is kept, however quick its compile, so a second run of a
    cell compiles nothing."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_info(devices, peak_bytes: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak_bytes)}


def emit(result: dict, checks: dict) -> None:
    """Print the checks on stderr and the result line on stdout, with the
    checks as its last key."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"({c['rule']})", file=sys.stderr)
    out = dict(result)
    out["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import spec

    cell = spec.resolve(args.workload)
    check_checkout()
    devices = check_devices(cell.chips)
    use_cache()
    drv = spec.driver(cell.config)
    run = drv.run(cell, devices=devices, seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace),
                  process_start=PROCESS_START, workdir=ROOT)
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed}
    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(run.ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        dev = device_info(devices, run.memory_peak_bytes)
        dev["busy_s"] = run.ctx["busy_s"]
        dev["window_s"] = run.ctx["window_s"]
        result["device"] = dev
        result["breakdown"] = run.ctx["breakdown"]
    else:
        result["metrics"] = {m["name"]: {"value": run.metrics[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device_info(devices, run.memory_peak_bytes)
    for k, v in run.extra.items():
        result[k] = v
    emit(result, run.checks)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoDevice as e:
        print(e.code if isinstance(e.code, str) else e, file=sys.stderr)
        sys.exit(2)
