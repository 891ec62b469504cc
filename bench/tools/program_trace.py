"""What the serving program's own spans say about a profiler trace.

  python bench/tools/program_trace.py <log dir or .xplane.pb>
  python bench/tools/program_trace.py --workload serve.phi4.chat \
      --seed 7 --seconds 51 [--keep .bench_trace/kept]

The first form reads a trace.  The second makes one on the chip: the
cell's traced run, as ``bench/run.py --trace 1`` makes it, with the trace
moved under ``--keep`` where the run would remove it, then read; the
run's metrics, end-to-end (of the traced window) and per-layer, and the
TTFT of the requests served before the profiler started, split into
queue wait, prefill wait and the request's own chunks, are printed first.

Printed, one JSON object per line: each ``serve.*`` span name with its
count and host time; the device's op time by name; the longest
device-idle gaps, each labelled by the innermost program span and the
benchmark span open at its middle, and the programs that ran just
before and just after it; and the device-idle time inside the
``bench.engine_step`` spans, per step, split by the ``serve.*`` leaf span
(a span holding no other) that covers it, with the share that some leaf
covers.  A tool only: no metric reads it.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def summarize(path: str, top: int = 12) -> dict:
    from bench.harness import program as PG
    from bench.harness import trace as TR

    tr = TR.load(path)
    spans = PG.program_spans(path)
    out: dict = {"spans": {}}
    for s in spans:
        row = out["spans"].setdefault(s.name, {"count": 0, "host_ms": 0.0})
        row["count"] += 1
        row["host_ms"] += s.dur / 1e6
    if not tr.devices:
        return out
    dev = tr.devices[0]
    out["device_ops_s"] = TR.op_time_by_name(tr, [dev])[:top]
    lo, hi = tr.window()
    gaps = sorted(TR.gaps(TR.busy(tr, dev), lo, hi),
                  key=lambda iv: iv[0] - iv[1])[:top]
    mods = tr.modules.get(dev, [])

    def around(s, e):
        before = [m for m in mods if m.start < s]
        after = [m for m in mods if m.end > e]
        return (PG.program_name(before[-1].name) if before else "",
                PG.program_name(after[0].name) if after else "")
    out["idle_gaps_ms"] = [
        [PG.span_at(spans, (s + e) / 2), TR.span_at(tr.spans, (s + e) / 2),
         (e - s) / 1e6, *around(s, e)] for s, e in gaps]
    steps = [s for s in tr.spans if s.name == "bench.engine_step"]
    if steps:
        by_leaf = PG.idle_by_leaf(tr, dev, steps, spans)
        out["steps"] = len(steps)
        out["step_idle_ms_per_step"] = {
            k: v / 1e6 / len(steps) for k, v in
            sorted(by_leaf.items(), key=lambda kv: -kv[1])}
        out["step_idle_under_leaf_pct"] = PG.leaf_share(by_leaf)
    return out


def run_and_keep(workload: str, seed: int, seconds: float, keep: Path
                 ) -> Path:
    """The cell's traced run, as ``bench/run.py --trace 1`` makes it, with
    the trace moved to ``keep`` where the run would remove it.  Prints the
    run's end-to-end metrics (which the traced window's own ticks give),
    its per-layer metrics and its extra counts."""
    from bench import run as bench_run
    from bench.harness import common, spec

    cell = spec.resolve(workload)
    bench_run.check_checkout()
    devices = bench_run.check_devices(cell.chips)
    bench_run.use_cache()

    def move(d):
        if d is not None:
            shutil.rmtree(keep, ignore_errors=True)
            keep.parent.mkdir(parents=True, exist_ok=True)
            shutil.move(str(d), str(keep))
    common.discard = move
    firsts = _record_first_tokens()
    drv = spec.driver(cell.config)
    run = drv.run(
        cell, devices=devices, seed=seed, seconds=seconds, trace=True,
        process_start=bench_run.PROCESS_START, workdir=bench_run.ROOT)
    per_layer = {m["name"]: spec.metric_reader(m["name"])(run.ctx)
                 for m in cell.per_layer}
    w0 = float(cell.traffic.get("warmup_s", 0.0))
    print(json.dumps({"correct": run.correct, "traced_end_to_end":
                      run.metrics, "per_layer": per_layer,
                      "ttft_parts": ttft_parts(
                          firsts, w0, w0 + seconds - min(seconds,
                                                         drv.TRACE_S)),
                      "extra": run.extra}), flush=True)
    if not keep.is_dir():
        raise SystemExit("the run made no trace")
    return keep


def _record_first_tokens() -> dict:
    """Request id → (arrival, admitted, first token, prefill wait), as
    each request's first token comes (serve clock, seconds)."""
    from repro.serve.metrics import ServeMetrics

    seen: dict = {}
    real = ServeMetrics.on_first_token

    def on_first_token(self, req_id):
        real(self, req_id)
        r = self.requests[req_id]
        seen[req_id] = (r.arrival_s, r.admitted_s, r.first_token_s,
                        r.prefill_wait_s)
    ServeMetrics.on_first_token = on_first_token
    return seen


def ttft_parts(firsts: dict, lo: float, hi: float) -> dict:
    """TTFT of the requests that arrived from ``lo`` on and had their
    first token before ``hi`` (the profiler's start), split into queue
    wait, prefill wait (steps in which another slot's chunk ran) and the
    rest (the request's own chunks): means, and the prefill wait's
    percentiles, in ms."""
    import numpy as np

    rows = np.array([(adm - arr, wait, ft - adm - wait)
                     for arr, adm, ft, wait in firsts.values()
                     if arr >= lo and ft < hi], np.float64).reshape(-1, 3)
    if not len(rows):
        return {}
    ms = 1e3 * rows
    return {"requests": len(rows),
            "mean_ms": dict(zip(("queue_wait", "prefill_wait", "own_chunks"),
                                ms.mean(axis=0).tolist())),
            "prefill_wait_p50_ms": float(np.percentile(ms[:, 1], 50)),
            "prefill_wait_p90_ms": float(np.percentile(ms[:, 1], 90))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--keep", default=".bench_trace/kept")
    a = ap.parse_args(argv)
    if a.workload:
        path = run_and_keep(a.workload, a.seed, a.seconds, Path(a.keep))
    elif a.trace:
        path = Path(a.trace)
    else:
        ap.error("give a trace, or --workload and --seed")
    for k, v in summarize(str(path)).items():
        print(json.dumps({k: v}), flush=True)


if __name__ == "__main__":
    main()
