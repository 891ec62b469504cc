"""Find the highest arrival rate a serving cell sustains, once, on the chip.

  python bench/tools/sweep.py --workload serve.phi4.chat \
      --rates 1,1.5,2,2.5,3 --segment-s 45 [--seed 3]

One process builds the cell's engine, then offers the cell's mix at each
rate in turn (ascending, ``segment-s`` seconds each, no pause between).
For each rate it prints one JSON line: requests offered, first tokens
and completions in the segment, the backlog (queued requests) at its
start and end, TTFT p50/p90 of the segment's requests, tokens per second.
A rate is sustained while the backlog does not grow over its segment.
The cell's traffic file then takes about four fifths of the knee.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T0 = time.monotonic()
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--segment-s", type=float, default=45.0)
    ap.add_argument("--seed", type=int, default=3)
    a = ap.parse_args(argv)

    import jax
    import numpy as np
    from repro.models import transformer as T
    from repro.serve.queue import Request

    from bench import run as bench_run
    from bench.harness import model as M
    from bench.harness import spec
    from bench.harness import traffic as TF
    from bench.harness import weights as W

    cell = spec.resolve(a.workload)
    devices = bench_run.check_devices(cell.chips)
    bench_run.use_cache()
    drv = spec.driver(cell.config)
    c = cell.config
    st = devices[0].memory_stats() or {}
    print(json.dumps({"bytes_limit": st.get("bytes_limit"),
                      "kind": devices[0].device_kind}), flush=True)
    cfg = M.arch_config(c)
    shapes = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    params = W.make_params(shapes, a.seed,
                           jax.sharding.SingleDeviceSharding(devices[0]))
    engine = drv._engine(cfg, params, c)
    del params
    base = 0
    all_reqs = []
    for i, rate in enumerate(float(r) for r in a.rates.split(",")):
        mix = dict(cell.traffic, warmup_s=0.0,
                   arrival=dict(cell.traffic["arrival"], rate_per_s=rate))
        reqs = TF.requests(mix, a.segment_s, a.seed + i, int(c["vocab_size"]))
        all_reqs += reqs
        if i == 0:
            drv._warm_up(engine, all_reqs, c)
            engine.metrics.start()
            print(json.dumps({"setup_s": time.monotonic() - T0,
                              "peak_bytes": devices[0].memory_stats().get(
                                  "peak_bytes_in_use")}), flush=True)
        m = engine.metrics
        t_start = m.now()
        engine.submit([Request(req_id=base + r.req_id,
                               prompt=r.prompt.tolist(),
                               max_new_tokens=r.max_new_tokens,
                               arrival_s=t_start + r.arrival_s)
                       for r in reqs])
        ids = {base + r.req_id for r in reqs}
        base += len(reqs)
        q0 = len(engine.queue)
        tok0 = m.tokens_out
        steps = 0
        while m.now() < t_start + a.segment_s:
            if not engine.table.busy():
                nxt = engine.queue.next_arrival()
                if nxt is None or nxt > m.now():
                    m.wait_until(min(t_start + a.segment_s, nxt or 1e18))
                    continue
            engine.step()
            steps += 1
        t_end = m.now()
        recs = [m.requests[i] for i in ids if i in m.requests]
        due = [r for r in recs if r.arrival_s < t_end]
        ttft = [r.ttft_s for r in due if r.ttft_s is not None]
        print(json.dumps({
            "rate": rate, "offered": len(due),
            "first_tokens": len(ttft),
            "completed": sum(1 for r in due if r.finished_s is not None),
            "backlog_start": q0,
            "backlog_end": sum(1 for r in due if r.admitted_s is None),
            "busy_slots_end": len(engine.table.busy()),
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else None,
            "ttft_p90_s": float(np.percentile(ttft, 90)) if ttft else None,
            "tokens_per_s": (m.tokens_out - tok0) / (t_end - t_start),
            "steps_per_s": steps / (t_end - t_start),
            "preemptions": m.preemptions}), flush=True)


if __name__ == "__main__":
    main()
