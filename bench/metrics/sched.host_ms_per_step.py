"""Serve scheduler: host time per engine step in which the device was
idle: the benchmark's host span around each ``engine.step()`` minus the
device-busy time inside it, averaged over the traced steps.  Should move
``itl_p95_ms``."""

from bench.harness import readers
from bench.harness import trace as TR


def read(ctx):
    spans = readers.step_spans(ctx, "bench.engine_step")
    if not spans:
        return None
    busy = TR.busy(ctx["trace"], ctx["devices"][0])
    tot = 0.0
    for s in spans:
        tot += s.dur - TR.length(TR.clip(busy, s.start, s.end))
    return tot / len(spans) / 1e6
