"""Serve scheduler: device-idle time per decode step before the decode
program starts: in each of the benchmark's ``bench.decode_tick`` spans
that ran the decode program (``jit_serve_decode``, the engine's name),
the idle time from the span's start to the program's start (the
engine's ``serve.decode.prepare``: block growth, the slot inputs and
block tables built and uploaded; and its dispatch), averaged over those
steps.  Should move ``itl_p95_ms``."""

from bench.harness import program
from bench.harness import readers
from bench.harness import trace as TR


def read(ctx):
    tr, dev = ctx["trace"], ctx["devices"][0]
    decodes = program.executions(tr, dev, program.DECODE)
    if not decodes:
        return None
    busy = TR.busy(tr, dev)
    tot, n = 0.0, 0
    for s in readers.step_spans(ctx, "bench.decode_tick"):
        starts = [m.start for m in decodes if s.start <= m.start < s.end]
        if starts:
            tot += program.idle_ns(busy, s.start, min(starts))
            n += 1
    return tot / n / 1e6 if n else None
