"""Serve scheduler: device-idle time per prompt chunk while the engine
prefills: the idle time inside the benchmark's ``bench.prefill_tick``
spans (each holds the tick's ``serve.prefill`` chunk: building and
uploading it, copy-on-write, dispatch, and on a prompt's last chunk the
first token's sampling and read-back and the prefix publish), over the
number of prefill program executions (``jit_serve_prefill*``, one per
chunk) in the trace.  Should move ``ttft_mean_ms``."""

from bench.harness import program
from bench.harness import readers
from bench.harness import trace as TR


def read(ctx):
    tr, dev = ctx["trace"], ctx["devices"][0]
    chunks = program.executions(tr, dev, program.PREFILL)
    ticks = readers.step_spans(ctx, "bench.prefill_tick")
    if not chunks or not ticks:
        return None
    busy = TR.busy(tr, dev)
    idle = sum(program.idle_ns(busy, s.start, s.end) for s in ticks)
    return idle / len(chunks) / 1e6
