"""Serve scheduler: share of the device's busy time in the traced window
spent in the prompt-prefill programs (``jit_serve_prefill`` and
``jit_serve_prefill_quiet``, the engine's names): the union of their op
intervals over the union of every op's.  Should move ``ttft_mean_ms``."""

from bench.harness import program
from bench.harness import trace as TR


def read(ctx):
    tr, dev = ctx["trace"], ctx["devices"][0]
    busy = TR.length(TR.busy(tr, dev))
    if not program.names_itself(tr, dev) or busy <= 0:
        return None
    return 100.0 * TR.length(program.busy_in(tr, dev, program.PREFILL)) \
        / busy
