"""Device: share of the traced training window in which no operation ran
on the first chip (1 - union of its op intervals / window).  Should move
``train_tokens_per_s``."""

from bench.harness import trace as TR


def read(ctx):
    tr = ctx["trace"]
    dev = ctx["devices"][0]
    lo, hi = tr.window()
    if hi <= lo or not tr.ops.get(dev):
        return None
    return 100.0 * (1.0 - TR.length(TR.busy(tr, dev, lo, hi)) / (hi - lo))
