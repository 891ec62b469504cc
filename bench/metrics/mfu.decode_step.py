"""Model step: the whole decode step's share of its roofline.  For each
traced step, the least time the chip needs (every weight and the live
tokens' K/V read once, or the live rows' FLOPs at peak, whichever is
longer) over the device time of the decode program; summed over steps.
Should move ``itl_p95_ms``."""

from bench.harness import readers


def read(ctx):
    return readers.decode_share(ctx, kernel=False)
