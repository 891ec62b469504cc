"""Device: share of the traced serving window in which no operation ran
on the chip (1 - union of op intervals / window).  Should move
``itl_p95_ms``."""

from bench.harness import readers


def read(ctx):
    return readers.idle_share(ctx)
