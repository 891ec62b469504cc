"""Kernels: the fused paged-attention decode kernel's share of its
roofline: the live rows' q and output and the live tokens' K/V moved
once (never the padded block-table width), at peak HBM bandwidth, over
the device time of the kernel's events in the decode program.  Should
move ``itl_p95_ms``."""

from bench.harness import readers


def read(ctx):
    return readers.decode_share(ctx, kernel=True)
