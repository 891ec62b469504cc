"""Model step: the MLA + MoE decode step's share of its roofline.  For
each traced step, the least time the chip needs (every weight outside the
routed experts read once, the held experts that the step's routing
counter shows were hit read once, the live latent read and the new one
written; or the FLOPs of the live rows, of the routed (token, expert)
pairs and of latent attention, at peak) over the device time of the
decode program; summed over steps.  Should move ``itl_p95_ms``."""

from bench.harness import flops_mla_moe


def read(ctx):
    return flops_mla_moe.decode_share(ctx, kernel=False)
