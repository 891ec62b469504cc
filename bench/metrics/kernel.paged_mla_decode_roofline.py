"""Kernels: the MLA paged decode kernel's share of its roofline: the live
rows' q_eff, q_rope and output and the live tokens' latent (c_kv and the
rotary key, 1,152 bytes a token a layer) moved once, or its scores and
values at peak FLOP/s, whichever takes longer, over the device time of
the ``paged_mla_decode_attention`` ops in the decode program
(``jit_serve_decode``).  Should move ``itl_p95_ms``."""

from bench.harness import flops_mla_moe


def read(ctx):
    return flops_mla_moe.decode_share(ctx, kernel=True)
