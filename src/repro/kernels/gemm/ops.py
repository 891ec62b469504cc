"""jit'd public wrapper for the GEMM kernel: padding + dtype policy;
interpret mode on non-TPU backends."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu

from .kernel import gemm_pallas
from .ref import gemm_ref


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def gemm(x: jax.Array, y: jax.Array, *, block_m: int = 128,
         block_n: int = 128, block_k: int = 128,
         interpret: bool | None = None) -> jax.Array:
    """Padded blocked GEMM. interpret=None → auto (interpret off-TPU)."""
    interpret = (not on_tpu()) if interpret is None else interpret
    M, K = x.shape
    _, N = y.shape
    pm = (-M) % block_m
    pk = (-K) % block_k
    pn = (-N) % block_n
    xp = jnp.pad(x, ((0, pm), (0, pk))) if (pm or pk) else x
    yp = jnp.pad(y, ((0, pk), (0, pn))) if (pk or pn) else y
    out = gemm_pallas(xp, yp, block_m=block_m, block_n=block_n,
                      block_k=block_k, interpret=interpret)
    return out[:M, :N]


__all__ = ["gemm", "gemm_ref"]
