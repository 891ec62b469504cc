"""Blocked GEMM Pallas kernel — the RedMulE analogue on TPU.

MAGIA's tile offloads MatMuls to RedMulE, a 24×8 semi-systolic FP array fed
from 32 TCDM banks (paper §2.1).  The TPU-native re-think (DESIGN.md §2):
the MXU is a 128×128 systolic array fed from VMEM, so the tiling becomes
128-aligned VMEM blocks with an f32 accumulator scratch that lives across the
K-loop — grid (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics), f32
accumulation regardless of input dtype (RedMulE likewise accumulates wider
than its inputs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(x_ref, y_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gemm_pallas(x: jax.Array, y: jax.Array, *, block_m: int = 128,
                block_n: int = 128, block_k: int = 128,
                out_dtype=None, interpret: bool = False) -> jax.Array:
    """x: [M,K] @ y: [K,N] → [M,N]; dims must divide by the block sizes
    (ops.py pads). MXU alignment: blocks should be multiples of 128."""
    M, K = x.shape
    K2, N = y.shape
    assert K == K2, (x.shape, y.shape)
    if M % block_m or N % block_n or K % block_k:
        raise ValueError(f"dims {(M, K, N)} not divisible by blocks "
                         f"{(block_m, block_k, block_n)}")
    out_dtype = out_dtype or x.dtype
    k_steps = K // block_k
    kernel = functools.partial(_gemm_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, N // block_n, k_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, y)
