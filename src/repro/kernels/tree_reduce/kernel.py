"""FractalSync-shaped tree reduction Pallas kernel.

On-chip analogue of the paper's H-tree: reduce N partial gradient rows to
one by **pairwise halving in log2(N) levels** — the same recursive-pairwise
order as the synchronization tree, which makes the reduction **bitwise
deterministic and independent of how partials arrived** (a linear
accumulation order changes with worker count; the tree order does not).
Used for micro-batch gradient-accumulation reduction inside a BSP rank
before the inter-chip fractal schedule takes over.

Grid: one program per 128-lane column block; the [N, block] tile reduces in
VMEM through log2(N) halvings (f32 accumulate).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _tree_reduce_kernel(x_ref, o_ref, *, levels: int):
    acc = x_ref[...].astype(jnp.float32)      # [N, block]
    n = acc.shape[0]
    for _ in range(levels):                   # pairwise halving: H-tree order
        half = n // 2
        acc = acc[:half] + acc[half:n]
        n = half
    o_ref[...] = acc[:1].astype(o_ref.dtype)


def tree_reduce_pallas(x: jax.Array, *, block: int = 512,
                       interpret: bool = False, out_dtype=None) -> jax.Array:
    """x: [N, D] → [D] pairwise-tree sum; N must be a power of two and
    D % block == 0 (ops.py pads).  ``out_dtype`` decouples the result
    dtype from the input — a bf16 *wire* payload accumulates in f32 and
    lands in the caller's accumulation dtype without a second launch
    (the fused-codec path of ``ops.coded_tree_reduce``)."""
    N, D = x.shape
    levels = int(math.log2(N))
    if 1 << levels != N:
        raise ValueError(f"N={N} not a power of two")
    if D % block:
        raise ValueError(f"D={D} not divisible by block={block}")
    kernel = functools.partial(_tree_reduce_kernel, levels=levels)
    out = pl.pallas_call(
        kernel,
        grid=(D // block,),
        in_specs=[pl.BlockSpec((N, block), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, block), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, D), out_dtype or x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
    return out[0]


# ---------------------------------------------------------------------------
# fused wire-codec variants: dequantize in VMEM, reduce in the same launch
# ---------------------------------------------------------------------------


# int8 codec blocks are tiled ROWS codec blocks at a time: a (ROWS, 128)
# int8 tile is one native (32, 128) int8 vreg tile on TPU, and a block's
# second-minor dim must be a multiple of 8 unless it spans the whole array
ROWS = 32


def _int8_tree_reduce_kernel(q_ref, s_ref, o_ref, *, levels: int):
    """ROWS 128-lane codec blocks: dequant q·scale in VMEM, then the same
    pairwise halving as ``_tree_reduce_kernel``.  H-tree order is
    preserved; only the dequant multiply may fuse into the first add
    (FMA), so fused vs dequant-then-reduce agree to an ulp, and the
    reduction stays deterministic in worker count."""
    acc = q_ref[...].astype(jnp.float32) * s_ref[...]      # [N, ROWS, 128]
    n = acc.shape[0]
    for _ in range(levels):
        half = n // 2
        acc = acc[:half] + acc[half:n]
        n = half
    o_ref[...] = acc[0].astype(o_ref.dtype)


def int8_tree_reduce_pallas(q: jax.Array, scale: jax.Array, *,
                            out_dtype=jnp.float32,
                            interpret: bool = False) -> jax.Array:
    """q: [N, nb, 128] int8 + scale: [N, nb, 1] f32 (per-row, per-128-lane
    codec blocks) → [nb*128] tree sum of the dequantized rows, one launch.
    N must be a power of two (ops.py pads with zero wire rows); nb is
    padded here to a multiple of ``ROWS`` with zero blocks."""
    N, nb, C = q.shape
    levels = int(math.log2(N))
    if 1 << levels != N:
        raise ValueError(f"N={N} not a power of two")
    pb = (-nb) % ROWS
    if pb:
        q = jnp.pad(q, ((0, 0), (0, pb), (0, 0)))
        scale = jnp.pad(scale, ((0, 0), (0, pb), (0, 0)))
    kernel = functools.partial(_int8_tree_reduce_kernel, levels=levels)
    out = pl.pallas_call(
        kernel,
        grid=((nb + pb) // ROWS,),
        in_specs=[pl.BlockSpec((N, ROWS, C), lambda j: (0, j, 0)),
                  pl.BlockSpec((N, ROWS, 1), lambda j: (0, j, 0))],
        out_specs=pl.BlockSpec((ROWS, C), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((nb + pb, C), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q, scale)
    return out[:nb].reshape(-1)


def _decode_add_bf16_kernel(k_ref, w_ref, o_ref):
    o_ref[...] = k_ref[...] + w_ref[...].astype(o_ref.dtype)


def _decode_add_int8_kernel(k_ref, q_ref, s_ref, o_ref):
    o_ref[...] = k_ref[...] + (q_ref[...].astype(jnp.float32)
                               * s_ref[...]).astype(o_ref.dtype)


def decode_add_bf16_pallas(keep: jax.Array, wire: jax.Array, *,
                           block: int = 512,
                           interpret: bool = False) -> jax.Array:
    """keep [M] + bf16 wire [M] → [M]: dequant+accumulate in one launch —
    the collective receive side of every fractal halving exchange.
    M % block == 0 (ops.py pads)."""
    M = keep.shape[0]
    out = pl.pallas_call(
        _decode_add_bf16_kernel,
        grid=(M // block,),
        in_specs=[pl.BlockSpec((1, block), lambda j: (0, j)),
                  pl.BlockSpec((1, block), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, block), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, M), keep.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(keep[None], wire[None])
    return out[0]


def decode_add_int8_pallas(keep: jax.Array, q: jax.Array, scale: jax.Array,
                           *, interpret: bool = False) -> jax.Array:
    """keep [M] + int8 wire (q [M/128, 128], scale [M/128, 1]) → [M]:
    per-block dequant fused into the accumulate, one launch.  The codec
    blocks are tiled ``ROWS`` at a time (nb padded with zero blocks)."""
    nb, C = q.shape
    keep = keep.reshape(nb, C)
    pb = (-nb) % ROWS
    if pb:
        keep = jnp.pad(keep, ((0, pb), (0, 0)))
        q = jnp.pad(q, ((0, pb), (0, 0)))
        scale = jnp.pad(scale, ((0, pb), (0, 0)))
    out = pl.pallas_call(
        _decode_add_int8_kernel,
        grid=((nb + pb) // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, C), lambda j: (j, 0)),
                  pl.BlockSpec((ROWS, C), lambda j: (j, 0)),
                  pl.BlockSpec((ROWS, 1), lambda j: (j, 0))],
        out_specs=pl.BlockSpec((ROWS, C), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((nb + pb, C), keep.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(keep, q, scale)
    return out[:nb].reshape(-1)
