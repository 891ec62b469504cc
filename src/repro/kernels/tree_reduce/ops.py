"""Public tree-reduce ops: padding + interpret mode off-TPU + fused codecs.

Besides the plain ``tree_reduce``, this module owns the *codec-fused*
variants that collapse the wire-codec dequantize into the reduction /
accumulate launch:

  * ``encode_rows``       — per-row wire encoding of an [N, D] stack.
  * ``coded_tree_reduce`` — H-tree sum of N wire-encoded rows without a
    separate dequant pass (int8 dequants in VMEM; bf16 rides the f32
    accumulator of the plain kernel).
  * ``decode_add``        — ``keep + decode(wire)`` in one launch: the
    receive side of every fractal halving exchange
    (``core/collectives._codec_exchange_add``).

Fusing drops one kernel launch per codec use, which is exactly the
per-step α overhead ``core/autotune.CODEC_STEP_ALPHAS`` prices —
the calibrated bucket tuner picks the cheaper codecs up automatically.

Off-TPU, ``decode_add`` is EXACTLY the jnp expression
``keep + codec.decode(wire)`` so collective token/bit-identity tests are
unaffected; ``interpret=True`` forces the kernel for parity tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu

from .kernel import (decode_add_bf16_pallas, decode_add_int8_pallas,
                     int8_tree_reduce_pallas, tree_reduce_pallas)
from .ref import tree_reduce_ref


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def tree_reduce(x: jax.Array, *, block: int = 512,
                interpret: bool | None = None) -> jax.Array:
    """[N, D] → [D] deterministic pairwise-tree sum. N padded up to a power
    of two with zeros; D padded to the block size."""
    interpret = (not on_tpu()) if interpret is None else interpret
    N, D = x.shape
    n2 = 1 << max(1, (N - 1).bit_length())
    block = min(block, 1 << (D - 1).bit_length() if D else block)
    pd = (-D) % block
    xp = jnp.pad(x, ((0, n2 - N), (0, pd)))
    out = tree_reduce_pallas(xp, block=block, interpret=interpret)
    return out[:D]


# ---------------------------------------------------------------------------
# fused wire codecs
# ---------------------------------------------------------------------------

_CODEC_BLOCK = 128          # int8 codec group == one TPU lane row


def encode_rows(x: jax.Array, codec: str):
    """Per-row wire encoding of an [N, D] stack of reduction operands.

    Unlike ``optim.compression.Int8Codec.encode`` (which groups along the
    leading axis of a flat payload), rows here are independent wire
    messages, so int8 groups run along D: q [N, D/128, 128] int8 +
    scale [N, D/128, 1] f32.  D must be a multiple of 128 for int8.
    """
    if codec == "none":
        return {"x": x}
    if codec == "bf16":
        return {"x": x.astype(jnp.bfloat16)}
    if codec == "int8":
        N, D = x.shape
        if D % _CODEC_BLOCK:
            raise ValueError(f"D={D} not divisible by {_CODEC_BLOCK}")
        xb = x.reshape(N, D // _CODEC_BLOCK, _CODEC_BLOCK)
        scale = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / 127.0
        safe = jnp.where(scale == 0, 1.0, scale)
        q = jnp.clip(jnp.round(xb / safe), -127, 127).astype(jnp.int8)
        return {"q": q, "scale": scale.astype(jnp.float32)}
    raise ValueError(f"unknown codec {codec!r}")


@functools.partial(jax.jit,
                   static_argnames=("codec", "block", "interpret"))
def coded_tree_reduce(wire, codec: str, *, block: int = 512,
                      interpret: bool | None = None) -> jax.Array:
    """H-tree sum of N wire-encoded rows → [D] f32, dequant fused into the
    reduction launch.  ``wire`` is ``encode_rows`` output; bf16 rows feed
    the plain kernel's f32 accumulator directly, int8 rows dequant in VMEM.
    The pairwise H-tree order is preserved (deterministic in N); int8 may
    differ from decode-then-``tree_reduce`` by an ulp where the dequant
    multiply fuses into the first add.
    """
    interpret = (not on_tpu()) if interpret is None else interpret
    if codec == "int8":
        q, scale = wire["q"], wire["scale"]
        N = q.shape[0]
        n2 = 1 << max(1, (N - 1).bit_length())
        qp = jnp.pad(q, ((0, n2 - N), (0, 0), (0, 0)))
        sp = jnp.pad(scale, ((0, n2 - N), (0, 0), (0, 0)))
        return int8_tree_reduce_pallas(qp, sp, out_dtype=jnp.float32,
                                       interpret=interpret)
    x = wire["x"]
    N, D = x.shape
    n2 = 1 << max(1, (N - 1).bit_length())
    block = min(block, 1 << (D - 1).bit_length() if D else block)
    pd = (-D) % block
    xp = jnp.pad(x, ((0, n2 - N), (0, pd)))
    out = tree_reduce_pallas(xp, block=block, interpret=interpret,
                             out_dtype=jnp.float32)
    return out[:D]


def decode_add(keep: jax.Array, wire, codec, *,
               interpret: bool | None = None) -> jax.Array:
    """``keep + codec.decode(wire)`` as ONE launch — the fused
    receive+accumulate of a fractal halving exchange.

    ``codec`` is an ``optim.compression.Codec`` instance (its ``name``
    selects the kernel).  On TPU the kernel always runs: bf16 payloads of
    any length are padded to the kernel block, int8 payloads must be flat
    (q [M/128, 128]).  Off-TPU with ``interpret=None`` this is EXACTLY
    ``keep + codec.decode(wire)`` — bit-stable for the collective identity
    tests; ``interpret=True`` runs the kernel for parity tests.
    """
    if interpret is None:
        if not on_tpu():
            return keep + codec.decode(wire, keep.shape, keep.dtype)
        interpret = False
    if codec.name == "bf16":
        flat, x = keep.reshape(-1), wire["x"].reshape(-1)
        M = flat.shape[0]
        block = min(512, 1 << max(1, (M - 1).bit_length()))
        pm = (-M) % block
        out = decode_add_bf16_pallas(jnp.pad(flat, (0, pm)),
                                     jnp.pad(x, (0, pm)), block=block,
                                     interpret=interpret)
        return out[:M].reshape(keep.shape)
    if codec.name == "int8":
        q = wire["q"]
        if keep.ndim != 1 or q.ndim != 2 or q.size != keep.shape[0]:
            raise ValueError(
                f"fused int8 decode_add needs a flat payload: keep "
                f"{keep.shape}, q {q.shape}")
        return decode_add_int8_pallas(keep, q, wire["scale"].reshape(-1, 1),
                                      interpret=interpret)
    raise ValueError(f"no fused decode_add for codec {codec.name!r}")


__all__ = ["tree_reduce", "tree_reduce_ref", "encode_rows",
           "coded_tree_reduce", "decode_add"]
