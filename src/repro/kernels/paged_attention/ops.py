"""Public fused paged-attention decode ops: decode-query checks + dispatch.

Decode-only (T == 1), forward-only (no grads flow at serve time), so no
custom_vjp is needed.  The kernel runs natively on TPU and in interpret
mode elsewhere; the jnp gather-then-attend reference lives in ``ref.py``.

``models/layers.py`` routes its paged T==1 decode branch here when the
resolved ``paged_kernel`` knob says "pallas"; the ``paged_gather`` path
stays as the ref/oracle lowering ("ref").
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from repro.kernels import on_tpu

from .kernel import paged_attention_pallas, paged_mla_attention_pallas
from .ref import paged_attention_ref, paged_mla_attention_ref


def _lengths(offset, batch: int):
    """Per-row valid-key counts from the cache offset (scalar or [B]):
    a query at position ``offset`` attends positions [0, offset]."""
    off = jnp.asarray(offset, jnp.int32)
    if off.ndim == 0:
        off = jnp.broadcast_to(off, (batch,))
    return off + 1


def paged_attention(q, k_pool, v_pool, tables, offset, *, scale=None,
                    window=None, softcap=None,
                    interpret: bool | None = None):
    """Fused GQA decode over a paged KV pool.

    q: [B, 1, Hq, d] (single decode query per row), pools
    [N, bs, Hkv, d(v)], tables [B, n] int32, offset scalar or [B] (tokens
    already cached; the query sits at that position) → [B, 1, Hq, dv],
    never materializing the gathered [B, n*bs, ...] view.
    """
    B, T, Hq, d = q.shape
    if T != 1:
        raise ValueError(f"paged_attention is decode-only (T==1), got T={T}")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    lengths = _lengths(offset, B)
    interpret = (not on_tpu()) if interpret is None else interpret
    o = paged_attention_pallas(q[:, 0], k_pool, v_pool, tables, lengths,
                               scale=scale, window=window, softcap=softcap,
                               interpret=interpret)
    return o[:, None]


def paged_mla_attention(q_eff, q_rope, ckv_pool, kr_pool, tables, offset, *,
                        scale: float, interpret: bool | None = None):
    """Fused MLA absorbed decode over paged latent pools.

    q_eff: [B, 1, H, r] (q_nope·W_uk), q_rope: [B, 1, H, dr], ckv_pool
    [N, bs, r], kr_pool [N, bs, dk] (as cached: the rotary key, zero
    lanes past dr when dk > dr), tables [B, n], offset scalar or [B] →
    latent attention output [B, 1, H, r] (the caller applies W_uv
    outside — it is a weight, not cache, contraction).  The pools are
    read in place.
    """
    B, T, H, r = q_eff.shape
    if T != 1:
        raise ValueError(
            f"paged_mla_attention is decode-only (T==1), got T={T}")
    qr = q_rope[:, 0]
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, kr_pool.shape[-1] - qr.shape[-1])))
    lengths = _lengths(offset, B)
    interpret = (not on_tpu()) if interpret is None else interpret
    o = paged_mla_attention_pallas(q_eff[:, 0], qr, ckv_pool, kr_pool,
                                   tables, lengths, scale=scale,
                                   interpret=interpret)
    return o[:, None]


__all__ = ["paged_attention", "paged_mla_attention",
           "paged_attention_ref", "paged_mla_attention_ref"]
