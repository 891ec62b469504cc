"""Flash-attention (fwd) Pallas kernel: online softmax in VMEM.

The dominant hot-spot of every assigned transformer at prefill shapes.  TPU
re-think of the classic GPU kernel (DESIGN.md §2): instead of warp-level
softmax reductions, blocks are MXU-aligned VMEM tiles; the (m, l, acc)
running statistics live in VMEM scratch across the KV grid steps (innermost,
"arbitrary"); causal masking is positional via block-offset iota, and
fully-masked KV blocks are skipped by the grid index map (the causal ~2×).

Supports GQA (q heads grouped over kv heads), causal masking, sliding
window, and logit softcap (Gemma-2).  Backward uses the pure-jnp oracle
via jax.custom_vjp recompute (kernels/flash_attention/ops.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, kv_steps: int,
                  causal: bool, window, softcap):
    qi = pl.program_id(1)          # query block
    ki = pl.program_id(2)          # kv block (innermost)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    run = jnp.bool_(True)
    if causal:
        # skip kv blocks entirely above the causal diagonal
        run &= (ki * block_k) <= (qi * block_q + block_q - 1)
    if window is not None:
        # skip kv blocks entirely left of the sliding window
        run &= ((ki + 1) * block_k - 1) > (qi * block_q - window)

    @pl.when(run)
    def _step():
        q = q_ref[0]                                  # [block_q, d]
        k = k_ref[0]                                  # [block_k, d]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window=None,
                           softcap=None, scale=None, block_q: int = 128,
                           block_k: int = 128, interpret: bool = False):
    """q: [BH, Tq, D], k/v: [BH, Tk, D] → [BH, Tq, D].

    Batch and (grouped) heads must be pre-flattened into BH (ops.py does
    GQA grouping + padding).  Tq % block_q == Tk % block_k == 0.
    """
    BH, Tq, D = q.shape
    _, Tk, Dv = v.shape
    if Tq % block_q or Tk % block_k:
        raise ValueError(f"{(Tq, Tk)} not divisible by {(block_q, block_k)}")
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    kv_steps = Tk // block_k
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        kv_steps=kv_steps, causal=causal, window=window, softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid=(BH, Tq // block_q, kv_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, Dv), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
