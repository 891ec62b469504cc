"""Fused paged-attention decode Pallas kernels: block-table-driven K/V
streaming with online softmax.

The serve engine's decode hot loop previously paid a full HBM round trip
per step: ``paged_gather`` materialized the virtual contiguous KV view
[B, n*bs, ...] from the pool before every attention call.  These kernels
walk the block table directly instead — the table rides the grid as a
scalar-prefetch operand, so each KV grid step's BlockSpec index map reads
``tables[b, j]`` and streams the *physical* block [bs, ...] straight from
the pool into VMEM.  The gathered view is never materialized; the
scattered layout is free (the hardware-offload lesson of the paper's
barrier design applied to data movement).

Two variants, both single-query (T == 1 decode):

* ``paged_attention_pallas``     — GQA: grid (B, n), per-batch-row
  program streams the row's blocks, all kv heads per block, and reduces
  each head's G grouped query heads at once.
* ``paged_mla_attention_pallas`` — MLA absorbed decode: grid (B, n);
  scores are latent-space (q_eff·c_kv + q_rope·k_rope) and the streamed
  c_kv block doubles as the value matrix.

Masking is by *virtual position only*: valid keys of row b are positions
``< lengths[b]`` (= cache offset + 1: the causal set of a query sitting
at the row's last position, including the token scattered this step).
Sentinel-padded table entries map to positions at/after ``lengths[b]``,
so the same mask hides them — exactly the invariant the gather path's
causal mask enforces.  Blocks entirely at/after the length are skipped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, bs: int, n: int,
                  hkv: int, d: int, dv: int, window, softcap):
    b = pl.program_id(0)
    j = pl.program_id(1)               # kv block step (innermost)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]

    @pl.when((j * bs) < length)
    def _step():
        for h in range(hkv):           # every kv head of the block, in turn
            q = q_ref[0, h]                               # [G, d]
            k = k_ref[0, :, h * d:(h + 1) * d]            # [bs, d]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            G = s.shape[0]
            pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (G, bs), 1)
            mask = pos < length
            if window is not None:
                # query sits at virtual position length-1
                mask &= (length - 1 - pos) < window
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p.astype(v_ref.dtype), v_ref[0, :, h * dv:(h + 1) * dv],
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == n - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pool, v_pool, tables, lengths, *,
                           scale: float, window=None, softcap=None,
                           interpret: bool = False):
    """q: [B, Hkv, G, d], pools: [N, bs, Hkv, d(v)], tables: [B, n] int32,
    lengths: [B] int32 → [B, Hkv, G, dv].  ops.py does the GQA reshape.

    The pools are viewed as [N, bs, Hkv*d] (a free reshape), so each grid
    step streams one whole physical block, every kv head at once, as a
    [bs, Hkv*d] tile whose head slices sit on 128-lane boundaries."""
    B, Hkv, G, d = q.shape
    N, bs = k_pool.shape[:2]
    dv = v_pool.shape[-1]
    n = tables.shape[1]
    kernel = functools.partial(_paged_kernel, scale=scale, bs=bs, n=n,
                               hkv=Hkv, d=d, dv=dv, window=window,
                               softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, d),
                         lambda b, j, tables, lengths: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, Hkv * d),
                         lambda b, j, tables, lengths: (tables[b, j], 0, 0)),
            pl.BlockSpec((1, bs, Hkv * dv),
                         lambda b, j, tables, lengths: (tables[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, dv),
                               lambda b, j, tables, lengths: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),    # m
            pltpu.VMEM((Hkv, G, 1), jnp.float32),    # l
            pltpu.VMEM((Hkv, G, dv), jnp.float32),   # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dv), q.dtype),
        name="paged_decode_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lengths, q, k_pool.reshape(N, bs, Hkv * d),
      v_pool.reshape(N, bs, Hkv * dv))


def _paged_mla_kernel(tables_ref, lengths_ref, qe_ref, qr_ref, ckv_ref,
                      kr_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                      bs: int, n: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]

    @pl.when((j * bs) < length)
    def _step():
        ckv = ckv_ref[0]                              # [bs, r]
        s = jnp.dot(qe_ref[0], ckv.T,
                    preferred_element_type=jnp.float32)
        s = s + jnp.dot(qr_ref[0], kr_ref[0].T,
                        preferred_element_type=jnp.float32)
        s = s * scale                                 # [H, bs]
        H = s.shape[0]
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (H, bs), 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(ckv.dtype), ckv, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_mla_attention_pallas(q_eff, q_rope, ckv_pool, kr_pool, tables,
                               lengths, *, scale: float,
                               interpret: bool = False):
    """q_eff: [B, H, r], q_rope: [B, H, dr], ckv_pool: [N, bs, r],
    kr_pool: [N, bs, dr], tables: [B, n], lengths: [B] → latent attention
    output [B, H, r] (the c_kv block is both key component and value)."""
    B, H, r = q_eff.shape
    dr = q_rope.shape[-1]
    N, bs = ckv_pool.shape[:2]
    n = tables.shape[1]
    kernel = functools.partial(_paged_mla_kernel, scale=scale, bs=bs, n=n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n),
        in_specs=[
            pl.BlockSpec((1, H, r),
                         lambda b, j, tables, lengths: (b, 0, 0)),
            pl.BlockSpec((1, H, dr),
                         lambda b, j, tables, lengths: (b, 0, 0)),
            pl.BlockSpec((1, bs, r),
                         lambda b, j, tables, lengths:
                         (tables[b, j], 0, 0)),
            pl.BlockSpec((1, bs, dr),
                         lambda b, j, tables, lengths:
                         (tables[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, r),
                               lambda b, j, tables, lengths: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),    # m
            pltpu.VMEM((H, 1), jnp.float32),    # l
            pltpu.VMEM((H, r), jnp.float32),    # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), q_eff.dtype),
        name="paged_mla_decode_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lengths, q_eff, q_rope, ckv_pool, kr_pool)
