"""Fused paged-attention decode Pallas kernels: block-table-driven K/V
streaming with online softmax.

The serve engine's decode hot loop previously paid a full HBM round trip
per step: ``paged_gather`` materialized the virtual contiguous KV view
[B, n*bs, ...] from the pool before every attention call.  These kernels
walk the block table directly instead: the table rides in as a
scalar-prefetch operand and the *physical* blocks (pages) are read from
the pool as it lies.  The gathered view is never materialized.

Two variants, both single-query (T == 1 decode), both with grid (B,),
one step per batch row, the pools read in place and only live pages
streamed, in double-buffered groups of ``pages_per_group`` pages:

* ``paged_attention_pallas``     — GQA.  The pools stay where XLA keeps
  them (``pl.ANY``), in the model's own layout [N, bs, Hkv, d]; the
  kernel copies whole pages (contiguous [bs, Hkv, d] slabs) into a
  double-buffered VMEM group of ``pages_per_group`` pages with
  ``make_async_copy`` and attends over
  the group while the next group's copies run.  The last group of a row
  prefetches the first group of the next live row, so a row's first
  copy also hides behind compute.  A group is [P*bs*Hkv, d] rows, token
  major and head minor; every query head scores the whole group in one
  matmul and a head-match mask keeps only its own kv head's rows, so no
  head is ever sliced or relaid out.
* ``paged_mla_attention_pallas`` — MLA absorbed decode over the latent
  pools [N, bs, r] (c_kv) and [N, bs, dr] (the shared rotary key, no
  head axis), copied page by page the same way; scores are latent-space
  (q_eff·c_kv + q_rope·k_rope), all heads against the whole group in one
  matmul, and the streamed c_kv group doubles as the value matrix.

Masking is by *virtual position only*: valid keys of row b are positions
``< lengths[b]`` (= cache offset + 1: the causal set of a query sitting
at the row's last position, including the token scattered this step).
Sentinel-padded table entries map to positions at/after ``lengths[b]``,
so the same mask hides them — exactly the invariant the gather path's
causal mask enforces.

Live pages: row b streams ``ceil(lengths[b] / bs)`` pages, or none
when ``tables[b, 0]`` is the sentinel block 0 — the engine never gives
an active row the sentinel block, and a masked row's table is all
sentinel (its length is a placeholder).  Pages, and whole groups, past
the live count are neither copied nor computed; a row with no live pages
outputs zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
SENTINEL = 0            # the pool's garbage block (models.layers.PAGED_SENTINEL)
# Cache bytes one group holds (K+V, or c_kv+k_rope): enough that a
# group's copies (~1.3 us at 819 GB/s) dwarf the fixed cost of a loop trip
# and of issuing them, while two groups stay a few MiB of VMEM.
_GROUP_BYTES = 1 << 20


def pages_per_group(page_bytes: int, n: int) -> int:
    """Pages in one group: ``_GROUP_BYTES`` of cache pages, at least one
    and at most the table width ``n``."""
    return max(1, min(n, _GROUP_BYTES // page_bytes))


def _paged_kernel(tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, bs: int, n: int, pages: int, window,
                  softcap):
    b = pl.program_id(0)
    num_rows = pl.num_programs(0)
    hkv = k_hbm.shape[2]
    rows = bs * hkv                    # one page: [bs, Hkv] positions
    hq = q_ref.shape[1]
    G = hq // hkv

    def live_pages(r):
        live = jnp.minimum(pl.cdiv(lengths_ref[r], bs), n)
        return jnp.where(tables_ref[r, 0] == SENTINEL, 0, live)

    def next_live(r):
        """The first row at or after ``r`` with live pages, or B."""
        return jax.lax.while_loop(
            lambda i: (i < num_rows) & (
                live_pages(jnp.minimum(i, num_rows - 1)) == 0),
            lambda i: i + 1, r)

    def copies(r, g, slot, start: bool):
        """Start (or wait for) the copies of row r's group g into buffer
        ``slot``: each of its live pages, K and V, whole."""
        def page(i, carry):
            blk = tables_ref[r, g * pages + i]
            dst = pl.ds(i * rows, rows)
            for hbm, buf, sem in ((k_hbm, k_buf, sems.at[0, slot]),
                                  (v_hbm, v_buf, sems.at[1, slot])):
                src = hbm.at[blk].reshape(rows, hbm.shape[-1])
                cp = pltpu.make_async_copy(src, buf.at[slot, dst], sem)
                cp.start() if start else cp.wait()
            return carry
        count = jnp.minimum(pages, live_pages(r) - g * pages)
        jax.lax.fori_loop(0, count, page, 0)

    @pl.when(b == 0)
    def _prologue():
        # masked positions get p = 0, but 0 * NaN is not 0: the buffers
        # start finite and only ever receive pool pages
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        first = next_live(0)

        @pl.when(first < num_rows)
        def _():
            copies(first, 0, 0, start=True)

    length = lengths_ref[b]
    groups = pl.cdiv(live_pages(b), pages)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0]                                          # [Hq, d]
    T = pages * rows
    # column c of a group is token c // Hkv of kv head c % Hkv; query row
    # h reads only its own kv head h // G
    col = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    own = (jax.lax.rem(col, hkv) ==
           jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (hq, 1), 0), G))

    def group(g, slot):
        other = 1 - slot

        @pl.when(g + 1 < groups)
        def _():
            copies(b, g + 1, other, start=True)

        @pl.when(g + 1 == groups)
        def _():
            nxt = next_live(b + 1)

            @pl.when(nxt < num_rows)
            def _():
                copies(nxt, 0, other, start=True)

        copies(b, g, slot, start=False)
        s = jax.lax.dot_general(q, k_buf[slot], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        pos = g * (pages * bs) + jax.lax.div(col, hkv)    # [1, T]
        mask = own & (pos < length)
        if window is not None:
            # query sits at virtual position length-1
            mask &= (length - 1 - pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v_buf.dtype), v_buf[slot],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return other

    slot_ref[0] = jax.lax.fori_loop(0, groups, group, slot_ref[0])
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pool, v_pool, tables, lengths, *,
                           scale: float, window=None, softcap=None,
                           interpret: bool = False):
    """q: [B, Hq, d] (Hq = Hkv * G, kv head major), pools: [N, bs, Hkv,
    d(v)], tables: [B, n] int32, lengths: [B] int32 → [B, Hq, dv].

    The pools are read where they lie: no reshape or copy of a pool-sized
    array happens outside the kernel."""
    B, hq, d = q.shape
    bs, hkv = k_pool.shape[1:3]
    dv = v_pool.shape[-1]
    n = tables.shape[1]
    pages = pages_per_group(bs * hkv * (d + dv) * k_pool.dtype.itemsize, n)
    kernel = functools.partial(_paged_kernel, scale=scale, bs=bs, n=n,
                               pages=pages, window=window, softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, hq, d), lambda b, tables, lengths: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hq, dv),
                               lambda b, tables, lengths: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages * bs * hkv, d), k_pool.dtype),
            pltpu.VMEM((2, pages * bs * hkv, dv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),         # [K/V, buffer]
            pltpu.SMEM((1,), jnp.int32),             # buffer of row b's group 0
            pltpu.VMEM((hq, 1), jnp.float32),        # m
            pltpu.VMEM((hq, 1), jnp.float32),        # l
            pltpu.VMEM((hq, dv), jnp.float32),       # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hq, dv), q.dtype),
        name="paged_decode_attention",
        # rows run in order: a row's last group prefetches the next's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables, lengths, q, k_pool, v_pool)


def _paged_mla_kernel(tables_ref, lengths_ref, qe_ref, qr_ref, ckv_hbm,
                      kr_hbm, o_ref, ckv_buf, kr_buf, sems, slot_ref, m_ref,
                      l_ref, acc_ref, *, scale: float, bs: int, n: int,
                      pages: int):
    b = pl.program_id(0)
    num_rows = pl.num_programs(0)

    def live_pages(r):
        live = jnp.minimum(pl.cdiv(lengths_ref[r], bs), n)
        return jnp.where(tables_ref[r, 0] == SENTINEL, 0, live)

    def next_live(r):
        """The first row at or after ``r`` with live pages, or B."""
        return jax.lax.while_loop(
            lambda i: (i < num_rows) & (
                live_pages(jnp.minimum(i, num_rows - 1)) == 0),
            lambda i: i + 1, r)

    def copies(r, g, slot, start: bool):
        """Start (or wait for) the copies of row r's group g into buffer
        ``slot``: each of its live pages, c_kv and k_rope, whole."""
        def page(i, carry):
            blk = tables_ref[r, g * pages + i]
            dst = pl.ds(i * bs, bs)
            for hbm, buf, sem in ((ckv_hbm, ckv_buf, sems.at[0, slot]),
                                  (kr_hbm, kr_buf, sems.at[1, slot])):
                cp = pltpu.make_async_copy(hbm.at[blk], buf.at[slot, dst],
                                           sem)
                cp.start() if start else cp.wait()
            return carry
        count = jnp.minimum(pages, live_pages(r) - g * pages)
        jax.lax.fori_loop(0, count, page, 0)

    @pl.when(b == 0)
    def _prologue():
        # masked positions get p = 0, but 0 * NaN is not 0: the buffers
        # start finite and only ever receive pool pages
        ckv_buf[...] = jnp.zeros_like(ckv_buf)
        kr_buf[...] = jnp.zeros_like(kr_buf)
        slot_ref[0] = 0
        first = next_live(0)

        @pl.when(first < num_rows)
        def _():
            copies(first, 0, 0, start=True)

    length = lengths_ref[b]
    groups = pl.cdiv(live_pages(b), pages)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    qe = qe_ref[0]                                        # [H, r]
    qr = qr_ref[0]                                        # [H, dr]
    T = pages * bs
    col = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    nt = (((1,), (1,)), ((), ()))                         # a @ b^T

    def group(g, slot):
        other = 1 - slot

        @pl.when(g + 1 < groups)
        def _():
            copies(b, g + 1, other, start=True)

        @pl.when(g + 1 == groups)
        def _():
            nxt = next_live(b + 1)

            @pl.when(nxt < num_rows)
            def _():
                copies(nxt, 0, other, start=True)

        copies(b, g, slot, start=False)
        ckv = ckv_buf[slot]                               # [T, r]
        s = jax.lax.dot_general(qe, ckv, nt,
                                preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qr, kr_buf[slot], nt,
                                    preferred_element_type=jnp.float32)
        s = s * scale                                     # [H, T]
        s = jnp.where(g * T + col < length, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(ckv.dtype), ckv, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return other

    slot_ref[0] = jax.lax.fori_loop(0, groups, group, slot_ref[0])
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_mla_attention_pallas(q_eff, q_rope, ckv_pool, kr_pool, tables,
                               lengths, *, scale: float,
                               interpret: bool = False):
    """q_eff: [B, H, r], q_rope: [B, H, dr], ckv_pool: [N, bs, r],
    kr_pool: [N, bs, dr], tables: [B, n], lengths: [B] → latent attention
    output [B, H, r] (the c_kv page is both key component and value).

    The pools are read where they lie: no reshape or copy of a pool-sized
    array happens outside the kernel."""
    B, H, r = q_eff.shape
    dr = q_rope.shape[-1]
    bs = ckv_pool.shape[1]
    n = tables.shape[1]
    pages = pages_per_group(bs * (r + dr) * ckv_pool.dtype.itemsize, n)
    kernel = functools.partial(_paged_mla_kernel, scale=scale, bs=bs, n=n,
                               pages=pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, r), lambda b, tables, lengths: (b, 0, 0)),
            pl.BlockSpec((1, H, dr), lambda b, tables, lengths: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, r),
                               lambda b, tables, lengths: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages * bs, r), ckv_pool.dtype),
            pltpu.VMEM((2, pages * bs, dr), kr_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),         # [c_kv/k_rope, buffer]
            pltpu.SMEM((1,), jnp.int32),             # buffer of row b's group 0
            pltpu.VMEM((H, 1), jnp.float32),         # m
            pltpu.VMEM((H, 1), jnp.float32),         # l
            pltpu.VMEM((H, r), jnp.float32),         # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), q_eff.dtype),
        name="paged_mla_decode_attention",
        # rows run in order: a row's last group prefetches the next's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables, lengths, q_eff, q_rope, ckv_pool, kr_pool)
