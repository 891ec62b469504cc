"""Model building blocks shared by all ten assigned architectures.

Pure-functional JAX: parameters are nested dicts of arrays; every layer is
(init_fn, apply_fn).  Conventions:

  * activations bf16 (configurable), softmax/normalizers f32;
  * attention is GQA-grouped (no KV head replication in memory);
  * sequences ≥ ``CHUNKED_ATTN_THRESHOLD`` use a lax.scan online-softmax
    (flash-style) path so 32k/500k shapes never materialize T×S scores —
    this is also the pure-jnp oracle for the Pallas flash kernel;
  * MoE routes over every expert and computes the experts it holds (all,
    or one chip's expert-parallel share): sort-based capacity dispatch in
    training (GShard capacity semantics without the O(T·E·C·d) one-hot
    einsum), dropless in serving; experts shard over the "model" axis.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YaRNConfig
from repro.kernels.paged_attention.ops import (paged_attention,
                                               paged_mla_attention)
from . import act_sharding as ACT

CHUNKED_ATTN_THRESHOLD = 8_192   # inference: online-softmax over KV chunks
ATTN_CHUNK = 1_024
QUERY_CHUNK_THRESHOLD = 2_048    # training: checkpointed query blocks
QUERY_CHUNK = 512


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


def _init_dense(key, d_in, d_out, dtype, scale=None, bias=False):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------------------------------------------------------------------
# Norms / positional encodings
# ---------------------------------------------------------------------------


def init_rmsnorm(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(p, x, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, y: YaRNConfig) -> jnp.ndarray:
    """Rotary frequencies under YaRN: extrapolated (plain) for the dims that
    turn more than ``beta_fast`` times over the original context,
    interpolated (divided by ``factor``) for those that turn fewer than
    ``beta_slow`` times, a linear ramp between."""
    def turn_dim(rotations):
        return (head_dim * math.log(y.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(turn_dim(y.beta_fast)), 0)
    hi = min(math.ceil(turn_dim(y.beta_slow)), head_dim - 1)
    hi = hi + 0.001 if hi == lo else hi
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    plain = rope_freqs(head_dim, theta)
    return plain / y.factor * ramp + plain * (1.0 - ramp)


def mla_softmax_scale(m: MLAConfig) -> float:
    """1/sqrt(dn + dr), times YaRN's mscale(mscale_all_dim) squared."""
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    if m.yarn is not None and m.yarn.mscale_all_dim:
        scale *= _yarn_mscale(m.yarn.factor, m.yarn.mscale_all_dim) ** 2
    return scale


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               yarn: Optional[YaRNConfig] = None) -> jnp.ndarray:
    """x: [..., T, H, D]; positions: [..., T] (broadcastable)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta) if yarn is None else yarn_freqs(d, theta, yarn)
    ang = positions[..., None].astype(jnp.float32) * inv  # [..., T, D/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    if yarn is not None:
        amp = (_yarn_mscale(yarn.factor, yarn.mscale)
               / _yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * amp, sin * amp
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    half = d_model // 2
    freq = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32)
                   / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Attention core (GQA, masks, online-softmax chunking)
# ---------------------------------------------------------------------------


def _mask_bias(pos_q, pos_k, *, causal, window, prefix_len):
    """Additive f32 bias [..., Tq, Tk] built from position comparisons."""
    pq = pos_q[..., :, None]
    pk = pos_k[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(pq.shape, pk.shape), bool)
    if causal:
        allowed = pk <= pq
        if prefix_len is not None:
            allowed = allowed | (pk < prefix_len)
        ok &= allowed
    if window is not None:
        ok &= (pq - pk) < window
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def gqa_attention(q, k, v, *, pos_q, pos_k, causal=True, window=None,
                  prefix_len=None, attn_cap=None, scale=None,
                  chunk=None, chunk_q=None) -> jnp.ndarray:
    """q: [B,Tq,Hq,Dk]  k: [B,Tk,Hkv,Dk]  v: [B,Tk,Hkv,Dv] → [B,Tq,Hq,Dv].

    ``chunk``  : online-softmax over Tk blocks — memory-lean FORWARD
                 (inference prefill; scan-backward would save carries).
    ``chunk_q``: checkpointed query blocks — memory-lean fwd+bwd for
                 TRAINING: per-block scores recomputed in backward, scan
                 outputs (not carries) are the only per-block residuals.
    """
    B, Tq, Hq, Dk = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    Dv = v.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(Dk)
    qg = q.reshape(B, Tq, Hkv, G, Dk) * scale
    # normalize positions to [B, T] so mask bias is [B, Tq, Tk]
    if pos_q.ndim == 1:
        pos_q = jnp.broadcast_to(pos_q[None, :], (B, Tq))
    if pos_k.ndim == 1:
        pos_k = jnp.broadcast_to(pos_k[None, :], (B, k.shape[1]))

    if chunk_q is not None and Tq > chunk_q:
        # Blocked attention with STATIC per-block KV extents (Python-unrolled
        # query blocks): block j only reads keys [lo_j, hi_j) where hi_j
        # follows the causal diagonal and lo_j the sliding window — the HLO
        # contains only the needed flops (≈½ for causal, ≈W/T for windowed)
        # instead of masked-but-computed full T×S scores.  Blocks are
        # jax.checkpoint'ed when grads flow (training=True callers), so the
        # backward recomputes one block's scores at a time.
        # Assumes pos_q/pos_k are arange-aligned (train/prefill from 0).
        C = chunk_q
        nq = -(-Tq // C)
        pad = nq * C - Tq
        if pad:
            qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
            pos_q = jnp.pad(pos_q, ((0, 0), (0, pad)),
                            constant_values=-1)      # masked (pk<=pq fails)
        Tk = k.shape[1]

        def block(lo, hi, q_blk, pq_blk, k_full, v_full, pk_full):
            # slice INSIDE the checkpointed fn: residuals are the original
            # k/v buffers (saved once), not per-block slice copies
            k_j, v_j = k_full[:, lo:hi], v_full[:, lo:hi]
            pk_j = pk_full[:, lo:hi]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_j
                           ).astype(jnp.float32)
            s = softcap(s, attn_cap)
            s = s + _mask_bias(pq_blk, pk_j, causal=causal, window=window,
                               prefix_len=prefix_len)[:, None, None]
            s = jnp.where(s == -jnp.inf, -1e30, s)   # padded rows stay finite
            p = jax.nn.softmax(s, axis=-1).astype(v_j.dtype)
            return jnp.einsum("bhgqk,bkhd->bqhgd", p, v_j)

        blk = jax.checkpoint(block, static_argnums=(0, 1))
        pre_hi = (-(-prefix_len // C) * C) if prefix_len else 0
        outs = []
        for j in range(nq):
            hi = Tk if not causal else min(Tk, max((j + 1) * C, pre_hi))
            lo = 0 if window is None else max(0, (j * C - window) // C * C)
            outs.append(blk(lo, hi, qg[:, j * C:(j + 1) * C],
                            pos_q[:, j * C:(j + 1) * C], k, v, pos_k))
        o = jnp.concatenate(outs, axis=1).reshape(B, nq * C, Hq, Dv)
        return o[:, :Tq]

    if chunk is None or k.shape[1] <= chunk:
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
        s = softcap(s, attn_cap)
        s = s + _mask_bias(pos_q, pos_k, causal=causal, window=window,
                           prefix_len=prefix_len)[:, None, None]
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
        return o.reshape(B, Tq, Hq, Dv)

    # ---- online-softmax over key chunks (flash-style, pure jnp oracle) ----
    Tk = k.shape[1]
    n_chunks = -(-Tk // chunk)
    pad = n_chunks * chunk - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos_k = jnp.pad(pos_k, [(0, 0)] * (pos_k.ndim - 1) + [(0, pad)],
                        constant_values=jnp.iinfo(jnp.int32).max // 2)
    kc = k.reshape(B, n_chunks, chunk, Hkv, Dk)
    vc = v.reshape(B, n_chunks, chunk, Hkv, Dv)
    pkc = pos_k.reshape(*pos_k.shape[:-1], n_chunks, chunk)

    def body(carry, xs):
        m, l, acc = carry
        k_j, v_j, pk_j = xs                     # [B,chunk,Hkv,D], pk [B,chunk]
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_j).astype(jnp.float32)
        s = softcap(s, attn_cap)
        bias = _mask_bias(pos_q, pk_j, causal=causal, window=window,
                          prefix_len=prefix_len)          # [B,Tq,chunk]
        s = s + bias[:, None, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # fully-masked-so-far rows keep m_new == -inf; use a finite proxy so
        # exp() never sees (-inf) − (-inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        corr = jnp.exp(m - m_safe)              # m == -inf → 0
        p = jnp.exp(s - m_safe[..., None])      # s == -inf → 0
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(v.dtype), v_j).astype(jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, G, Tq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Tq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Tq, Dv), jnp.float32)
    xs = (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
          jnp.moveaxis(pkc, -2, 0))
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), xs)
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(o, -2, 1).reshape(B, Tq, Hq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged KV cache primitives (pool [num_blocks, block_size, ...] + block table)
# ---------------------------------------------------------------------------
#
# The serve engine's paged backend replaces the per-slot contiguous cache row
# [B, S, ...] with one pooled tensor [num_blocks, block_size, ...] per leaf;
# each slot maps virtual positions onto physical blocks through a fixed-width
# block table [B, n_max] (jit-stable: unallocated entries padded with the
# SENTINEL block 0, whose contents are garbage by construction and causally
# masked everywhere).  With max_len % block_size == 0 the gathered virtual
# view has the SAME shape and values as the contiguous row, so attention
# over it agrees with the contiguous path — the token-identity invariant
# the serve benchmarks assert end to end.

PAGED_SENTINEL = 0


def paged_gather(pool, tables):
    """pool [N, bs, ...] + tables [B, n] -> virtual view [B, n*bs, ...].

    Virtual position p of row b lives at pool[tables[b, p // bs], p % bs].
    Sentinel-padded table entries gather garbage at virtual positions past
    the row's allocated length — positions the causal mask always hides.
    """
    N, bs = pool.shape[:2]
    B, n = tables.shape
    g = jnp.take(pool, tables.reshape(-1), axis=0)        # [B*n, bs, ...]
    return g.reshape((B, n * bs) + pool.shape[2:])


def paged_scatter(pool, new, tables, offset, sentinel=PAGED_SENTINEL):
    """Write ``new`` [B,T,...] at virtual positions [offset, offset+T)
    through ``tables`` [B, n] into ``pool`` [N, bs, ...].

    ``offset`` is a scalar (chunked prefill; shared start) or a per-row
    [B] vector (slots at independent lengths) — ragged multi-token writes
    start each row's span at its own offset.  Positions beyond the
    table's span — end-padding of a short final prefill chunk, or the
    tail of a ragged row — and positions whose table entry is the
    SENTINEL are written to block ``sentinel`` instead of clamping onto a
    live block.  Masked decode rows carry an all-sentinel table row, so
    their writes land in the sentinel block too.
    """
    N, bs = pool.shape[:2]
    B, T = new.shape[:2]
    n = tables.shape[1]
    off = jnp.asarray(offset)
    if off.ndim == 0:
        pos = off.astype(jnp.int32) + jnp.arange(T, dtype=jnp.int32)
        pos = jnp.broadcast_to(pos[None, :], (B, T))
    else:
        pos = off.astype(jnp.int32)[:, None] \
            + jnp.arange(T, dtype=jnp.int32)[None, :]
    bi = pos // bs
    blk = jnp.take_along_axis(tables, jnp.clip(bi, 0, n - 1), axis=1)
    blk = jnp.where((bi < n) & (blk != PAGED_SENTINEL), blk, sentinel)
    flat = new.reshape((B * T,) + new.shape[2:]).astype(pool.dtype)
    return pool.at[blk.reshape(-1), (pos % bs).reshape(-1)].set(flat)


def _cache_update(buf, new, offset):
    """Write ``new`` [B,T,...] into cache ``buf`` [B,S,...] at ``offset``.

    ``offset`` is a scalar (shared write position) or, for T == 1 decode, a
    per-row [B] vector — the serve engine's slots sit at independent
    sequence lengths inside one batched decode step.

    * T == S (prefill filling the whole cache): replace outright;
    * T == 1 (decode): one-hot select over S — shard-local under an
      S-over-"model" layout, unlike dynamic-update-slice whose GSPMD
      lowering materializes [S_local × S] masks;
    * general T: dynamic_update_slice (chunked prefill; scalar offset only).
    """
    S = buf.shape[1]
    T = new.shape[1]
    if T == S:
        return new.astype(buf.dtype)
    off = jnp.asarray(offset)
    if T == 1:
        if off.ndim == 1:      # per-slot write positions
            hit = jnp.arange(S, dtype=jnp.int32)[None, :] == off[:, None]
            hit = hit.reshape((off.shape[0], S) + (1,) * (buf.ndim - 2))
        else:
            hit = (jnp.arange(S, dtype=jnp.int32) == off)
            hit = hit.reshape((1, S) + (1,) * (buf.ndim - 2))
        return jnp.where(hit, new.astype(buf.dtype), buf)
    if off.ndim != 0:
        raise ValueError("multi-token cache writes need a scalar offset")
    return lax.dynamic_update_slice_in_dim(buf, new.astype(buf.dtype),
                                           offset, axis=1)


def _kv_write(cache, new, offset, layer, tables=None):
    """Write one step's K/V rows into layer ``layer`` of a segment's
    stacked cache in place; nothing outside the written rows moves.

    ``cache`` {leaf: [reps, ...]} holds every repeat of the layer, ``new``
    {leaf: [B, T, ...]} the step's rows.  Paged (``tables`` [B, n]): the
    stack is addressed as one flat pool a leaf, [reps*N, bs, ...] (merging
    the two major dims moves no data), through the tables offset to the
    layer's N blocks.  Sentinel entries stay ``PAGED_SENTINEL``, so the
    decode kernels still skip masked rows, while the writes they redirect
    land on the layer's own block 0.  Contiguous: the layer's rows
    [B, S, ...] take the write (``_cache_update``) and go back by an
    indexed update along the stack.

    Returns (the stacked cache, what attention reads — the flat pools, or
    the layer's rows — and the layer's tables into the flat pools)."""
    if tables is None:
        rows = {name: _cache_update(
            lax.dynamic_index_in_dim(x, layer, 0, keepdims=False),
            new[name], offset) for name, x in cache.items()}
        return ({name: lax.dynamic_update_index_in_dim(x, rows[name], layer,
                                                       0)
                 for name, x in cache.items()}, rows, None)
    N = next(iter(cache.values())).shape[1]
    base = layer * N
    tables = jnp.where(tables == PAGED_SENTINEL, PAGED_SENTINEL,
                       tables + base)
    pools = {name: paged_scatter(x.reshape((-1,) + x.shape[2:]), new[name],
                                 tables, offset, sentinel=base)
             for name, x in cache.items()}
    return ({name: pools[name].reshape(x.shape)
             for name, x in cache.items()}, pools, tables)


# ---------------------------------------------------------------------------
# GQA attention layer (projections + rope + cache)
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ArchConfig):
    dt = _dtype(cfg)
    D, H, Hkv, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init_dense(ks[0], D, H * Dh, dt, bias=cfg.qkv_bias),
        "wk": _init_dense(ks[1], D, Hkv * Dh, dt, bias=cfg.qkv_bias),
        "wv": _init_dense(ks[2], D, Hkv * Dh, dt, bias=cfg.qkv_bias),
        "wo": _init_dense(ks[3], H * Dh, D, dt,
                          scale=1.0 / math.sqrt(H * Dh)),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(Dh, dt)
        p["k_norm"] = init_rmsnorm(Dh, dt)
    return p


def apply_attention(p, cfg: ArchConfig, x, *, positions, kv_cache=None,
                    cache_offset=None, window=None, prefix_len=None,
                    block_tables=None, paged_kernel="ref", layer=None):
    """x: [B,T,D]. Returns (out [B,T,D], new_kv or None).

    kv_cache: the segment's stacked cache dict(k=[reps,B,S,Hkv,Dh], v=...),
    of which this layer is repeat ``layer`` (traced); updated in place
    (``_kv_write``).  cache_offset: scalar current length (tokens already
    in cache).  block_tables: paged mode — kv_cache leaves are pools
    [reps, N, bs, Hkv, Dh] and [B, n] tables map virtual positions onto
    physical blocks.  paged_kernel: "pallas" routes paged T==1 decode
    through the fused block-table kernel (no gathered [B, n*bs, ...]
    view); "ref" keeps the gather-then-attend oracle lowering."""
    B, T, D = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense(p["wq"], x).reshape(B, T, H, Dh)
    k = dense(p["wk"], x).reshape(B, T, Hkv, Dh)
    v = dense(p["wv"], x).reshape(B, T, Hkv, Dh)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    if cfg.pos_embed == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if kv_cache is None:
        pos_k = positions
        chunk_q = QUERY_CHUNK if T >= QUERY_CHUNK_THRESHOLD else None
        o = gqa_attention(q, k, v, pos_q=positions, pos_k=pos_k,
                          causal=True, window=window, prefix_len=prefix_len,
                          attn_cap=cfg.attn_softcap, chunk_q=chunk_q)
        new_kv = {"k": k, "v": v}
    else:
        new_kv, kv, tables = _kv_write(kv_cache, {"k": k, "v": v},
                                       cache_offset, layer, block_tables)
        if tables is not None:
            if T == 1 and paged_kernel == "pallas" and prefix_len is None:
                # fused decode: the kernel walks the tables directly and
                # streams pool blocks; the [B, n*bs, ...] gather never exists
                o = paged_attention(q, kv["k"], kv["v"], tables,
                                    cache_offset, window=window,
                                    softcap=cfg.attn_softcap)
                out = dense(p["wo"], o.reshape(B, T, H * Dh))
                return out, new_kv
            kv = {name: paged_gather(pool, tables)
                  for name, pool in kv.items()}
        k_all, v_all = kv["k"], kv["v"]
        S = k_all.shape[1]
        pos_k = jnp.arange(S, dtype=jnp.int32)[None, :]
        pos_q = positions if positions.ndim > 1 else positions[None, :]
        # prefill (T>1): blocked attention with static causal extents;
        # single-query decode never blocks: scores are [B,H,1,S] (tiny) and
        # blocking would fight the model-axis sharding of S.
        chunk_q = QUERY_CHUNK * 2 if (T >= QUERY_CHUNK_THRESHOLD) else None
        o = gqa_attention(q, k_all, v_all, pos_q=pos_q, pos_k=pos_k,
                          causal=True, window=window, prefix_len=prefix_len,
                          attn_cap=cfg.attn_softcap, chunk_q=chunk_q)
    out = dense(p["wo"], o.reshape(B, T, H * Dh))
    return out, new_kv


# ---------------------------------------------------------------------------
# MLA: multi-head latent attention (DeepSeek-V3)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ArchConfig):
    m: MLAConfig = cfg.mla
    dt = _dtype(cfg)
    D, H = cfg.d_model, cfg.num_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "q_down": _init_dense(ks[0], D, m.q_lora_rank, dt),
        "q_norm": init_rmsnorm(m.q_lora_rank, dt),
        "q_up": _init_dense(ks[1], m.q_lora_rank, H * qk_dim, dt),
        "kv_down": _init_dense(ks[2], D, m.kv_lora_rank + m.qk_rope_head_dim, dt),
        "kv_norm": init_rmsnorm(m.kv_lora_rank, dt),
        "kv_up": _init_dense(ks[3], m.kv_lora_rank,
                             H * (m.qk_nope_head_dim + m.v_head_dim), dt),
        "wo": _init_dense(ks[4], H * m.v_head_dim, D, dt),
    }


def rope_lanes(dr: int) -> int:
    """Width at which the MLA caches keep the shared rotary key: ``dr``
    zero-padded to whole 128-lane tiles.  A TPU keeps a 64-wide minor dim
    in a transposed layout (or pads it to 128 lanes anyway), and the
    decode kernel's page copies need whole lane tiles."""
    return -(-dr // 128) * 128


def _pad_lanes(x, width: int):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def apply_mla(p, cfg: ArchConfig, x, *, positions, kv_cache=None,
              cache_offset=None, block_tables=None, paged_kernel="ref",
              layer=None):
    """Latent-cache MLA. Cache stores c_kv [B,S,kv_lora] and the shared
    rotary key k_rope [B,S,rope_lanes(dr)] (no head axis, zero lanes past
    dr), stacked over the segment's repeats with this layer at ``layer``
    (as in ``apply_attention``); paged mode pools them as [N, bs, ...]
    addressed via block_tables.  paged_kernel="pallas" fuses paged T==1
    absorbed decode (no gather, the pools read in place)."""
    m: MLAConfig = cfg.mla
    B, T, D = x.shape
    H = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    q = dense(p["q_up"], rms_norm(p["q_norm"], dense(p["q_down"], x),
                                  cfg.norm_eps)).reshape(B, T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, m.yarn)

    kv = dense(p["kv_down"], x)
    c_kv = rms_norm(p["kv_norm"], kv[..., :m.kv_lora_rank], cfg.norm_eps)
    k_rope = apply_rope(kv[..., None, m.kv_lora_rank:], positions,
                        cfg.rope_theta, m.yarn)[:, :, 0]      # [B,T,dr]

    if kv_cache is not None:
        kr_new = _pad_lanes(k_rope, kv_cache["k_rope"].shape[-1])
        new_cache, kv, tables = _kv_write(
            kv_cache, {"c_kv": c_kv, "k_rope": kr_new}, cache_offset, layer,
            block_tables)
        if tables is not None:
            if T == 1 and paged_kernel == "pallas":
                # fused absorbed decode in latent space, straight off the
                # pools (the weight absorption of _mla_absorbed_decode with
                # the gather + [B,S] latent view fused away; scores_sshard
                # is a sharding hint only, skipped inside the kernel path)
                w_up = p["kv_up"]["w"].reshape(m.kv_lora_rank, H, dn + dv)
                w_k, w_v = w_up[..., :dn], w_up[..., dn:]
                q_eff = jnp.einsum("bthd,rhd->bthr", q_nope, w_k)
                o_lat = paged_mla_attention(
                    q_eff, q_rope, kv["c_kv"], kv["k_rope"], tables,
                    cache_offset, scale=mla_softmax_scale(m))
                o = jnp.einsum("bthr,rhd->bthd", o_lat, w_v)
                out = dense(p["wo"], o.reshape(B, T, H * dv))
                return out, new_cache
            kv = {name: paged_gather(pool, tables)
                  for name, pool in kv.items()}
        c_kv, k_rope = kv["c_kv"], kv["k_rope"][..., :dr]
        S = c_kv.shape[1]
        pos_k = jnp.arange(S, dtype=jnp.int32)[None, :]
        pos_q = positions if positions.ndim > 1 else positions[None, :]
    else:
        S = T
        pos_k = positions
        pos_q = positions
        new_cache = {"c_kv": c_kv, "k_rope": k_rope}

    # decode uses the ABSORBED-WEIGHT form (DeepSeek inference trick): score
    # and output projections fold W_uk/W_uv into q/o so K/V are NEVER
    # materialized from the latent — attention runs in the 512-d latent
    # space directly against the S-sharded cache.
    if kv_cache is not None and T == 1:
        o = _mla_absorbed_decode(p, cfg, q_nope, q_rope, c_kv, k_rope,
                                 cache_offset)
        out = dense(p["wo"], o.reshape(B, T, H * dv))
        return out, new_cache

    up = dense(p["kv_up"], c_kv).reshape(B, S, H, dn + dv)
    k_nope, v = up[..., :dn], up[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None], (B, S, H, dr))],
        axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    if kv_cache is None:        # training
        chunk_q = QUERY_CHUNK if T >= QUERY_CHUNK_THRESHOLD else None
    elif T > 1:                 # prefill
        chunk_q = QUERY_CHUNK * 2 if T >= QUERY_CHUNK_THRESHOLD else None
    else:                       # decode
        chunk_q = None
    o = gqa_attention(qf, k, v, pos_q=pos_q, pos_k=pos_k, causal=True,
                      attn_cap=None, scale=mla_softmax_scale(m),
                      chunk_q=chunk_q)
    out = dense(p["wo"], o.reshape(B, T, H * dv))
    return out, new_cache


def _mla_absorbed_decode(p, cfg, q_nope, q_rope, c_kv, k_rope, offset):
    """One-token MLA attention in latent space (weight absorption).

      scores = (q_nope·W_uk)·c_kv + q_rope·k_rope     [B,H,1,S]
      out    = (softmax·c_kv)·W_uv                    [B,1,H,dv]

    c_kv stays S-sharded over "model" end to end; the per-layer wire cost is
    the (small) absorbed weights + softmax partials instead of all-gathering
    a [B,S,H,192] materialized K (the 204 GiB/dev baseline pathology).
    """
    m = cfg.mla
    B, T, H, dn = q_nope.shape
    S = c_kv.shape[1]
    dv = m.v_head_dim
    w_up = p["kv_up"]["w"].reshape(m.kv_lora_rank, H, dn + dv)
    w_k, w_v = w_up[..., :dn], w_up[..., dn:]

    q_eff = jnp.einsum("bthd,rhd->bthr", q_nope, w_k)       # [B,1,H,r]
    s = jnp.einsum("bthr,bsr->bhts", q_eff, c_kv).astype(jnp.float32)
    s = s + jnp.einsum("bthd,bsd->bhts", q_rope,
                       k_rope).astype(jnp.float32)
    s = s * mla_softmax_scale(m)
    s = ACT.scores_sshard(s)
    off = jnp.asarray(offset).reshape((-1, 1, 1, 1))   # scalar or per-slot [B]
    valid = jnp.arange(S, dtype=jnp.int32)[None, None, None, :] <= off
    s = jnp.where(valid, s, -1e30)
    prob = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhts,bsr->bthr", prob.astype(c_kv.dtype), c_kv)
    return jnp.einsum("bthr,rhd->bthd", o_lat, w_v)         # [B,1,H,dv]


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ArchConfig, d_ff=None):
    """mlp styles: swiglu/geglu (gated, 3 matrices) or gelu (plain, 2)."""
    dt = _dtype(cfg)
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w_up": _init_dense(ks[1], D, F, dt),
        "w_down": _init_dense(ks[2], F, D, dt, scale=1.0 / math.sqrt(F)),
    }
    if cfg.mlp != "gelu":
        p["w_gate"] = _init_dense(ks[0], D, F, dt)
    return p


def apply_mlp(p, cfg: ArchConfig, x):
    if cfg.mlp == "gelu":
        return dense(p["w_down"],
                     jax.nn.gelu(dense(p["w_up"], x), approximate=True))
    act = jax.nn.silu if cfg.mlp == "swiglu" else \
        (lambda z: jax.nn.gelu(z, approximate=True))
    return dense(p["w_down"], act(dense(p["w_gate"], x)) * dense(p["w_up"], x))


# ---------------------------------------------------------------------------
# Mixture of Experts (sort-based capacity dispatch in training, dropless
# held experts in serving; EP over "model")
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ArchConfig):
    """Router over all ``num_experts`` (with the noaux_tc router's
    selection-only correction bias as ``router/b``), the held experts'
    stacked ``w_gate/w``, ``w_up/w`` [E_held, D, F] and ``w_down/w``
    [E_held, F, D], and the shared expert."""
    mo: MoEConfig = cfg.moe
    dt = _dtype(cfg)
    D, E, F = cfg.d_model, mo.held, mo.d_expert
    ks = jax.random.split(key, 5)
    scale_in = 1.0 / math.sqrt(D)
    scale_out = 1.0 / math.sqrt(F)
    p = {
        "router": _init_dense(ks[0], D, mo.num_experts, jnp.float32,
                              bias=mo.router == "noaux_tc"),
        "w_gate": {"w": (jax.random.normal(ks[1], (E, D, F), jnp.float32)
                         * scale_in).astype(dt)},
        "w_up": {"w": (jax.random.normal(ks[2], (E, D, F), jnp.float32)
                       * scale_in).astype(dt)},
        "w_down": {"w": (jax.random.normal(ks[3], (E, F, D), jnp.float32)
                         * scale_out).astype(dt)},
    }
    if mo.num_shared:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=F * mo.num_shared)
    return p


def _noaux_tc_select(scores, bias, mo: MoEConfig):
    """DeepSeek-V3's group-limited choice: the bias-corrected scores pick
    the experts, the group score is the sum of a group's best two, and
    only the best ``topk_group`` groups are searched."""
    T, E = scores.shape
    G = mo.n_group
    biased = scores + bias.astype(jnp.float32)
    grp = lax.top_k(biased.reshape(T, G, E // G), 2)[0].sum(-1)    # [T,G]
    _, keep = lax.top_k(grp, mo.topk_group)
    allowed = jnp.zeros((T, G), bool).at[
        jnp.arange(T)[:, None], keep].set(True)
    allowed = jnp.repeat(allowed, E // G, axis=1)                  # [T,E]
    _, idx = lax.top_k(jnp.where(allowed, biased, -jnp.inf), mo.top_k)
    return idx


def _router_gates(p, mo: MoEConfig, x2d):
    """Top-k routing over all experts: gates [T,k] (the unbiased scores of
    the chosen experts, normalized and scaled), expert ids [T,k], and the
    scores [T,E] for the balance loss."""
    # float32 at full precision, as published (a TPU's default would
    # round both to bfloat16 and reorder near-tied experts)
    logits = jnp.matmul(x2d.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if mo.router == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        gates, idx = lax.top_k(scores, mo.top_k)     # [T,k]
    else:                                            # noaux_tc
        scores = jax.nn.sigmoid(logits)
        idx = _noaux_tc_select(scores, p["router"]["b"], mo)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
    if mo.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates * mo.routed_scaling, idx, scores


def moe_load_balance_loss(scores, idx, num_experts):
    """Switch-style load-balance aux loss (mean prob × token fraction)."""
    T = scores.shape[0]
    frac_prob = scores.mean(0)
    counts = jnp.zeros((num_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    frac_tok = counts / jnp.maximum(counts.sum(), 1.0)
    return num_experts * jnp.sum(frac_prob * frac_tok)


def _expert_act(cfg: ArchConfig):
    return jax.nn.silu if cfg.mlp == "swiglu" else \
        (lambda z: jax.nn.gelu(z, approximate=True))


def _held_dropless(p, cfg: ArchConfig, x2d, held_gates):
    """Every held expert over every token, weighted by its gate [T,Eh]
    (zero where the token was not routed to it): nothing can drop."""
    act = _expert_act(cfg)
    h = act(jnp.einsum("td,edf->etf", x2d, p["w_gate"]["w"])) \
        * jnp.einsum("td,edf->etf", x2d, p["w_up"]["w"])
    y = jnp.einsum("etf,efd->etd", h, p["w_down"]["w"])
    return jnp.einsum("te,etd->td", held_gates.astype(y.dtype), y)


def apply_moe(p, cfg: ArchConfig, x, *, dropless: bool = False,
              valid=None):
    """x: [B,T,D] → (y, aux_loss, routed).

    The router runs over all ``num_experts``; the layer computes the part
    of the result that its held experts [held_from, held_from + held)
    give, plus the shared expert.  ``routed`` [E_held] int32 counts the
    tokens routed to each held expert (only where ``valid`` [B,T], if
    given, is set).

    ``dropless`` (serving: decode steps and prefill chunks) runs every
    held expert over all the tokens (``_held_dropless``).  Otherwise
    (training) a group-wise sort-based capacity dispatch: tokens are
    grouped by sequence (group = batch row), GShard-style, so the
    dispatch buffer is [B, E_held, C, D] with LOCAL capacity
    C = ceil(T·k/E·cf): the batch dim stays sharded over the data axes
    and experts shard over "model" (EP) — no tensor ever materializes
    global-capacity buffers.  Per group:

      1. top-k routing → (token, expert, gate) triples
      2. stable sort by held expert (others last); position-in-expert via
         segment arithmetic
      3. scatter into [E_held, C, D]; batched expert GEMMs
      4. gather back with gate weighting; overflow (and tokens of experts
         not held) drop (GShard).
    """
    mo: MoEConfig = cfg.moe
    B, T, D = x.shape
    E, K, Eh, lo = mo.num_experts, mo.top_k, mo.held, mo.held_from
    C = max(1, int(math.ceil(T * K / E * mo.capacity_factor)))

    with jax.named_scope("moe.route"):
        gates, idx, scores = _router_gates(p, mo, x.reshape(B * T, D))
        local = idx - lo                                      # [BT,k]
        is_held = (local >= 0) & (local < Eh)
        hit = (jax.nn.one_hot(jnp.where(is_held, local, Eh), Eh + 1,
                              dtype=jnp.int32)[..., :Eh])     # [BT,k,Eh]
        if valid is not None:
            hit = hit * valid.reshape(B * T, 1, 1).astype(jnp.int32)
        routed = hit.sum((0, 1))
        aux = moe_load_balance_loss(scores, idx, E)

    with jax.named_scope("moe.experts"):
        if dropless:
            held_gates = jnp.einsum("tk,tke->te", gates,
                                    hit.astype(gates.dtype))
            y = _held_dropless(p, cfg, x.reshape(B * T, D), held_gates
                               ).reshape(B, T, D)
        else:
            y = _capacity_dispatch(p, cfg, x, gates.reshape(B, T, K),
                                   local.reshape(B, T, K),
                                   is_held.reshape(B, T, K), C)
        if mo.num_shared:
            y = y + apply_mlp(p["shared"], cfg, x.reshape(B * T, D)
                              ).reshape(B, T, D)
    return y, aux, routed


def _capacity_dispatch(p, cfg: ArchConfig, x, gates, local, is_held, C):
    B, T, D = x.shape
    K = gates.shape[-1]
    Eh = cfg.moe.held

    def dispatch_group(gate_g, local_g, held_g):
        """Returns (slot_tok [Eh,C], e_sorted, slot, t_sorted, w).

        The [Eh,C,D] buffer is built by GATHER (rows indexed by a tiny
        [Eh,C+1] int32 slot→token map built with a cheap scatter), never by
        scattering activations into the expert-sharded dim — GSPMD can keep
        an E-sharded gather fully local, whereas a data-dependent scatter
        into a sharded dim forces replication (observed: 3.1 TiB/device on
        deepseek train before this change)."""
        flat_e = jnp.where(held_g, local_g, Eh).reshape(-1)  # [T*K]
        flat_g = gate_g.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
        order = jnp.argsort(flat_e, stable=True)
        e_s, t_s, g_s = flat_e[order], flat_t[order], flat_g[order]
        starts = jnp.searchsorted(e_s, jnp.arange(Eh, dtype=e_s.dtype))
        e_c = jnp.minimum(e_s, Eh - 1)
        pos = jnp.arange(T * K, dtype=jnp.int32) - starts[e_c]
        keep = (e_s < Eh) & (pos < C)
        slot = jnp.where(keep, pos, C)                   # C = overflow bin
        slot_tok = jnp.full((Eh, C + 1), T, jnp.int32)   # T = "empty" row
        slot_tok = slot_tok.at[e_c, slot].set(
            jnp.where(keep, t_s, T))[:, :C]              # [Eh,C] tiny
        w = (g_s * keep.astype(jnp.float32))
        return slot_tok, e_c, slot, t_s, w

    slot_tok, e_s, slot, t_s, w = jax.vmap(dispatch_group)(gates, local,
                                                           is_held)
    # gather rows per expert slot: [B,Eh,C,D]; padded row T reads zeros
    x_pad = jnp.concatenate(
        [x, jnp.zeros((B, 1, D), x.dtype)], axis=1)      # [B,T+1,D]
    buf = jnp.take_along_axis(
        x_pad[:, :, None, :],
        slot_tok.reshape(B, Eh * C, 1, 1).astype(jnp.int32), axis=1
    ).reshape(B, Eh, C, D)
    # buf: [B,Eh,C,D] — B over data axes, E over "model" (EP)
    buf = ACT.moe_buf(buf)

    act = _expert_act(cfg)
    h = act(jnp.einsum("becd,edf->becf", buf, p["w_gate"]["w"])) \
        * jnp.einsum("becd,edf->becf", buf, p["w_up"]["w"])
    y_exp = ACT.moe_buf(
        jnp.einsum("becf,efd->becd", h, p["w_down"]["w"]))  # [B,Eh,C,D]

    def combine_group(y_g, e_s, slot, t_s, w):
        contrib = y_g[e_s, jnp.minimum(slot, C - 1)] \
            * w.astype(y_g.dtype)[:, None]
        return jnp.zeros((T, D), y_g.dtype).at[t_s].add(contrib)

    return jax.vmap(combine_group)(y_exp, e_s, slot, t_s, w)
