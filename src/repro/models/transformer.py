"""LM assembler: builds any assigned architecture from its ArchConfig.

Layers are grouped into homogeneous *segments* (``ArchConfig.segments``) and
scanned with stacked parameters — HLO size stays O(distinct block kinds), not
O(num_layers), which keeps the 512-device dry-run compile tractable even for
the 94-layer / 88-layer configs.

API (all pure functions):

  init_params(cfg, key)                         -> params pytree
  forward(params, cfg, tokens, ...)             -> logits [B,T,V]
  loss_fn(params, cfg, batch)                   -> (loss, metrics)
  init_cache(cfg, batch, max_len)               -> decode cache pytree
  prefill(params, cfg, tokens, cache)           -> (last_logits, cache, offset)
  decode_step(params, cfg, token, cache, offset)-> (logits, cache)

The loss is sequence-chunked (logits for 512 tokens at a time under
jax.checkpoint) so a 129k-vocab train step never materializes [B,T,V].
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from . import layers as L
from . import ssm as S
from . import act_sharding as ACT

LOSS_CHUNK = 512

# Rematerialization policy for the layer scan: "block" checkpoints each
# scanned unit (classic layer-remat: activations recomputed in backward),
# "dots" saves matmul outputs only, "none" stores everything.  Set by the
# trainer / dry-run driver; a policy knob, not an architecture property.
_REMAT = "block"


def set_remat(mode: str) -> None:
    global _REMAT
    if mode not in ("none", "block", "dots"):
        raise ValueError(mode)
    _REMAT = mode


def _maybe_remat(fn):
    if _REMAT == "none":
        return fn
    if _REMAT == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn)

ATTN_KINDS = ("attn", "attn_moe", "local", "global")
MLA_KINDS = ("mla", "mla_moe")
MAMBA_KINDS = ("mamba", "mamba_moe")
XLSTM_KINDS = ("mlstm", "slstm")
MOE_KINDS = ("attn_moe", "mla_moe", "mamba_moe")
# the SlotState split: positional (KV) caches are addressed by slot row /
# block table, recurrent caches by a pooled state row (``rec_rows``)
REC_KINDS = MAMBA_KINDS + XLSTM_KINDS


def has_recurrent(cfg: ArchConfig) -> bool:
    """True if any layer carries O(1) recurrent state (mamba / xLSTM)."""
    return any(k in REC_KINDS for unit, _ in cfg.segments() for k in unit)


def has_attention(cfg: ArchConfig) -> bool:
    """True if any layer carries a positional KV cache (attention / MLA)."""
    return any(k in ATTN_KINDS or k in MLA_KINDS
               for unit, _ in cfg.segments() for k in unit)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def init_block(key, cfg: ArchConfig, kind: str):
    dt = L._dtype(cfg)
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {}
    if kind in ATTN_KINDS:
        p["norm1"] = L.init_rmsnorm(cfg.d_model, dt)
        p["attn"] = L.init_attention(ks[0], cfg)
        p["norm2"] = L.init_rmsnorm(cfg.d_model, dt)
    elif kind in MLA_KINDS:
        p["norm1"] = L.init_rmsnorm(cfg.d_model, dt)
        p["attn"] = L.init_mla(ks[0], cfg)
        p["norm2"] = L.init_rmsnorm(cfg.d_model, dt)
    elif kind in MAMBA_KINDS:
        p["norm1"] = L.init_rmsnorm(cfg.d_model, dt)
        p["mamba"] = S.init_mamba(ks[0], cfg)
        p["norm2"] = L.init_rmsnorm(cfg.d_model, dt)
    elif kind == "mlstm":
        return {"cell": S.init_mlstm(ks[0], cfg)}
    elif kind == "slstm":
        return {"cell": S.init_slstm(ks[0], cfg)}
    else:
        raise ValueError(f"unknown layer kind {kind!r}")

    if cfg.norm_style == "sandwich":
        p["post1"] = L.init_rmsnorm(cfg.d_model, dt)
        p["post2"] = L.init_rmsnorm(cfg.d_model, dt)

    if kind in MOE_KINDS:
        p["ffn"] = L.init_moe(ks[1], cfg)
    else:
        p["ffn"] = L.init_mlp(ks[1], cfg)
    return p


def _gather_rec(cache, layer, rec_rows):
    """Layer ``layer``'s recurrent state, read from the segment's stacked
    leaves [reps, R, ...]: the pooled rows ``rec_rows`` [B] (serve engine)
    or, without them, every row."""
    rows = slice(None) if rec_rows is None else rec_rows
    return jax.tree.map(lambda x: x[layer, rows], cache)


def _scatter_rec(cache, new_state, layer, rec_rows):
    """Write layer ``layer``'s advanced state back into the stacked leaves
    in place.  Rows gated off by the update mask carry their own gathered
    value, so duplicate sentinel indices (row 0 for every masked batch
    row) all write identical bits — the scatter stays deterministic."""
    if rec_rows is None:
        return jax.tree.map(
            lambda full, ns: lax.dynamic_update_index_in_dim(
                full, ns.astype(full.dtype), layer, 0), cache, new_state)
    return jax.tree.map(
        lambda full, ns: full.at[layer, rec_rows].set(ns.astype(full.dtype)),
        cache, new_state)


def apply_block(p, cfg: ArchConfig, kind: str, h, *, positions,
                cache=None, offset=None, prefix_len=None, block_tables=None,
                paged_kernel="ref", rec_rows=None, update_mask=None,
                layer=None):
    """Returns (h, new_cache, aux_loss, routed).

    ``cache`` is the segment's stacked cache for this layer's position in
    the unit (leaves [reps, ...]), of which the block reads and writes
    repeat ``layer`` (traced) in place and returns the whole stack.
    ``rec_rows`` [B] addresses pooled recurrent state (serve engine): the
    block gathers each batch row's state from the pool, advances it, and
    scatters it back.  ``update_mask`` [B,T] prefix-gates the advance per
    row (chunk padding / inactive decode slots); attention layers ignore
    it — their masked writes land on causally-hidden positions instead —
    and an MoE layer counts only its set tokens in ``routed`` ([E_held]
    tokens routed to each held expert; None for a dense FFN).  With a
    cache (serving) the MoE layer is dropless."""
    aux = jnp.zeros((), jnp.float32)
    routed = None
    if kind in XLSTM_KINDS:
        fwd = S.mlstm_forward if kind == "mlstm" else S.slstm_forward
        state = None if cache is None else _gather_rec(cache, layer, rec_rows)
        h, new_state = fwd(p["cell"], cfg, h, state, update_mask=update_mask)
        if cache is not None:
            new_state = _scatter_rec(cache, new_state, layer, rec_rows)
        return h, new_state, aux, routed

    sandwich = cfg.norm_style == "sandwich"

    # --- mixer (attention / MLA / mamba) ---
    x = L.rms_norm(p["norm1"], h, cfg.norm_eps)
    if kind in ATTN_KINDS:
        window = cfg.sliding_window if kind == "local" else None
        mix, new_cache = L.apply_attention(
            p["attn"], cfg, x, positions=positions, kv_cache=cache,
            cache_offset=offset, window=window, prefix_len=prefix_len,
            block_tables=block_tables, paged_kernel=paged_kernel,
            layer=layer)
    elif kind in MLA_KINDS:
        mix, new_cache = L.apply_mla(p["attn"], cfg, x, positions=positions,
                                     kv_cache=cache, cache_offset=offset,
                                     block_tables=block_tables,
                                     paged_kernel=paged_kernel, layer=layer)
    else:  # mamba
        state = None if cache is None else _gather_rec(cache, layer, rec_rows)
        mix, new_cache = S.mamba_forward(p["mamba"], cfg, x, state,
                                         update_mask=update_mask)
        if cache is not None:
            new_cache = _scatter_rec(cache, new_cache, layer, rec_rows)
    if sandwich:
        mix = L.rms_norm(p["post1"], mix, cfg.norm_eps)
    h = h + mix

    # --- FFN / MoE ---
    x = L.rms_norm(p["norm2"], h, cfg.norm_eps)
    if kind in MOE_KINDS:
        y, aux, routed = L.apply_moe(p["ffn"], cfg, x,
                                     dropless=cache is not None,
                                     valid=update_mask)
    else:
        y = L.apply_mlp(p["ffn"], cfg, x)
    if sandwich:
        y = L.rms_norm(p["post2"], y, cfg.norm_eps)
    h = h + y
    return h, new_cache, aux, routed


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def _block_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int):
    dt = L._dtype(cfg)
    if kind in ATTN_KINDS:
        hkv, dh = cfg.num_kv_heads, cfg.head_dim
        z = lambda *s: jnp.zeros(s, dt)
        return {"k": z(batch, max_len, hkv, dh), "v": z(batch, max_len, hkv, dh)}
    if kind in MLA_KINDS:
        m = cfg.mla
        return {"c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dt),
                "k_rope": jnp.zeros((batch, max_len,
                                     L.rope_lanes(m.qk_rope_head_dim)), dt)}
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    if kind in MAMBA_KINDS:
        return {"conv": jnp.zeros((batch, s.d_conv - 1, d_in), dt),
                "h": jnp.zeros((batch, d_in, s.d_state), jnp.float32)}
    if kind == "mlstm":
        nh, dh = s.num_heads, d_in // s.num_heads
        return {"conv": jnp.zeros((batch, s.d_conv - 1, d_in), dt),
                "C": jnp.zeros((batch, nh, dh, dh), jnp.float32),
                "n": jnp.zeros((batch, nh, dh), jnp.float32),
                "m": jnp.zeros((batch, nh), jnp.float32)}
    if kind == "slstm":
        D = cfg.d_model
        z = lambda: jnp.zeros((batch, D), jnp.float32)
        return {"conv": jnp.zeros((batch, s.d_conv - 1, D), dt),
                "c": z(), "n": z(), "h": z(), "m": z()}
    raise ValueError(kind)


def init_cache(cfg: ArchConfig, batch: int, max_len: int):
    """Per-segment stacked caches: leading dim = segment repeat count."""
    caches = []
    for unit, reps in cfg.segments():
        unit_cache = {f"l{j}": _block_cache(cfg, kind, batch, max_len)
                      for j, kind in enumerate(unit)}
        caches.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (reps,) + x.shape).copy(),
            unit_cache))
    return caches


def init_paged_cache(cfg: ArchConfig, num_blocks: int, block_size: int):
    """Pooled paged cache: every leaf is [reps, num_blocks, block_size, ...].

    Structurally this is ``init_cache`` with (batch=num_blocks,
    max_len=block_size) — axis 1 is the PHYSICAL BLOCK dim and axis 2 the
    position-in-block dim; block tables map each slot's virtual positions
    onto it.  Positional caches (attention / MLA) only: a recurrent state
    has no positions to page."""
    for unit, _reps in cfg.segments():
        for kind in unit:
            if kind not in ATTN_KINDS and kind not in MLA_KINDS:
                raise ValueError(
                    f"{cfg.name}: layer kind {kind!r} has a recurrent "
                    "cache; the paged backend supports attention/MLA only "
                    "— use init_hybrid_cache for mixed stacks")
    return init_cache(cfg, num_blocks, block_size)


def init_hybrid_cache(cfg: ArchConfig, *, kv_batch: int, kv_len: int,
                      rec_batch: int):
    """SlotState cache for mixed stacks: each layer's leaves sized by its
    backend.  Positional (attention / MLA) leaves get the KV geometry —
    ``(kv_batch, kv_len)`` is ``(max_slots, max_len)`` for the contiguous
    backend or ``(num_blocks, block_size)`` for the paged one.  Recurrent
    leaves get ``rec_batch`` pooled state rows (row 0 is the sentinel row
    masked decode slots address, so pass usable_rows + 1)."""
    caches = []
    for unit, reps in cfg.segments():
        unit_cache = {}
        for j, kind in enumerate(unit):
            if kind in REC_KINDS:
                unit_cache[f"l{j}"] = _block_cache(cfg, kind, rec_batch, 0)
            else:
                unit_cache[f"l{j}"] = _block_cache(cfg, kind, kv_batch,
                                                   kv_len)
        caches.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (reps,) + x.shape).copy(),
            unit_cache))
    return caches


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: ArchConfig, key) -> Dict[str, Any]:
    dt = L._dtype(cfg)
    keys = jax.random.split(key, 8)
    V, D = cfg.vocab_size, cfg.d_model
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(keys[0], (V, D), jnp.float32)
                  * 0.02).astype(dt),
        "final_norm": L.init_rmsnorm(D, dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = L._init_dense(keys[1], D, V, dt,
                                       scale=1.0 / math.sqrt(D))
    if cfg.frontend:
        fd = cfg.frontend_dim or D
        params["frontend_proj"] = L._init_dense(keys[2], fd, D, dt)
    if cfg.pos_embed == "sinusoidal":
        pass  # non-learned

    segs = []
    seg_key = keys[3]
    for unit, reps in cfg.segments():
        seg_key, sub = jax.random.split(seg_key)
        unit_keys = jax.random.split(sub, reps)

        def init_unit(k, unit=unit):
            uks = jax.random.split(k, len(unit))
            return {f"l{j}": init_block(uks[j], cfg, kind)
                    for j, kind in enumerate(unit)}

        segs.append(jax.vmap(init_unit)(unit_keys))
    params["segments"] = segs

    if cfg.mtp_depth:
        mtp_keys = jax.random.split(keys[4], cfg.mtp_depth)
        params["mtp"] = [
            {"proj": L._init_dense(mtp_keys[i], 2 * D, D, dt),
             "block": init_block(jax.random.fold_in(mtp_keys[i], 7), cfg,
                                 "mla" if cfg.mla else "attn"),
             "norm": L.init_rmsnorm(D, dt)}
            for i in range(cfg.mtp_depth)]
    return params


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------


def _embed(params, cfg: ArchConfig, tokens, frontend_embeds=None,
           positions=None):
    h = params["embed"][tokens]
    if cfg.embed_scale:
        h = h * math.sqrt(cfg.d_model)
    if cfg.frontend and frontend_embeds is not None:
        pre = L.dense(params["frontend_proj"], frontend_embeds.astype(h.dtype))
        h = jnp.concatenate([pre, h], axis=1)
    if cfg.pos_embed == "sinusoidal":
        if positions is None:
            positions = jnp.arange(h.shape[1], dtype=jnp.int32)
        pe = L.sinusoidal_pos(positions, cfg.d_model).astype(h.dtype)
        h = h + (pe[None] if pe.ndim == 2 else pe)
    return ACT.hidden(h)


def _run_segments(params, cfg: ArchConfig, h, *, positions, caches=None,
                  offset=None, prefix_len=None, block_tables=None,
                  paged_kernel="ref", rec_rows=None, update_mask=None):
    """Scan each segment's stacked unit over its repeats.  Returns (h,
    new_caches, aux_total, routed): ``routed`` [L_moe, E_held] int32 holds
    the tokens routed to each held expert of each MoE layer, in layer
    order, when a cache is given (None without one, or with no MoE
    layer).

    A segment's stacked cache rides in the scan's carry, and repeat ``i``
    reads and writes layer ``i`` of each leaf in place: a step moves only
    the rows it writes, never a whole layer's pool (a cache given as the
    scan's xs / ys would be sliced out and written anew every layer)."""
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    routed = []
    for si, (unit, reps) in enumerate(cfg.segments()):
        seg_params = params["segments"][si]
        seg_cache = None if caches is None else caches[si]

        def unit_step(h, p_unit, c_unit=None, i=None, unit=unit):
            aux_sum = jnp.zeros((), jnp.float32)
            new_c, counts = {}, []
            for j, kind in enumerate(unit):
                c = None if c_unit is None else c_unit[f"l{j}"]
                h, nc, aux, r = apply_block(
                    p_unit[f"l{j}"], cfg, kind, h, positions=positions,
                    cache=c, offset=offset, prefix_len=prefix_len,
                    block_tables=block_tables, paged_kernel=paged_kernel,
                    rec_rows=rec_rows, update_mask=update_mask, layer=i)
                new_c[f"l{j}"] = nc
                aux_sum = aux_sum + aux
                if r is not None:
                    counts.append(r)
            counts = jnp.stack(counts) if counts else None
            return ACT.hidden(h), new_c, aux_sum, counts

        if seg_cache is None:
            # drop per-layer cache outputs to keep train HLO lean
            def body_nocache(h, p_unit, unit=unit):
                h, _, aux_sum, _ = unit_step(h, p_unit, unit=unit)
                return h, aux_sum
            h, auxs = lax.scan(_maybe_remat(body_nocache), h, seg_params)
            new_caches.append(None)
        else:
            def body(carry, xs, unit=unit):
                h, c_unit = carry
                p_unit, i = xs
                h, c_unit, aux_sum, counts = unit_step(h, p_unit, c_unit, i,
                                                       unit=unit)
                return (h, c_unit), (aux_sum, counts)
            (h, ncache), (auxs, counts) = lax.scan(
                body, (h, seg_cache),
                (seg_params, jnp.arange(reps, dtype=jnp.int32)))
            new_caches.append(ncache)
            if counts is not None:           # [reps, moe layers in unit, E]
                routed.append(counts.reshape(-1, counts.shape[-1]))
        aux_total = aux_total + jnp.sum(auxs)
    routed = jnp.concatenate(routed) if routed else None
    return h, new_caches, aux_total, routed


def forward(params, cfg: ArchConfig, tokens, frontend_embeds=None,
            positions=None):
    """Full-sequence logits (small vocab / small T only — training uses
    ``loss_fn`` which chunks the head)."""
    h = _embed(params, cfg, tokens, frontend_embeds)
    T = h.shape[1]
    if positions is None:
        positions = jnp.arange(T, dtype=jnp.int32)
    prefix_len = cfg.frontend_tokens if cfg.prefix_lm else None
    h, _, aux, _ = _run_segments(params, cfg, h, positions=positions,
                                 prefix_len=prefix_len)
    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
    logits = _head(params, cfg, h)
    return logits


def _head(params, cfg: ArchConfig, h):
    w = params["embed"].T if cfg.tie_embeddings else params["head"]["w"]
    logits = ACT.logits((h @ w).astype(jnp.float32))
    return L.softcap(logits, cfg.logit_softcap)


def _chunked_xent(params, cfg: ArchConfig, h, labels, mask):
    """Sequence-chunked cross-entropy: logits never exceed [B,chunk,V]."""
    B, T, D = h.shape
    chunk = min(LOSS_CHUNK, T)
    n_chunks = -(-T // chunk)
    pad = n_chunks * chunk - T
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hc = h.reshape(B, n_chunks, chunk, D)
    lc = labels.reshape(B, n_chunks, chunk)
    mc = mask.reshape(B, n_chunks, chunk)

    @jax.checkpoint
    def chunk_loss(h_j, l_j, m_j):
        logits = _head(params, cfg, h_j)               # [B,chunk,V] f32
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l_j[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * m_j
        return jnp.sum(nll), jnp.sum(m_j)

    def body(carry, xs):
        tot, cnt = carry
        s, c = chunk_loss(*xs)
        return (tot + s, cnt + c), None

    (tot, cnt), _ = lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (jnp.moveaxis(hc, 1, 0), jnp.moveaxis(lc, 1, 0),
         jnp.moveaxis(mc, 1, 0)))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params, cfg: ArchConfig, batch) -> Tuple[jax.Array, Dict]:
    """batch: {tokens [B,T], labels [B,T], (frontend [B,Tf,Df])}.

    labels < 0 are masked. Returns (loss, metrics)."""
    tokens = batch["tokens"]
    labels = batch["labels"]
    h = _embed(params, cfg, tokens, batch.get("frontend"))
    T = h.shape[1]
    positions = jnp.arange(T, dtype=jnp.int32)
    prefix_len = cfg.frontend_tokens if cfg.prefix_lm else None
    h, _, aux, _ = _run_segments(params, cfg, h, positions=positions,
                                 prefix_len=prefix_len)
    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)

    if cfg.frontend and batch.get("frontend") is not None:
        h_txt = h[:, cfg.frontend_tokens:]
    else:
        h_txt = h
    mask = (labels >= 0).astype(jnp.float32)
    labels_safe = jnp.maximum(labels, 0)
    loss = _chunked_xent(params, cfg, h_txt, labels_safe, mask)
    metrics = {"xent": loss, "aux": aux}

    if cfg.mtp_depth and "mtp" in params:
        # DeepSeek MTP: module i predicts token t+1+i from [h_t ; emb_{t+i}]
        mtp_loss = jnp.zeros((), jnp.float32)
        h_cur = h_txt
        for i, mod in enumerate(params["mtp"]):
            emb_next = params["embed"][tokens[:, 1 + i:]]
            h_in = jnp.concatenate(
                [h_cur[:, :emb_next.shape[1]],
                 emb_next.astype(h_cur.dtype)], axis=-1)
            h_i = L.dense(mod["proj"], h_in)
            kind = "mla" if cfg.mla else "attn"
            pos_i = jnp.arange(h_i.shape[1], dtype=jnp.int32)
            h_i, _, _, _ = apply_block(mod["block"], cfg, kind, h_i,
                                       positions=pos_i)
            h_i = L.rms_norm(mod["norm"], h_i, cfg.norm_eps)
            lbl_i = labels[:, 1 + i:]
            msk_i = (lbl_i >= 0).astype(jnp.float32)
            mtp_loss = mtp_loss + _chunked_xent(
                params, cfg, h_i, jnp.maximum(lbl_i, 0), msk_i)
            h_cur = h_i
        loss = loss + cfg.mtp_loss_weight * mtp_loss / cfg.mtp_depth
        metrics["mtp"] = mtp_loss

    if cfg.moe:
        loss = loss + 0.01 * aux
    return loss, metrics


# ---------------------------------------------------------------------------
# inference: prefill + decode
# ---------------------------------------------------------------------------


def prefill(params, cfg: ArchConfig, tokens, cache, frontend_embeds=None):
    """Fill the cache with the prompt; logits for the last position only."""
    h = _embed(params, cfg, tokens, frontend_embeds)
    T = h.shape[1]
    positions = jnp.arange(T, dtype=jnp.int32)
    prefix_len = cfg.frontend_tokens if cfg.prefix_lm else None
    offset = jnp.zeros((), jnp.int32)
    h, new_caches, _, _ = _run_segments(params, cfg, h,
                                        positions=positions, caches=cache,
                                        offset=offset,
                                        prefix_len=prefix_len)
    h_last = L.rms_norm(params["final_norm"], h[:, -1:], cfg.norm_eps)
    return _head(params, cfg, h_last), new_caches, jnp.array(T, jnp.int32)


def decode_step(params, cfg: ArchConfig, token, cache, offset,
                block_tables=None, paged_kernel="ref", rec_rows=None,
                active=None, return_routed: bool = False):
    """token: [B,1] ints; offset: tokens-already-cached — a scalar shared by
    the batch, or a per-row [B] vector (serve slots at independent lengths
    inside one batched decode step).  ``block_tables`` [B, n] switches the
    cache to the paged layout (pooled leaves, see ``init_paged_cache``);
    ``paged_kernel="pallas"`` routes paged attention through the fused
    block-table decode kernel instead of gather-then-attend.

    Recurrent layers (SlotState "recurrent" backend): ``rec_rows`` [B]
    addresses each batch row's pooled state row, ``active`` [B] bool gates
    the state advance — inactive rows map to the sentinel row 0 and keep
    it unchanged, so masked decode rows never touch live state.

    ``return_routed`` (MoE architectures) adds a third output: the tokens
    of the step routed to each held expert of each MoE layer,
    [L_moe, E_held] int32, counted over the ``active`` rows only."""
    B = token.shape[0]
    off = jnp.asarray(offset)
    if off.ndim == 1:
        positions = off[:, None].astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(off[None, None], (B, 1)).astype(jnp.int32)
    update_mask = None
    if active is not None:
        update_mask = jnp.asarray(active).reshape(B, 1).astype(bool)
    h = _embed(params, cfg, token, positions=positions)
    h, new_caches, _, routed = _run_segments(
        params, cfg, h, positions=positions, caches=cache, offset=offset,
        block_tables=block_tables, paged_kernel=paged_kernel,
        rec_rows=rec_rows, update_mask=update_mask)
    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
    if return_routed:
        return _head(params, cfg, h), new_caches, routed
    return _head(params, cfg, h), new_caches


def prefill_chunk(params, cfg: ArchConfig, tokens, cache, offset,
                  with_logits: bool = True, block_tables=None,
                  rec_rows=None, valid=None):
    """Write a prompt chunk at cache positions [offset, offset+T).

    The serve engine's chunked-admission primitive: a fixed-shape [B,T]
    chunk lands at a (traced) scalar ``offset``, so arbitrary prompt
    lengths stream through one compiled function.  Returns logits for the
    WHOLE chunk [B,T,V] (the engine picks the real last position — the tail
    chunk is right-padded) and the updated cache.  Interior chunks only
    feed the cache: pass ``with_logits=False`` (a Python-level switch —
    compile one variant per value) to skip the full-vocab head projection,
    the dominant FLOPs at production vocab sizes; logits come back None.

    Positional caches tolerate padding anywhere (garbage positions stay
    causally hidden until overwritten); recurrent caches would advance on
    it, so recurrent-bearing archs pass ``valid`` — the count of real
    tokens from the chunk start — and ``rec_rows`` [B] addressing the
    pooled state rows: state advances over exactly the first ``valid``
    positions and freezes on the padded tail.
    """
    B, T = tokens.shape
    if T >= L.QUERY_CHUNK_THRESHOLD:
        # the blocked-attention path (chunk_q) computes STATIC per-block key
        # extents assuming positions start at 0 — at a nonzero cache offset
        # it would silently mask out the causally-visible prefix
        raise ValueError(
            f"prefill chunk length {T} >= {L.QUERY_CHUNK_THRESHOLD}: "
            "offset prefill must stay below the blocked-attention "
            "threshold — use smaller chunks")
    off = jnp.asarray(offset, jnp.int32)
    positions = (off + jnp.arange(T, dtype=jnp.int32))[None, :]
    positions = jnp.broadcast_to(positions, (B, T))
    update_mask = None
    if valid is not None:
        v = jnp.asarray(valid, jnp.int32)
        update_mask = jnp.broadcast_to(
            (jnp.arange(T, dtype=jnp.int32) < v)[None, :], (B, T))
    h = _embed(params, cfg, tokens, positions=positions)
    h, new_caches, _, _ = _run_segments(params, cfg, h, positions=positions,
                                        caches=cache, offset=off,
                                        block_tables=block_tables,
                                        rec_rows=rec_rows,
                                        update_mask=update_mask)
    if not with_logits:
        return None, new_caches
    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
    return _head(params, cfg, h), new_caches


# ---------------------------------------------------------------------------
# per-slot cache surgery (serve engine)
# ---------------------------------------------------------------------------
#
# Cache leaves are stacked per segment as [reps, B, ...]: axis 1 is the
# batch/slot dim.  These three ops are the whole slot-reuse cache API —
# admission takes a slot view, prefills it, writes it back; completion
# resets the slot.  All accept a traced slot index (jit-stable).


def take_slot(cache, slot):
    """Extract one slot's cache as a batch-1 view (leaf [reps, 1, ...])."""
    return jax.tree.map(
        lambda x: lax.dynamic_slice_in_dim(x, slot, 1, axis=1), cache)


def write_slot(cache, sub, slot):
    """Write a batch-1 slot cache (from ``take_slot``) back at ``slot``."""
    return jax.tree.map(
        lambda x, s: lax.dynamic_update_slice_in_dim(
            x, s.astype(x.dtype), slot, axis=1), cache, sub)


def reset_slot(cache, slot):
    """Zero one slot's rows in every cache leaf, other slots untouched."""
    return jax.tree.map(lambda x: x.at[:, slot].set(jnp.zeros((), x.dtype)),
                        cache)


# Kind-aware variants (the SlotState protocol): in a hybrid cache, axis 1
# means "slot row" for contiguous-KV leaves, "physical block" for paged
# leaves, and "pooled state row" for recurrent leaves — so slot surgery
# must walk the config in parallel with the cache and touch only the
# leaves whose backend it addresses.


def _map_by_kind(cfg, cache, fn_for_kind):
    """Apply ``fn_for_kind(kind) -> leaf_fn | None`` over each layer's
    subtree (None = leave the layer's leaves untouched)."""
    out = []
    for si, (unit, _reps) in enumerate(cfg.segments()):
        seg = {}
        for j, kind in enumerate(unit):
            fn = fn_for_kind(kind)
            leaves = cache[si][f"l{j}"]
            seg[f"l{j}"] = leaves if fn is None else jax.tree.map(fn, leaves)
        out.append(seg)
    return out


def take_state(cfg, cache, slot):
    """Slice one contiguous-KV slot's rows as a batch-1 view; recurrent
    leaves pass through WHOLE (they are addressed by ``rec_rows`` inside
    the forward, not by the batch dim)."""
    return _map_by_kind(
        cfg, cache,
        lambda kind: None if kind in REC_KINDS else
        (lambda x: lax.dynamic_slice_in_dim(x, slot, 1, axis=1)))


def write_state(cfg, cache, sub, slot):
    """Write a ``take_state`` view back: contiguous-KV leaves land in the
    slot's row; recurrent leaves come back whole (the forward already
    scattered their rows in place)."""
    out = []
    for si, (unit, _reps) in enumerate(cfg.segments()):
        seg = {}
        for j, kind in enumerate(unit):
            full, s = cache[si][f"l{j}"], sub[si][f"l{j}"]
            if kind in REC_KINDS:
                seg[f"l{j}"] = s
            else:
                seg[f"l{j}"] = jax.tree.map(
                    lambda x, y: lax.dynamic_update_slice_in_dim(
                        x, y.astype(x.dtype), slot, axis=1), full, s)
        out.append(seg)
    return out


def reset_slot_state(cfg, cache, slot=None, rec_row=None):
    """Zero a contiguous-KV slot row (``slot``) and/or a pooled recurrent
    state row (``rec_row``); pass None to leave that backend untouched
    (paged-KV leaves are always untouched — block freshness is the
    allocator's job)."""
    def fn(kind):
        if kind in REC_KINDS:
            if rec_row is None:
                return None
            return lambda x: x.at[:, rec_row].set(jnp.zeros((), x.dtype))
        if slot is None:
            return None
        return lambda x: x.at[:, slot].set(jnp.zeros((), x.dtype))
    return _map_by_kind(cfg, cache, fn)


def copy_block(cache, src, dst):
    """Copy one physical block's payload in every paged-cache leaf
    (leaf [reps, num_blocks, block_size, ...], axis 1 = block).  The
    device half of copy-on-write: the allocator hands out a private block
    id and this clones the shared content into it before any write.
    Traced src/dst (jit-stable)."""
    def cp(x):
        blk = lax.dynamic_slice_in_dim(x, src, 1, axis=1)
        return lax.dynamic_update_slice_in_dim(x, blk, dst, axis=1)
    return jax.tree.map(cp, cache)
