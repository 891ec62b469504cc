"""Plain reference for a DeepSeek-V3-style decoder at one chip's
expert-parallel share, in straightforward jax.numpy, written from the
published equations (arXiv:2412.19437, §2.1) and the configuration file
alone.

It imports nothing of the program.  Its weights come from the seed
through ``weights``, one layer at a time, under the program's parameter
names, so the reference never reads what the program holds.

Equations (pre-norm decoder; ``first_k_dense_replace`` dense layers, then
MoE layers):

  h0 = E[x]
  per layer:  a = RMSNorm(h; g1)
    MLA (full, not absorbed):
      c_q = RMSNorm(a W_dq; g_q);  [q_nope, q_pe] = c_q W_uq   per head
      [c_kv, k_pe] = a W_dkv;  c_kv = RMSNorm(c_kv; g_kv)
      q_pe, k_pe = RoPE(q_pe), RoPE(k_pe)         (k_pe shared by heads)
      [k_nope, v] = c_kv W_ukv                     per head
      o = softmax([q_nope, q_pe] [k_nope, k_pe]^T * s_a + causal) v
      h = h + o W_o
    RoPE under rope_scaling (YaRN, arXiv:2309.00071, as DeepSeek-V3's
    modeling code applies it): with f_i = theta^(-2i/dr), i < dr/2, and
    d(n) = dr ln(L0 / (2 pi n)) / (2 ln theta) (L0 the original context),
      lo = max(floor(d(beta_fast)), 0), hi = min(ceil(d(beta_slow)), dr-1)
      w_i = clip((i - lo) / (hi - lo), 0, 1)
      f'_i = f_i / factor * w_i + f_i * (1 - w_i)
      cos, sin of pos f'_i, times m(mscale) / m(mscale_all_dim),
      where m(x) = 0.1 x ln(factor) + 1;
    s_a = m(mscale_all_dim)^2 / sqrt(dn + dr)   (1 / sqrt(dn + dr) unscaled)
    m = RMSNorm(h; g2)
    dense layer:  h = h + (silu(m Wg) * (m Wu)) Wd
    MoE layer (router over all E experts, sigmoid scores s = sigmoid(m W_r)):
      s' = s + b (the correction bias: selection only)
      group score = sum of the best two s' of each of n_group groups;
      the best topk_group groups are searched, the top k of s' in them
      chosen; gates = s of the chosen, over their sum, times
      routed_scaling_factor;
      h = h + sum over the HELD experts e of gate_e * FFN_e(m) + FFN_shared(m)
      (the gate of an expert that was not chosen is 0; the experts not
      held here would add their part on other chips, and are left out)
  logits = RMSNorm(h; gf) W_head                 (untied)

Departure, the same on both sides: RoPE rotates pairs (i, i + dr/2) —
the published checkpoint's pairs (2i, 2i+1) are a fixed permutation of
the rotary columns of W_uq and W_dkv, which random weights do not see.

``precision`` is ``reference.Precision``: ``f32`` (the reference,
float32 at HIGHEST) or the controls ``bf16`` and ``fp8``.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W
from .reference import Precision, rms_norm

ATTN = ("norm1/scale", "norm2/scale", "attn/q_down/w", "attn/q_norm/scale",
        "attn/q_up/w", "attn/kv_down/w", "attn/kv_norm/scale",
        "attn/kv_up/w", "attn/wo/w")
DENSE = ("ffn/w_gate/w", "ffn/w_up/w", "ffn/w_down/w")
MOE = ("ffn/router/w", "ffn/router/b", "ffn/w_gate/w", "ffn/w_up/w",
       "ffn/w_down/w", "ffn/shared/w_gate/w", "ffn/shared/w_up/w",
       "ffn/shared/w_down/w")
ROUTER = ("ffn/router/w", "ffn/router/b")          # kept in float32
# the widest served tokens judged also across their narrowest router tie
TIE_JUDGED = 8


def kinds(c: Dict[str, Any]) -> List[str]:
    k = int(c["first_k_dense_replace"])
    return ["dense"] * k + ["moe"] * (int(c["num_hidden_layers"]) - k)


def layer_names(c: Dict[str, Any]) -> List[Tuple[str, int]]:
    """(name prefix, index in its stack) of each layer, as the program's
    parameter tree stacks them: the layer pattern's shortest period that
    tiles it is one stacked unit, else each run of one kind is its own
    stack (the tree's naming, which ``weights`` draws by)."""
    pat = kinds(c)
    n = len(pat)
    for p in range(1, n + 1):
        if n % p == 0 and pat == pat[:p] * (n // p):
            return [(f"segments/0/l{i % p}/", i // p) for i in range(n)]
    out, seg, i = [], 0, 0
    while i < n:
        j = i
        while j < n and pat[j] == pat[i]:
            out.append((f"segments/{seg}/l0/", j - i))
            j += 1
        seg, i = seg + 1, j
    return out


def shapes(c: Dict[str, Any], kind: str) -> Dict[str, Tuple[int, ...]]:
    """Shape of each of one layer's leaves, by short name."""
    D, H = int(c["hidden_size"]), int(c["num_attention_heads"])
    qr, r = int(c["q_lora_rank"]), int(c["kv_lora_rank"])
    dn, dr, dv = (int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"]),
                  int(c["v_head_dim"]))
    s = {"norm1/scale": (D,), "norm2/scale": (D,),
         "attn/q_down/w": (D, qr), "attn/q_norm/scale": (qr,),
         "attn/q_up/w": (qr, H * (dn + dr)), "attn/kv_down/w": (D, r + dr),
         "attn/kv_norm/scale": (r,), "attn/kv_up/w": (r, H * (dn + dv)),
         "attn/wo/w": (H * dv, D)}
    if kind == "dense":
        F = int(c["intermediate_size"])
        s.update({"ffn/w_gate/w": (D, F), "ffn/w_up/w": (D, F),
                  "ffn/w_down/w": (F, D)})
    else:
        E, Eh = int(c["router_experts"]), int(c["n_routed_experts"])
        F = int(c["moe_intermediate_size"])
        Fs = F * int(c["n_shared_experts"])
        s.update({"ffn/router/w": (D, E), "ffn/router/b": (E,),
                  "ffn/w_gate/w": (Eh, D, F), "ffn/w_up/w": (Eh, D, F),
                  "ffn/w_down/w": (Eh, F, D),
                  "ffn/shared/w_gate/w": (D, Fs),
                  "ffn/shared/w_up/w": (D, Fs),
                  "ffn/shared/w_down/w": (Fs, D)})
    return s


def program_dtype(c: Dict[str, Any]):
    return jnp.dtype(c["precision"]["params"])


def layer_weights(c: Dict[str, Any], seed: int, l: int, pr: Precision
                  ) -> Dict[str, Any]:
    prefix, idx = layer_names(c)[l]
    dt = program_dtype(c)
    out = {}
    for name, shape in shapes(c, kinds(c)[l]).items():
        keep = jnp.float32 if name in ROUTER else dt
        w = W.layer_leaf(seed, prefix + name, idx, shape, keep)
        out[name] = pr.weight(w, name.endswith("/w"))
    return out


# -- the layer ----------------------------------------------------------------

def select(sb, c: Dict[str, Any], flip=None):
    """The published noaux_tc choice from the biased scores sb [T, E]:
    the best topk_group groups by the sum of their best two, then the
    top k within them.  Returns (chosen [T, E] bool, group margin [T],
    expert margin [T]): how far the last group (expert) kept lies above
    the best one left out, the distance a rounding has to cross to move
    the token.  ``flip`` [T] int32, where not 0, takes the other side of
    one of those near-ties at that row: 1 swaps the last group kept for
    the best left out, 2 the last expert chosen for the best left out."""
    E = int(c["router_experts"])
    G, kg = int(c["n_group"]), int(c["topk_group"])
    k = int(c["num_experts_per_tok"])
    grp = jnp.sort(sb.reshape(-1, G, E // G), axis=-1)[..., -2:].sum(-1)
    g_rank = jnp.argsort(jnp.argsort(-grp, axis=-1), axis=-1)     # 0 = best
    keep = g_rank < kg
    if flip is not None:
        keep = jnp.where((flip == 1)[:, None],
                         (g_rank < kg - 1) | (g_rank == kg), keep)
    cand = jnp.where(jnp.repeat(keep, E // G, axis=-1), sb, -jnp.inf)
    e_rank = jnp.argsort(jnp.argsort(-cand, axis=-1), axis=-1)
    chosen = e_rank < k
    if flip is not None:
        chosen = jnp.where((flip == 2)[:, None],
                           (e_rank < k - 1) | (e_rank == k), chosen)
    gs, es = -jnp.sort(-grp, axis=-1), -jnp.sort(-cand, axis=-1)
    g_margin = (gs[:, kg - 1] - gs[:, kg] if kg < G
                else jnp.full(gs.shape[:1], jnp.inf))
    return chosen, g_margin, es[:, k - 1] - es[:, k]


def route(m, w_r, bias, c: Dict[str, Any], pr: Precision, flip=None):
    """Gates [T, E] of the published noaux_tc router: zero off the chosen
    top k, the chosen experts' sigmoid scores normalized and scaled; and
    ``select``'s two margins."""
    s = jax.nn.sigmoid(pr.mm(m, w_r).astype(jnp.float32))        # [T, E]
    chosen, g_margin, e_margin = select(s + bias.astype(jnp.float32), c,
                                        flip)
    g = jnp.where(chosen, s, 0.0)
    if c["norm_topk_prob"]:
        g = g / g.sum(-1, keepdims=True)
    return g * float(c["routed_scaling_factor"]), (g_margin, e_margin)


def rotary(c: Dict[str, Any], dr: int):
    """The rotary frequencies [dr/2], the amplitude of cos and sin, and
    the softmax scale, from ``rope_theta`` and ``rope_scaling`` (None or
    YaRN; any other is refused)."""
    theta = float(c["rope_theta"])
    freq = 1.0 / theta ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
    dn = int(c["qk_nope_head_dim"])
    ys = c.get("rope_scaling")
    if ys is None:
        return freq, 1.0, 1.0 / math.sqrt(dn + dr)
    if ys.get("type") != "yarn":
        raise ValueError(f"{c['name']}: rope_scaling {ys.get('type')!r} is "
                         "not mapped (only yarn)")
    factor = float(ys["factor"])
    l0 = float(ys["original_max_position_embeddings"])

    def m(x):
        return 1.0 if factor <= 1 else 0.1 * x * math.log(factor) + 1.0

    def d(n):
        return dr * math.log(l0 / (2 * math.pi * n)) / (2 * math.log(theta))

    lo = max(math.floor(d(float(ys["beta_fast"]))), 0)
    hi = min(math.ceil(d(float(ys["beta_slow"]))), dr - 1)
    w = np.clip((np.arange(dr // 2) - lo) / ((hi - lo) or 0.001), 0, 1)
    freq = freq / factor * w + freq * (1 - w)
    ma = float(ys.get("mscale_all_dim") or 0.0)
    amp = m(float(ys.get("mscale", 1.0))) / m(ma)
    return freq, amp, (m(ma) ** 2 if ma else 1.0) / math.sqrt(dn + dr)


def rope(x, pos, freq, amp):
    """x [T, H, dr], pos [T]: rotate pairs (i, i + dr/2) by pos * freq_i,
    cos and sin times ``amp``."""
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(freq, jnp.float32)
    cos = (jnp.cos(ang) * amp)[:, None, :]
    sin = (jnp.sin(ang) * amp)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def ffn(m, wg, wu, wd, pr: Precision):
    gate = pr.mm(m, wg).astype(jnp.float32)
    up = pr.mm(m, wu).astype(jnp.float32)
    return pr.mm((jax.nn.silu(gate) * up).astype(pr.act), wd)


def layer(p: Dict[str, Any], h, pos, c: Dict[str, Any], kind: str,
          pr: Precision, q_block: int = 256, flip=None):
    """One layer over one sequence h [T, D] at positions pos [T]; queries
    in blocks of ``q_block`` rows.  Returns h and, for an MoE layer, the
    router's two margins [T] (``select``; infinite for a dense layer),
    with ``flip`` passed to the router."""
    T = h.shape[0]
    H = int(c["num_attention_heads"])
    r = int(c["kv_lora_rank"])
    dn, dr, dv = (int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"]),
                  int(c["v_head_dim"]))
    eps = float(c["rms_norm_eps"])
    freq, amp, scale = rotary(c, dr)
    a = rms_norm(h, p["norm1/scale"], eps)
    cq = rms_norm(pr.mm(a, p["attn/q_down/w"]), p["attn/q_norm/scale"], eps)
    q = pr.mm(cq, p["attn/q_up/w"]).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, freq, amp)], -1)
    ckv = pr.mm(a, p["attn/kv_down/w"])
    c_kv = rms_norm(ckv[:, :r], p["attn/kv_norm/scale"], eps)
    k_pe = rope(ckv[:, None, r:], pos, freq, amp)                 # [T,1,dr]
    kv = pr.mm(c_kv, p["attn/kv_up/w"]).reshape(T, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (T, H, dr))], -1)
    v = kv[..., dn:]
    outs = []
    for s0 in range(0, T, q_block):
        qb = q[s0:s0 + q_block]
        sc = jnp.einsum("qhd,thd->hqt", qb.astype(jnp.float32),
                        k.astype(jnp.float32), precision=pr.hp) * scale
        allowed = pos[None, :] <= pos[s0:s0 + q_block, None]
        sc = jnp.where(allowed[None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        ob = jnp.einsum("hqt,thd->qhd", w.astype(pr.act), v,
                        precision=pr.hp, preferred_element_type=jnp.float32)
        outs.append(ob.reshape(-1, H * dv).astype(pr.act))
    h = h + pr.mm(jnp.concatenate(outs, 0), p["attn/wo/w"])
    m = rms_norm(h, p["norm2/scale"], eps)
    if kind == "dense":
        none = jnp.full((T,), jnp.inf)
        return h + ffn(m, p["ffn/w_gate/w"], p["ffn/w_up/w"],
                       p["ffn/w_down/w"], pr), (none, none)
    lo = int(c["experts_held_from"])
    gates, margins = route(m, p["ffn/router/w"], p["ffn/router/b"], c, pr,
                           flip)
    held = gates[:, lo:lo + int(c["n_routed_experts"])]
    y = ffn(m, p["ffn/shared/w_gate/w"], p["ffn/shared/w_up/w"],
            p["ffn/shared/w_down/w"], pr).astype(jnp.float32)
    for e in range(held.shape[1]):
        y = y + held[:, e:e + 1] * ffn(
            m, p["ffn/w_gate/w"][e], p["ffn/w_up/w"][e],
            p["ffn/w_down/w"][e], pr).astype(jnp.float32)
    return h + y.astype(pr.act), margins


# -- serving: logits over prompts with their served tokens ---------------------

def served_logits(c: Dict[str, Any], seed: int,
                  seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
                  reduce: Callable, precision: str = "f32",
                  row_block: int = 512,
                  flips: Sequence[Optional[Tuple[int, int, int]]] = None,
                  margins: List = None) -> None:
    """For each (prompt, served) pair, the model over prompt + served
    (without the last served token); the logits at the positions that
    predict the served tokens go to ``reduce(seq_index, first_row, n,
    logits[row_block, V] float32)`` in blocks of ``row_block`` rows (the
    first ``n`` real).  Layer by layer: one layer's weights at a time.
    Each sequence is padded at its end to a multiple of ``row_block``;
    under the causal mask the padding changes no real position.

    ``flips[i]`` = (layer, position, mode), where given, passes
    ``select``'s mode to that layer's router at that position of sequence
    i.  ``margins``, a list, receives (layer, sequence, (group margin,
    expert margin)) at every real position (numpy [T] each; infinite for
    a dense layer)."""
    pr = Precision(precision)
    dt = program_dtype(c)
    D, V = int(c["hidden_size"]), int(c["vocab_size"])
    toks = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in seqs]
    pad = [-(-len(t) // row_block) * row_block for t in toks]
    emb = pr.weight(W.plain_leaf(seed, "embed", (V, D), dt), True)
    hs = [jnp.take(emb, jnp.asarray(np.pad(t, (0, n - len(t)))), axis=0
                   ).astype(pr.act) for t, n in zip(toks, pad)]
    del emb
    steps = {kd: jax.jit(lambda p, h, pos, f, kd=kd: layer(
        p, h, pos, c, kd, pr, flip=f)) for kd in set(kinds(c))}
    for l, kd in enumerate(kinds(c)):
        p = layer_weights(c, seed, l, pr)
        out = []
        for i, (t, h) in enumerate(zip(toks, hs)):
            f = np.zeros(h.shape[0], np.int32)
            if flips is not None and flips[i] is not None \
                    and flips[i][0] == l:
                f[flips[i][1]] = flips[i][2]
            h, mg = steps[kd](p, h, jnp.arange(h.shape[0], dtype=jnp.int32),
                              jnp.asarray(f))
            out.append(h)
            if margins is not None:
                margins.append((l, len(out) - 1,
                                tuple(np.asarray(x)[:len(t)] for x in mg)))
        hs = out
        del p
    g = pr.weight(W.plain_leaf(seed, "final_norm/scale", (D,), dt), False)
    w_head = pr.weight(W.plain_leaf(seed, "head/w", (D, V), dt), True)
    head = jax.jit(lambda h, g, w: pr.mm(
        rms_norm(h, g, float(c["rms_norm_eps"])), w).astype(jnp.float32))
    for i, ((p, s), h) in enumerate(zip(seqs, hs)):
        lo = len(p) - 1                      # predicts served[0]
        for r in range(0, len(s), row_block):
            n = min(row_block, len(s) - r)
            rows = jax.lax.dynamic_slice_in_dim(
                jnp.pad(h, ((0, row_block), (0, 0))), lo + r, row_block)
            reduce(i, r, n, head(rows, g, w_head))


def logit_gaps(c, seed, seqs, tokens: Sequence[np.ndarray],
               precision: str = "f32", **kw) -> Tuple[List[np.ndarray],
                                                      List[np.ndarray]]:
    """Per sequence: how far the logit of ``tokens[i][j]`` lies below the
    best logit at that position, and the best token, under
    ``precision`` (``kw`` as ``served_logits`` takes them)."""
    gaps = [np.zeros(len(s), np.float64) for _, s in seqs]
    best = [np.zeros(len(s), np.int64) for _, s in seqs]
    fn = jax.jit(lambda lg, t: (jnp.max(lg, -1)
                                - jnp.take_along_axis(lg, t[:, None], -1)[:, 0],
                                jnp.argmax(lg, -1)))

    def reduce(i, r, n, logits):
        t = np.zeros(logits.shape[0], np.int32)
        t[:n] = tokens[i][r:r + n]
        g, b = fn(logits, jnp.asarray(t))
        gaps[i][r:r + n] = np.asarray(g)[:n]
        best[i][r:r + n] = np.asarray(b)[:n]

    served_logits(c, seed, seqs, reduce, precision, **kw)
    return gaps, best


def tie_judged_gaps(c, seed, seqs, tokens: Sequence[np.ndarray],
                    n: int = TIE_JUDGED) -> Tuple[List[np.ndarray],
                                                  List[np.ndarray],
                                                  List[Dict[str, Any]]]:
    """The float32 reference's gaps (``logit_gaps``), and the same with
    the ``n`` widest of them judged also across a router tie.

    The group-limited router is discontinuous: where the last group or
    expert kept and the best one left out nearly tie, the rounding of a
    lower-precision program can take the other side, move the token to
    other experts, and serve another token than the reference's best.
    Neither side of such a tie is wrong.  So each of the ``n`` widest
    tokens is judged again by the reference with its narrowest tie at
    that position (over the MoE layers, group or expert, ``select``'s
    margins) taken the other way, and keeps the smaller of its two gaps.
    Returns (gaps, judged gaps, one record a judged token)."""
    margins: List = []
    gaps, _ = logit_gaps(c, seed, seqs, tokens, margins=margins)
    flat = [(float(g), i, j) for i, gi in enumerate(gaps)
            for j, g in enumerate(gi) if g > 0]
    picks, sub, flips = [], [], []
    for g, i, j in sorted(flat, reverse=True)[:n]:
        pos = len(seqs[i][0]) - 1 + j
        m, l, mode = min((float(mg[mode - 1][pos]), l, mode)
                         for l, s, mg in margins if s == i
                         for mode in (1, 2))
        if not np.isfinite(m):                   # no MoE layer
            continue
        picks.append({"seq": i, "token": j, "position": pos, "gap": g,
                      "layer": l, "tie": ("group", "expert")[mode - 1],
                      "margin": m})
        sub.append((seqs[i][0], np.asarray(tokens[i][:j + 1])))
        flips.append((l, pos, mode))
    judged = [g.copy() for g in gaps]
    if picks:
        g2, _ = logit_gaps(c, seed, sub, [t for _, t in sub], flips=flips)
        for pk, gg in zip(picks, g2):
            pk["flipped_gap"] = float(gg[-1])
            judged[pk["seq"]][pk["token"]] = min(pk["gap"], pk["flipped_gap"])
    return gaps, judged, picks
