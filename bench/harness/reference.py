"""Plain references: a dense GQA decoder in straightforward jax.numpy,
written from the published equations and the configuration file alone.

It imports nothing of the program.  Its weights come from the seed
through ``weights`` (the same draw the program was given), one layer at a
time, so the reference never reads what the program holds.

Equations (pre-norm decoder, Llama/Phi-3/Qwen2 family):

  h0 = E[x]
  per layer:  a = RMSNorm(h; g1);  q, k, v = a Wq (+bq), a Wk (+bk), a Wv (+bv)
              q, k = RoPE(q), RoPE(k)          (theta from the file, whole head)
              o = softmax(q k^T / sqrt(dh) + causal) v     (GQA groups)
              h = h + o Wo
              m = RMSNorm(h; g2);  h = h + (silu(m Wg) * (m Wu)) Wd
  logits = RMSNorm(h; gf) E^T                   (tied embeddings)

``precision`` selects how it computes: ``f32`` (float32 weights and
activations, matmuls at HIGHEST: the reference), ``bf16`` (weights,
activations and stored state in bfloat16) and ``fp8`` (both inputs of
each matmul rounded to float8 e4m3, with a scale per row of the left and
per column of the right, everything else float32).  The last two are
the controls.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import flops as F
from . import weights as W
from .model import lr_at, plain_rope

SEG = "segments/0/l0/"


def leaf_shapes(c: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """Name → shape of every parameter, stacked leaves with the layer dim
    first, under the program's names (``weights`` draws them by name)."""
    m = F.Dense.of(c)
    L, D, H, K, Dh, Fd = (m.layers, m.d, m.heads, m.kv_heads, m.head_dim,
                          m.d_ff)
    s = {"embed": (m.vocab, D), "final_norm/scale": (D,),
         SEG + "norm1/scale": (L, D), SEG + "norm2/scale": (L, D),
         SEG + "attn/wq/w": (L, D, H * Dh), SEG + "attn/wk/w": (L, D, K * Dh),
         SEG + "attn/wv/w": (L, D, K * Dh), SEG + "attn/wo/w": (L, H * Dh, D),
         SEG + "ffn/w_gate/w": (L, D, Fd), SEG + "ffn/w_up/w": (L, D, Fd),
         SEG + "ffn/w_down/w": (L, Fd, D)}
    if m.qkv_bias:
        s[SEG + "attn/wq/b"] = (L, H * Dh)
        s[SEG + "attn/wk/b"] = (L, K * Dh)
        s[SEG + "attn/wv/b"] = (L, K * Dh)
    return s


def program_dtype(c: Dict[str, Any]):
    return jnp.dtype(c["precision"]["params"])


# -- precision --------------------------------------------------------------

def _fp8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis`` (absmax
    to the format's largest normal, 448)."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


class Precision:
    def __init__(self, name: str):
        if name not in ("f32", "bf16", "fp8"):
            raise ValueError(f"unknown precision {name!r}")
        self.name = name
        self.act = jnp.bfloat16 if name == "bf16" else jnp.float32
        self.hp = (jax.lax.Precision.HIGHEST if name in ("f32", "fp8")
                   else jax.lax.Precision.DEFAULT)

    def weight(self, w, is_matrix: bool):
        """The weight as kept: bfloat16 under ``bf16``, else float32 (fp8
        rounds a matrix where it enters a product, so that an update in
        training is kept in full)."""
        if self.name == "bf16":
            return w.astype(jnp.bfloat16)
        return w.astype(jnp.float32)

    def mm(self, x, w):
        """x [..., k] @ w [k, n]."""
        if self.name == "fp8":
            x = _fp8(x, axis=-1)             # one scale per row
            w = _fp8(w, axis=-2)             # one scale per output column
        y = jnp.matmul(x.astype(self.act), w, precision=self.hp,
                       preferred_element_type=jnp.float32)
        return y.astype(self.act)


# -- the model ----------------------------------------------------------------

def rms_norm(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, theta):
    """x [T, H, dh], pos [T]: rotate pairs (i, i + dh/2) by pos * f_i."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def layer(p: Dict[str, Any], h, pos, c: Dict[str, Any], pr: Precision,
          q_block: int = 1024):
    """One decoder layer over one sequence h [T, D] at positions pos [T].
    ``p`` holds the layer's leaves under their short names (``wq``, ...).
    Queries are processed in blocks of ``q_block`` rows."""
    m = F.Dense.of(c)
    T = h.shape[0]
    H, K, Dh = m.heads, m.kv_heads, m.head_dim
    G = H // K
    eps = float(c["rms_norm_eps"])
    a = rms_norm(h, p["norm1"], eps)

    def proj(name, width):
        y = pr.mm(a, p["w" + name])
        if ("b" + name) in p:
            y = (y.astype(jnp.float32) + p["b" + name].astype(jnp.float32)
                 ).astype(pr.act)
        return y.reshape(T, width, Dh)

    theta = plain_rope(c)
    q = rope(proj("q", H), pos, theta)
    k = rope(proj("k", K), pos, theta)
    v = proj("v", K)
    scale = 1.0 / math.sqrt(Dh)
    outs = []
    for s in range(0, T, q_block):
        qb = q[s:s + q_block].reshape(-1, K, G, Dh)
        sc = jnp.einsum("qkgd,tkd->kgqt", qb.astype(jnp.float32),
                        k.astype(jnp.float32), precision=pr.hp) * scale
        allowed = pos[None, :] <= pos[s:s + q_block, None]
        sc = jnp.where(allowed[None, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        ob = jnp.einsum("kgqt,tkd->qkgd", w.astype(pr.act), v,
                        precision=pr.hp,
                        preferred_element_type=jnp.float32)
        outs.append(ob.reshape(-1, H * Dh).astype(pr.act))
    o = jnp.concatenate(outs, 0)
    h = h + pr.mm(o, p["wo"])
    mm = rms_norm(h, p["norm2"], eps)
    gate = pr.mm(mm, p["w_gate"]).astype(jnp.float32)
    up = pr.mm(mm, p["w_up"]).astype(jnp.float32)
    return h + pr.mm((jax.nn.silu(gate) * up).astype(pr.act), p["w_down"])


SHORT = {"norm1/scale": "norm1", "norm2/scale": "norm2",
         "attn/wq/w": "wq", "attn/wk/w": "wk", "attn/wv/w": "wv",
         "attn/wo/w": "wo", "attn/wq/b": "bq", "attn/wk/b": "bk",
         "attn/wv/b": "bv", "ffn/w_gate/w": "w_gate", "ffn/w_up/w": "w_up",
         "ffn/w_down/w": "w_down"}


def _layer_weights(c, seed, l, pr: Precision):
    shapes = leaf_shapes(c)
    dt = program_dtype(c)
    out = {}
    for name, shape in shapes.items():
        if not name.startswith(SEG):
            continue
        w = W.layer_leaf(seed, name, l, shape[1:], dt)
        out[SHORT[name[len(SEG):]]] = pr.weight(w, name.endswith("/w"))
    return out


# -- serving: logits over prompts with their served tokens ---------------------

def served_logits(c: Dict[str, Any], seed: int,
                  seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
                  reduce: Callable, precision: str = "f32",
                  row_block: int = 512) -> List[Any]:
    """For each (prompt, served) pair, run the model over prompt + served
    (without the last served token) and hand the logits at the positions
    that predict the served tokens to ``reduce(seq_index, first_row, n,
    logits[row_block, V] float32)`` (the first ``n`` rows are real), in
    blocks of ``row_block`` rows.  Layer by
    layer: only one layer's weights are on the device at a time.

    Each sequence is padded at its end to a multiple of ``row_block``
    positions; under the causal mask the padding changes no real
    position, and the few padded lengths compile once and are cached."""
    pr = Precision(precision)
    dt = program_dtype(c)
    toks = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in seqs]
    pad = [-(-len(t) // row_block) * row_block for t in toks]
    emb = pr.weight(W.plain_leaf(seed, "embed", leaf_shapes(c)["embed"], dt),
                    True)
    hs = [jnp.take(emb, jnp.asarray(np.pad(t, (0, n - len(t)))), axis=0
                   ).astype(pr.act) for t, n in zip(toks, pad)]
    step = jax.jit(lambda p, h, pos: layer(p, h, pos, c, pr))
    for l in range(int(c["num_hidden_layers"])):
        p = _layer_weights(c, seed, l, pr)
        hs = [step(p, h, jnp.arange(h.shape[0], dtype=jnp.int32))
              for h in hs]
        del p
    g = pr.weight(W.plain_leaf(seed, "final_norm/scale",
                               leaf_shapes(c)["final_norm/scale"], dt), False)
    head = jax.jit(lambda h, g, e: pr.mm(
        rms_norm(h, g, float(c["rms_norm_eps"])), e.T).astype(jnp.float32))
    out = []
    for i, ((p, s), h) in enumerate(zip(seqs, hs)):
        lo = len(p) - 1                      # predicts served[0]
        for r in range(0, len(s), row_block):
            n = min(row_block, len(s) - r)
            rows = jax.lax.dynamic_slice_in_dim(
                jnp.pad(h, ((0, row_block), (0, 0))), lo + r, row_block)
            out.append(reduce(i, r, n, head(rows, g, emb)))
    return out


def logit_gaps(c, seed, seqs, tokens: Sequence[np.ndarray],
               precision: str = "f32") -> Tuple[List[np.ndarray],
                                                List[np.ndarray]]:
    """Per sequence: how far the logit of ``tokens[i][j]`` lies below the
    best logit at that position, and the best token, under
    ``precision``."""
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

    served_logits(c, seed, seqs, reduce, precision)
    return gaps, best


# -- training: loss, gradient and AdamW of the same global batch ---------------

def init_params(c: Dict[str, Any], seed: int, pr: Precision,
                device=None) -> Dict[str, Any]:
    dt = program_dtype(c)
    shapes = leaf_shapes(c)

    def make(s):
        out = {}
        for name, shape in shapes.items():
            if name.startswith(SEG):
                w = jax.vmap(lambda l: W.layer_leaf(
                    s, name, l, shape[1:], dt))(jnp.arange(shape[0]))
            else:
                w = W.plain_leaf(s, name, shape, dt)
            out[name] = pr.weight(w, name.endswith("/w"))
        return out

    sh = jax.sharding.SingleDeviceSharding(device) if device else None
    return jax.jit(make, out_shardings=sh)(W.seed_parts(seed))


def loss_sum(params, tokens, labels, c, pr: Precision, head_rows=512):
    """Sum of next-token cross-entropy over the rows of one micro-batch."""
    B, T = tokens.shape
    eps = float(c["rms_norm_eps"])
    stack = {SHORT[n[len(SEG):]]: v for n, v in params.items()
             if n.startswith(SEG)}
    emb = params["embed"]
    pos = jnp.arange(T, dtype=jnp.int32)

    @jax.checkpoint
    def body(h, p):
        return jax.vmap(lambda hb: layer(p, hb, pos, c, pr))(h), None

    h = jnp.take(emb, tokens, axis=0).astype(pr.act)
    h, _ = jax.lax.scan(body, h, stack)
    h = rms_norm(h, params["final_norm/scale"], eps)
    head_rows = min(head_rows, B * T)
    hr = h.reshape(-1, head_rows, h.shape[-1])
    lr_ = labels.reshape(-1, head_rows)

    @jax.checkpoint
    def xent(carry, xs):
        hb, lb = xs
        lg = pr.mm(hb, emb.T).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, lb[:, None], -1)[:, 0]
        return carry + jnp.sum(lse - gold), None

    tot, _ = jax.lax.scan(xent, jnp.zeros((), jnp.float32), (hr, lr_))
    return tot


def train(c: Dict[str, Any], seed: int, batches: Sequence[Dict[str, np.ndarray]],
          precision: str = "f32", micro_rows: int = 1, device=None,
          devices: Sequence[Any] = ()) -> Dict[str, Any]:
    """AdamW on the configuration's optimizer settings over ``batches``
    (one global batch per step).  Returns each step's mean loss, the norm
    of each leaf's first gradient, of its change after the last step, and
    of its value before the first.

    The rows of a batch are summed in blocks of ``micro_rows``; with
    ``devices``, the blocks are spread over them (each holds a copy of the
    parameters) and their gradients summed on the first, which holds the
    optimizer state."""
    pr = Precision(precision)
    o = c["optimizer"]
    b1, b2, eps_, wd = o["beta1"], o["beta2"], o["eps"], o["weight_decay"]
    state_dt = pr.act
    devs = list(devices) or [device or jax.devices()[0]]
    dev = devs[0]
    params = init_params(c, seed, pr, dev)
    p0_norm = {k: float(jnp.linalg.norm(v.astype(jnp.float32)))
               for k, v in params.items()}
    grad_mb = jax.jit(jax.value_and_grad(
        lambda p, t, y: loss_sum(p, t, y, c, pr)))
    acc = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x + y.astype(jnp.float32), a, b))

    @jax.jit
    def adam(p, g, m, v, t, lr):
        m = b1 * m.astype(jnp.float32) + (1 - b1) * g
        v = b2 * v.astype(jnp.float32) + (1 - b2) * g * g
        mh = m / (1 - b1 ** t)
        vh = v / (1 - b2 ** t)
        p32 = p.astype(jnp.float32)
        new = p32 - lr * (mh / (jnp.sqrt(vh) + eps_) + wd * p32)
        return new.astype(p.dtype), m.astype(state_dt), v.astype(state_dt)

    m = {k: jnp.zeros(v.shape, state_dt, device=dev)
         for k, v in params.items()}
    v2 = {k: jnp.zeros(v.shape, state_dt, device=dev)
          for k, v in params.items()}
    losses, g0 = [], None
    for step, batch in enumerate(batches):
        copies = [params] + [jax.device_put(params, d) for d in devs[1:]]
        n_tok = batch["tokens"].size
        part = [None] * len(devs)
        loss_parts = []
        for i, r in enumerate(range(0, batch["tokens"].shape[0], micro_rows)):
            k = i % len(devs)
            tok = jax.device_put(batch["tokens"][r:r + micro_rows], devs[k])
            lab = jax.device_put(batch["labels"][r:r + micro_rows], devs[k])
            l_mb, g_mb = grad_mb(copies[k], tok, lab)
            loss_parts.append(l_mb)
            part[k] = jax.tree.map(lambda x: x.astype(jnp.float32), g_mb) \
                if part[k] is None else acc(part[k], g_mb)
            del g_mb
        del copies
        g = None
        for pk in part:
            if pk is None:
                continue
            pk = jax.device_put(pk, dev)
            g = pk if g is None else acc(g, pk)
        del part
        g = jax.tree.map(lambda x: x / n_tok, g)
        losses.append(sum(float(x) for x in loss_parts) / n_tok)
        if g0 is None:
            g0 = {k: float(jnp.linalg.norm(x)) for k, x in g.items()}
        lr = lr_at(o, step)
        for k in params:
            params[k], m[k], v2[k] = adam(params[k], g[k], m[k], v2[k],
                                          float(step + 1), lr)
        del g
    del m, v2
    p0 = init_params(c, seed, pr, dev)       # drawn again, not kept
    delta = {k: float(jnp.linalg.norm(params[k].astype(jnp.float32)
                                      - p0[k].astype(jnp.float32)))
             for k in params}
    return {"losses": losses, "grad_norms": g0, "delta_norms": delta,
            "param_norms": p0_norm}
