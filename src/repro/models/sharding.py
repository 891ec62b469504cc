"""Parameter / activation / cache sharding policy (GSPMD PartitionSpecs).

Mesh axes: ``("data","model")`` single-pod, ``("pod","data","model")``
multi-pod.  Policy (MaxText-style FSDP × TP):

  * FSDP axes = ("pod","data"): parameters + optimizer moments sharded on the
    d_model-ish dimension (ZeRO-3; XLA all-gathers per scanned layer).
  * TP axis = "model": attention heads / MoE experts / d_ff / vocab.
  * Guards: a dim only gets an axis if divisible by the axis product AND, for
    head-structured projections, if the head count itself divides the axis —
    otherwise that axis is dropped (e.g. gemma2's 8 heads on a 16-way model
    axis: attention stays fsdp-only; recorded as a roofline hillclimb lever).
  * Decode caches shard batch over FSDP axes and sequence over "model"
    (a 500k-token KV/state must live across the pod).

The policy is data (name-pattern rules), so hillclimb variants can override
single rules without touching model code (see launch/dryrun.py --opt).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    axes = (axes,) if isinstance(axes, str) else axes
    return math.prod(mesh.shape[a] for a in axes)


def _fits(dim: int, mesh: Mesh, axes) -> bool:
    return dim % axis_size(mesh, axes) == 0


def _path_keys(path) -> list:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(int(p.idx))
        elif hasattr(p, "name"):
            out.append(str(p.name))
    return out


# name of the projection component (parent of the "w"/"b" leaf)
_COL_PARALLEL = {"wq", "w_gate", "w_up", "up_proj", "in_proj", "q_up",
                 "kv_up", "ffn_up", "w_if"}          # [D_in, D_out·TP]
_ROW_PARALLEL = {"wo", "w_down", "out_proj", "down_proj", "ffn_down",
                 "dt_proj"}                          # [D_in·TP, D_out]
_KV_PROJ = {"wk", "wv"}
_REPLICATED = {"q_norm", "k_norm", "norm", "norm1", "norm2", "post1", "post2",
               "out_norm", "group_norm", "final_norm", "kv_norm",
               "frontend_proj", "proj", "skip", "r_gates"}


def _leaf_spec(cfg: ArchConfig, keys: list, shape: Tuple[int, ...],
               mesh: Mesh, mode: str = "train") -> P:
    """mode="train": FSDP×TP (ZeRO-3: per-layer weight gathers amortize over
    fwd+bwd).  mode="serve": weights must not move per token — dense weights
    TP-only (replicated over the data axes), MoE experts sharded over ALL
    axes (full EP: tokens travel, weights stay)."""
    fsdp = fsdp_axes(mesh) if mode == "train" else ()
    ep_axes = ("model",) + fsdp_axes(mesh) if mode == "serve" else ("model",)
    has_model = "model" in mesh.shape
    stacked = "segments" in keys          # lax.scan leading repeat dim
    nd = len(shape)
    lead = (None,) if stacked else ()
    body = shape[1:] if stacked else shape

    def spec(*axes):
        axes = tuple(a if (a and _fits(body[i], mesh, a)) else None
                     for i, a in enumerate(axes))
        return P(*(lead + axes))

    # ---- top-level tensors -------------------------------------------------
    if keys[:1] == ["embed"]:
        return spec("model", fsdp)
    if "head" in keys:
        return spec(fsdp, "model")
    if keys and keys[0] == "mtp" and "block" not in keys:
        return P(*((None,) * nd))

    name = next((k for k in reversed(keys)
                 if isinstance(k, str) and k not in ("w", "b")), "")

    if name in _REPLICATED or not has_model and not fsdp:
        return P(*((None,) * nd))

    # ---- MoE expert tensors [E, D, F] / [E, F, D]: EP over "model"
    # (train) or over every axis (serve: 1-expert-per-chip at 256 chips) ----
    if name in ("w_gate", "w_up", "w_down") and len(body) == 3 \
            and cfg.moe and body[0] == cfg.moe.held:
        if mode == "serve":
            if body[0] % axis_size(mesh, ep_axes) == 0:
                return spec(ep_axes, None, None)   # full EP: 1 expert/chip
            # E doesn't cover every axis (e.g. qwen3's 128e on 256 chips):
            # E over "model" + intra-expert TP over the data axes (weights
            # still pinned; activations move instead)
            ftp = fsdp_axes(mesh)
            if name == "w_down":
                return spec("model", ftp, None)
            return spec("model", None, ftp)
        if name == "w_down":
            return spec("model", None, fsdp)
        return spec("model", fsdp, None)

    leaf = keys[-1] if keys else ""
    if leaf == "b":                      # bias of a projection
        if name in _COL_PARALLEL and _head_ok(cfg, name, mesh):
            return spec("model")
        return P(*((None,) * nd))

    if len(body) == 1:                   # 1-D vectors (A, D, conv_b, scale)
        if name in ("A_log", "D", "conv_b") or leaf in ("D",):
            return spec("model") if len(body) == 1 else P(None)
        return P(*((None,) * nd))

    if name == "conv_w":                 # [K, C]
        return spec(None, "model")
    if name in ("A_log",):               # [d_in, N]
        return spec("model", None)
    if name == "x_proj":                 # [d_in, dt+2N]: row-parallel-ish
        return spec("model", None)
    if name in ("wq", "wk", "wv") and len(body) == 3:
        return P(*((None,) * nd))        # xLSTM headwise cells: replicate

    if name in _COL_PARALLEL:
        model = "model" if _head_ok(cfg, name, mesh) else None
        return spec(fsdp, model)
    if name in _KV_PROJ:
        model = "model" if cfg.num_kv_heads % axis_size(mesh, "model") == 0 \
            else None
        return spec(fsdp, model)
    if name in _ROW_PARALLEL:
        model = "model" if _head_ok(cfg, name, mesh) else None
        return spec(model, fsdp)
    if name in ("router", "q_down", "kv_down"):
        return spec(fsdp, None)
    # default: replicate
    return P(*((None,) * nd))


def _head_ok(cfg: ArchConfig, name: str, mesh: Mesh) -> bool:
    """Head-structured projections need heads % TP == 0 to stay head-aligned."""
    tp = axis_size(mesh, "model")
    if name in ("wq", "wo"):
        return cfg.num_heads % tp == 0
    return True


def param_specs(cfg: ArchConfig, params_shape, mesh: Mesh,
                mode: str = "train"):
    """PartitionSpec pytree mirroring the parameter pytree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = []
    for path, leaf in flat:
        keys = _path_keys(path)
        specs.append(_leaf_spec(cfg, keys, tuple(leaf.shape), mesh, mode))
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# batch / cache / logits specs
# ---------------------------------------------------------------------------


def batch_spec(mesh: Mesh) -> Dict[str, P]:
    dp = fsdp_axes(mesh)
    return {"tokens": P(dp, None), "labels": P(dp, None),
            "frontend": P(dp, None, None)}


def cache_specs(cfg: ArchConfig, cache_shape, mesh: Mesh):
    """Decode caches: batch over FSDP, sequence (or largest state dim) over
    "model" when divisible."""
    dp = fsdp_axes(mesh)

    def leaf(path, x):
        keys = _path_keys(path)
        name = next((k for k in reversed(keys) if isinstance(k, str)), "")
        nd = len(x.shape)
        stacked = 1  # caches are stacked per segment repeat: [R, B, ...]
        base = [None] * nd
        if nd >= 2 and x.shape[1] % axis_size(mesh, dp) == 0:
            base[1] = dp            # batch dim (long_500k has batch 1)
        if name in ("k", "v", "c_kv", "k_rope") and nd >= 3 \
                and x.shape[2] % axis_size(mesh, "model") == 0:
            base[2] = "model"       # sequence dim of KV caches
        elif name in ("h",) and nd >= 3 \
                and x.shape[2] % axis_size(mesh, "model") == 0:
            base[2] = "model"       # mamba state d_in
        elif name == "conv" and nd >= 4 - 0 and \
                x.shape[-1] % axis_size(mesh, "model") == 0:
            base[-1] = "model"
        return P(*base)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(
        treedef, [leaf(p, x) for p, x in flat])


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
