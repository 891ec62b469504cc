"""The program's configuration objects, built from a configuration file.

The file holds the published config.json keys of the model as it is run;
this module maps them onto the program's ``ArchConfig`` (and, for
training, its optimizer and BSP settings).  It is the only place where
the benchmark translates the published names into the program's.
"""

from __future__ import annotations

from typing import Any, Dict


def plain_rope(c: Dict[str, Any]) -> float:
    """The RoPE base of a configuration whose rotary embedding both the
    program and the reference compute: over the whole head, unscaled.
    Any other is refused rather than run as plain RoPE."""
    if float(c.get("partial_rotary_factor", 1.0)) != 1.0:
        raise ValueError(f"{c['name']}: partial_rotary_factor "
                         f"{c['partial_rotary_factor']} is not mapped; only "
                         "a rotation of the whole head is")
    if c.get("rope_scaling") is not None:
        raise ValueError(f"{c['name']}: rope_scaling is not mapped")
    return float(c["rope_theta"])


def arch_config(c: Dict[str, Any]):
    from repro.configs.base import ArchConfig

    if c.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{c['name']}: only SwiGLU (silu) MLPs are mapped")
    heads = int(c["num_attention_heads"])
    return ArchConfig(
        name=c["name"],
        family="dense",
        num_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]),
        num_heads=heads,
        num_kv_heads=int(c["num_key_value_heads"]),
        d_ff=int(c["intermediate_size"]),
        vocab_size=int(c["vocab_size"]),
        head_dim=int(c.get("head_dim") or c["hidden_size"] // heads),
        qkv_bias=bool(c.get("qkv_bias", c.get("attention_bias", False))),
        mlp="swiglu",
        rope_theta=plain_rope(c),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        max_seq=int(c["max_position_embeddings"]),
        param_dtype=c["precision"]["params"],
        source=c["source"],
    )


def adamw_config(c: Dict[str, Any]):
    from repro.optim import adamw

    o = c["optimizer"]
    if o.get("grad_clip") is not None:
        raise ValueError("the BSP tier applies no gradient clipping; the "
                         "configuration must state grad_clip null")
    return adamw.AdamWConfig(
        lr=o["lr"], beta1=o["beta1"], beta2=o["beta2"], eps=o["eps"],
        weight_decay=o["weight_decay"], warmup_steps=o["warmup_steps"],
        total_steps=o["total_steps"], min_lr_ratio=o["min_lr_ratio"])


def bsp_config(c: Dict[str, Any]):
    from repro.core.bsp import BSPConfig

    b = c["bsp"]
    return BSPConfig(sync_axes=("data",), schedule=b["schedule"],
                     compression=b["compression"], bucket_mb=b["bucket_mb"])


def replace_sizes(c: Dict[str, Any], **sizes) -> Dict[str, Any]:
    """A copy of a configuration with some sizes changed (tests only use
    this, to run a cell's path at a size a CPU holds)."""
    out = dict(c)
    for k, v in sizes.items():
        if isinstance(v, dict):
            out[k] = dict(out.get(k, {}), **v)
        else:
            out[k] = v
    return out


def lr_at(o: Dict[str, Any], step: int) -> float:
    """Learning rate of step ``step`` (0-based): linear warm-up, then a
    cosine decay to ``min_lr_ratio`` of the peak."""
    import math

    warm = min(1.0, (step + 1) / max(o["warmup_steps"], 1))
    frac = min(1.0, max(0.0, (step - o["warmup_steps"])
                        / max(o["total_steps"] - o["warmup_steps"], 1)))
    cos = 0.5 * (1 + math.cos(math.pi * frac))
    decay = o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * cos
    return o["lr"] * warm * decay


__all__ = ["plain_rope", "arch_config", "adamw_config", "bsp_config",
           "replace_sizes", "lr_at"]
