"""Architecture registry: ``--arch <id>`` → ArchConfig, plus param counting.

Each assigned architecture lives in ``src/repro/configs/<id>.py`` with the
exact published configuration; ``get_config(name)`` resolves either the full
config or its ``-smoke`` reduction.
"""

from __future__ import annotations

import importlib
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig

ARCH_IDS: List[str] = [
    "deepseek-v3-671b",
    "qwen3-moe-235b-a22b",
    "qwen2.5-3b",
    "granite-34b",
    "phi4-mini-3.8b",
    "gemma2-2b",
    "paligemma-3b",
    "musicgen-medium",
    "xlstm-1.3b",
    "jamba-v0.1-52b",
]


def _module_name(arch_id: str) -> str:
    return "repro.configs." + arch_id.replace("-", "_").replace(".", "_")


def get_config(name: str) -> ArchConfig:
    smoke = name.endswith("-smoke")
    base = name[:-len("-smoke")] if smoke else name
    if base not in ARCH_IDS:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_IDS}")
    cfg = importlib.import_module(_module_name(base)).CONFIG
    return cfg.reduced() if smoke else cfg


def all_configs() -> Dict[str, ArchConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


# ---------------------------------------------------------------------------
# parameter counting (roofline MODEL_FLOPS and memory budgets)
# ---------------------------------------------------------------------------


def param_shapes(cfg: ArchConfig):
    from repro.models.transformer import init_params
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.key(0))


def count_params(cfg: ArchConfig, active_only: bool = False) -> int:
    """Total (or per-token-active) parameter count.

    Active MoE params: routed expert weights count at top_k/num_experts;
    everything else (router, shared experts, attention, norms) is always on.
    """
    shapes = param_shapes(cfg)
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = 0
    for path, leaf in leaves:
        n = int(np.prod(leaf.shape))
        if active_only and cfg.moe is not None and _is_routed_expert(
                path, leaf, cfg):
            n = int(n * cfg.moe.top_k / cfg.moe.num_experts)
        total += n
    return total


def _is_routed_expert(path, leaf, cfg: ArchConfig) -> bool:
    keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
    if "ffn" not in keys or "shared" in keys or "router" in keys:
        return False
    # routed expert tensors carry the expert dim: [..., E, D, F]-shaped
    return any(s == cfg.moe.held for s in leaf.shape)


def embedding_params(cfg: ArchConfig) -> int:
    n = cfg.vocab_size * cfg.d_model
    if not cfg.tie_embeddings:
        n *= 2
    return n


def non_embedding_params(cfg: ArchConfig, active_only=False) -> int:
    return count_params(cfg, active_only) - embedding_params(cfg)
