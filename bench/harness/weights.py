"""Weights drawn from the seed, the same for the program and the reference.

Every leaf is named by its path in the program's parameter tree, e.g.
``segments/0/l0/attn/wq/w``.  Its values come from a key folded from the
seed and a hash of that name, and a leaf stacked over layers (leading dim
= layer count, under ``segments/``) draws layer ``l`` from that key folded
with ``l``.  So the program's whole tree is made in one jitted call on the
device, and the reference draws any single layer again, from the seed
alone, without reading what the program holds.

Values, by the last part of the name:

  embed        N(0, 0.02^2)
  w            N(0, 1 / fan_in)            fan_in = second-to-last dim
  b            N(0, 0.02^2)
  scale        1 + N(0, 0.05^2)

drawn in float32 and rounded to the dtype the program keeps.
"""

from __future__ import annotations

import zlib
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _name(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _draw(key, name: str, shape: Tuple[int, ...]) -> jax.Array:
    last = name.rsplit("/", 1)[-1]
    if last == "scale":
        return 1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)
    if last == "embed" or last == "b":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if last == "w":
        return jax.random.normal(key, shape, jnp.float32) \
            * (1.0 / float(shape[-2]) ** 0.5)
    raise ValueError(f"no rule for parameter {name!r}")


def seed_parts(seed) -> Tuple[Any, Any]:
    """A seed (any whole number up to 2**62) as two int32 values, the low
    31 bits and the rest.  Passed to a jitted function as arguments, they
    keep the seed out of the compiled program, so one program (and one
    entry of the compilation cache) serves every seed."""
    if isinstance(seed, tuple):
        return seed
    return (np.int32(seed & 0x7FFFFFFF), np.int32((seed >> 31) & 0x7FFFFFFF))


def leaf_key(seed, name: str):
    lo, hi = seed_parts(seed)
    base = jax.random.fold_in(jax.random.key(lo), hi)
    return jax.random.fold_in(base, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def layer_leaf(seed, name: str, layer: int, shape: Sequence[int],
               dtype) -> jax.Array:
    """Layer ``layer`` of the stacked leaf ``name`` (``shape`` without the
    layer dim), as the program holds it (``dtype``)."""
    k = jax.random.fold_in(leaf_key(seed, name), layer)
    return _draw(k, name, tuple(shape)).astype(dtype)


def plain_leaf(seed, name: str, shape: Sequence[int], dtype) -> jax.Array:
    return _draw(leaf_key(seed, name), name, tuple(shape)).astype(dtype)


def draw_leaf(seed, name: str, sd) -> jax.Array:
    if name.startswith("segments/"):
        n_layers = sd.shape[0]
        per = jax.vmap(lambda l: layer_leaf(seed, name, l, sd.shape[1:],
                                            sd.dtype))
        return per(jnp.arange(n_layers))
    return plain_leaf(seed, name, sd.shape, sd.dtype)


def tree_like(shapes: Any, seed) -> Any:
    """A tree shaped like ``shapes`` (ShapeDtypeStructs), drawn from
    ``seed`` (traceable: call it inside a jit)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    return jax.tree_util.tree_unflatten(
        treedef, [draw_leaf(seed, _name(p), sd) for p, sd in flat])


def make_params(init_shapes: Any, seed: int, out_shardings=None) -> Any:
    """The program's parameters, made on the device in one jitted call."""
    fn = jax.jit(lambda s: tree_like(init_shapes, s),
                 out_shardings=out_shardings)
    return fn(seed_parts(seed))


def names(shapes: Any) -> Dict[str, Any]:
    """Leaf name → ShapeDtypeStruct."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return {_name(p): sd for p, sd in flat}


__all__ = ["tree_like", "make_params", "layer_leaf", "plain_leaf", "names",
           "leaf_key", "seed_parts", "draw_leaf"]
