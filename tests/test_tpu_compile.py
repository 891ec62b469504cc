"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Nothing runs: each case lowers the kernel with ``interpret=False`` for a
described (not attached) v5e chip and compiles it with the TPU compiler,
which refuses misaligned block shapes, kernels that overrun VMEM and other
things interpret mode never sees.  Shapes are the real widths of the
configurations the serve and train paths run.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.ops import (paged_attention,
                                               paged_mla_attention)
from repro.kernels.tree_reduce.ops import (coded_tree_reduce, decode_add,
                                           tree_reduce)
from repro.optim.compression import CODECS

# qwen2.5-3b decode: 16 query heads over 2 kv heads of width 128, 8 slots
# of 2048 positions in 16-position blocks
B, BS, N_BLK = 8, 16, 2048 // 16
# a ~4M-element gradient bucket, deliberately not a multiple of either the
# bf16 block (512) or the int8 row tile (32 codec blocks of 128)
M = 128 * 31_250


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one, so keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# one HLO instruction: its name, the dims of the array it produces, opcode
_HLO_OP = re.compile(r"%(\S+) = \w+\[([\d,]+)\]\S* (\S+?)\(")


def _pool_shapes(n_pool_blocks, *tails, dtype=jnp.bfloat16):
    return [((n_pool_blocks, BS) + t, dtype) for t in tails]


def test_paged_gqa_qwen25_3b(one_chip):
    hq, hkv, d = 16, 2, 128
    pool = 1 + B * N_BLK
    text = _compiled_text(
        lambda q, k, v, t, o: paged_attention(q, k, v, t, o,
                                              interpret=False),
        one_chip, ((B, 1, hq, d), jnp.bfloat16),
        *_pool_shapes(pool, (hkv, d), (hkv, d)),
        ((B, N_BLK), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_gqa_phi4_mini_reads_pool_in_place(one_chip):
    """phi4-mini decode: 32 slots, 24 query heads over 8 kv heads of width
    128, a 1,920-block pool, 256-entry tables.  The kernel reads the pools
    as they lie: no op outside it produces a pool-sized array (the
    [N, 16, 1024] relayout the kernel once needed)."""
    b, hq, hkv, d, pool, n = 32, 24, 8, 128, 1920, 256
    text = _compiled_text(
        lambda q, k, v, t, o: paged_attention(q, k, v, t, o,
                                              interpret=False),
        one_chip, ((b, 1, hq, d), jnp.bfloat16),
        *_pool_shapes(pool, (hkv, d), (hkv, d)),
        ((b, n), jnp.int32), ((b,), jnp.int32))
    assert "tpu_custom_call" in text
    pool_elems = pool * BS * hkv * d
    made = [(m.group(1), m.group(3)) for m in _HLO_OP.finditer(text)
            if math.prod(int(x) for x in m.group(2).split(",")) == pool_elems]
    assert made and all(op == "parameter" for _, op in made), made


def test_paged_mla_deepseek_v3(one_chip):
    """DeepSeek-V3 decode at one chip's share: 64 slots, 128 heads over
    the 512-wide latent and 64-wide rotary key, a 32,769-block pool,
    512-entry tables, the pools shaped as the model caches them.  The
    kernel reads them in place: only parameters are pool-sized (no copy
    or relayout of the rotary-key pool before the custom call)."""
    from repro.models import transformer as T
    from repro.models.registry import get_config

    cfg = get_config("deepseek-v3-671b")
    b, h, r, dr, pool, n = 64, 128, 512, 64, 32_769, 512
    cache = jax.eval_shape(lambda: T.init_paged_cache(cfg, pool, BS))
    leaf = cache[0]["l0"]
    ckv, kr = leaf["c_kv"].shape[1:], leaf["k_rope"].shape[1:]
    text = _compiled_text(
        lambda qe, qr, ckv, kr, t, o: paged_mla_attention(
            qe, qr, ckv, kr, t, o, scale=0.07, interpret=False),
        one_chip, ((b, 1, h, r), jnp.bfloat16), ((b, 1, h, dr), jnp.bfloat16),
        (ckv, jnp.bfloat16), (kr, jnp.bfloat16),
        ((b, n), jnp.int32), ((b,), jnp.int32))
    assert "tpu_custom_call" in text
    sizes = {math.prod(ckv), math.prod(kr)}
    made = [(m.group(1), m.group(3)) for m in _HLO_OP.finditer(text)
            if math.prod(int(x) for x in m.group(2).split(",")) in sizes]
    assert len(made) == 2 and all(op == "parameter" for _, op in made), made


def test_tree_reduce(one_chip):
    text = _compiled_text(lambda x: tree_reduce(x, interpret=False),
                          one_chip, ((4, M), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("codec", ["bf16", "int8"])
def test_decode_add(one_chip, codec):
    c = CODECS[codec]

    def fn(keep, send):
        return decode_add(keep, c.encode(send), c, interpret=False)

    text = _compiled_text(fn, one_chip, ((M,), jnp.float32),
                          ((M,), jnp.float32))
    assert "tpu_custom_call" in text


def test_int8_coded_tree_reduce(one_chip):
    nb = M // 128
    text = _compiled_text(
        lambda q, s: coded_tree_reduce({"q": q, "scale": s}, "int8",
                                       interpret=False),
        one_chip, ((4, nb, 128), jnp.int8), ((4, nb, 1), jnp.float32))
    assert "tpu_custom_call" in text
