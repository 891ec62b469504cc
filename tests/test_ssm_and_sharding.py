"""Chunked-BPTT scan equivalence + serve/train sharding-policy invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models import ssm
from repro.models.registry import get_config
from repro.models import sharding as SH
from repro.launch.mesh import make_mesh


# ------------------------------------------------------- chunked scan -----


def _body(c, x):
    c = jnp.tanh(c * 0.9 + x)
    return c, c * 2.0


@pytest.mark.parametrize("T,chunk", [(16, 4), (17, 4), (4, 8), (1024, 256)])
def test_chunked_scan_matches_plain(T, chunk):
    xs = jnp.asarray(np.random.default_rng(0).normal(size=(T, 3)),
                     jnp.float32)
    c0 = jnp.zeros((3,), jnp.float32)
    c_ref, ys_ref = lax.scan(_body, c0, xs)
    c_got, ys_got = ssm.chunked_scan(_body, c0, xs, chunk=chunk)
    np.testing.assert_allclose(np.asarray(c_got), np.asarray(c_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ys_got), np.asarray(ys_ref),
                               rtol=1e-6, atol=1e-6)


def test_chunked_scan_grad_matches():
    xs = jnp.asarray(np.random.default_rng(1).normal(size=(512, 3)),
                     jnp.float32)
    c0 = jnp.zeros((3,), jnp.float32)

    def loss(fn, xs):
        _, ys = fn(_body, c0, xs)
        return jnp.sum(ys ** 2)

    g_ref = jax.grad(lambda x: loss(lax.scan, x))(xs)
    g_got = jax.grad(lambda x: loss(
        lambda b, c, x: ssm.chunked_scan(b, c, x, chunk=128), x))(xs)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)


def test_chunked_scan_tuple_carry_and_xs():
    T = 64
    xs = (jnp.ones((T, 2)), jnp.arange(T, dtype=jnp.float32))

    def body(c, x):
        a, b = x
        c = c + jnp.sum(a) + b
        return c, c

    ref = lax.scan(body, 0.0, xs)
    got = ssm.chunked_scan(body, 0.0, xs, chunk=16)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(ref[1]))


# ------------------------------------------------- sharding invariants ----


def _specs_for(arch, mode, mesh_shape=(4, 4), axes=("data", "model")):
    cfg = get_config(arch)
    # AbstractMesh: the policy only reads axis sizes — no devices needed
    mesh = jax.sharding.AbstractMesh(tuple(mesh_shape), tuple(axes))
    from repro.models import transformer as T
    pshape = jax.eval_shape(lambda k: T.init_params(cfg, k),
                            jax.random.key(0))
    return cfg, mesh, pshape, SH.param_specs(cfg, pshape, mesh, mode=mode)


@pytest.mark.parametrize("arch", ["deepseek-v3-671b", "qwen3-moe-235b-a22b",
                                  "jamba-v0.1-52b"])
def test_specs_divisibility(arch):
    """Every assigned axis must divide its dim (pjit would reject)."""
    for mode in ("train", "serve"):
        cfg, mesh, pshape, specs = _specs_for(arch, mode)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        flat_p = jax.tree.leaves(pshape)
        assert len(flat_s) == len(flat_p)
        for spec, leaf in zip(flat_s, flat_p):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                axes_t = (ax,) if isinstance(ax, str) else ax
                size = 1
                for a in axes_t:
                    size *= mesh.shape[a]
                assert dim % size == 0, (arch, mode, spec, leaf.shape)


def test_serve_mode_never_fsdp_shards_dense_weights():
    """Serving must not re-gather dense weights per token: no 'data' axis on
    non-expert tensors."""
    cfg, mesh, pshape, specs = _specs_for("deepseek-v3-671b", "serve")
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    for path, spec in flat:
        keys = [str(getattr(p, "key", "")) for p in path]
        is_expert = any(k in ("w_gate", "w_up", "w_down") for k in keys) \
            and "shared" not in keys and "segments" in keys \
            and "attn" not in keys
        axes_used = set()
        for ax in tuple(spec):
            if isinstance(ax, str):
                axes_used.add(ax)
            elif ax:
                axes_used.update(ax)
        if not is_expert and "mlp" not in keys:
            # dense/attention tensors: data axis must not appear
            if "data" in axes_used:
                # only experts may span the data axis in serve mode
                assert is_expert, (keys, spec)


def test_serve_mode_expert_sharding_covers_all_axes_when_divisible():
    cfg, mesh, pshape, specs = _specs_for("deepseek-v3-671b", "serve",
                                          (16, 16), ("data", "model"))
    # deepseek: 256 experts on 256 chips → full EP over both axes
    found = False
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    for path, spec in flat:
        keys = [str(getattr(p, "key", "")) for p in path]
        if "ffn" in keys and "w_gate" in keys and "shared" not in keys:
            first = tuple(spec)[1]   # [stack, E, D, F] → E axis entry
            if first and set(first if not isinstance(first, str)
                             else (first,)) == {"model", "data"}:
                found = True
    assert found


def test_cache_specs_batch1_unsharded():
    cfg = get_config("jamba-v0.1-52b")
    mesh = jax.sharding.AbstractMesh((4, 4), ("data", "model"))
    from repro.models import transformer as T
    cshape = jax.eval_shape(lambda: T.init_cache(cfg, 1, 256))
    specs = SH.cache_specs(cfg, cshape, mesh)
    for spec, leaf in zip(
            jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)),
            jax.tree.leaves(cshape)):
        entries = tuple(spec)
        if len(leaf.shape) >= 2 and leaf.shape[1] == 1:
            assert entries[1] is None     # batch-1 must not shard
