"""Compile a cell's step programs for a described TPU v5e, with no chip.

  JAX_PLATFORMS=cpu PYTHONPATH=src python bench/tools/aot.py serve \
      --config phi4-mini-3.8b-serve [--kv-blocks 2048]
  JAX_PLATFORMS=cpu PYTHONPATH=src python bench/tools/aot.py train \
      --config qwen2.5-3b-bsp --chips 4 [--layers 4] [--batch-per-chip 2]

Prints each program's ``memory_analysis()`` (arguments, outputs, aliased
and temporary bytes) and whether a TPU custom call (a Pallas kernel) is in
it.  Nothing runs, so no time is measured; the compiler refuses what would
not fit or not lower on the chip.  Used to size ``kv_blocks``, the depth
and the per-chip batch before the first chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from bench.harness import model as M  # noqa: E402

GB = 1e9


def _mem(label, compiled):
    m = compiled.memory_analysis()
    text = compiled.as_text()
    row = {
        "program": label,
        "argument_gb": m.argument_size_in_bytes / GB,
        "output_gb": m.output_size_in_bytes / GB,
        "alias_gb": m.alias_size_in_bytes / GB,
        "temp_gb": m.temp_size_in_bytes / GB,
        "total_gb": (m.argument_size_in_bytes + m.output_size_in_bytes
                     - m.alias_size_in_bytes + m.temp_size_in_bytes) / GB,
        "tpu_custom_call": "tpu_custom_call" in text,
    }
    print(json.dumps(row), flush=True)
    return row


def _topology():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def serve(cfg_json, kv_blocks):
    import repro.kernels.paged_attention.ops as ops
    from repro.models import transformer as T

    ops.on_tpu = lambda: True        # lower the real kernel, not interpret
    topo = _topology()
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    cfg = M.arch_config(cfg_json)
    e = cfg_json["engine"]
    S, bs, max_len, C = (e["max_slots"], e["block_size"], e["max_len"],
                         e["prefill_chunk"])
    n = max_len // bs
    N = kv_blocks or e["kv_blocks"]

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    params = sds(jax.eval_shape(lambda: T.init_params(cfg,
                                                      jax.random.key(0))))
    cache = sds(jax.eval_shape(lambda: T.init_paged_cache(cfg, N, bs)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    decode = jax.jit(lambda p, tok, c, off, bt: T.decode_step(
        p, cfg, tok, c, off, block_tables=bt, paged_kernel="pallas"))
    rows = [_mem(f"decode S={S} N={N}", decode.lower(
        params, i32(S, 1), cache, i32(S), i32(S, n)).compile())]
    for with_logits in (True, False):
        admit = jax.jit(lambda p, c, tok, off, tb, wl=with_logits:
                        T.prefill_chunk(p, cfg, tok, c, off,
                                        with_logits=wl, block_tables=tb))
        rows.append(_mem(f"admit C={C} logits={with_logits}", admit.lower(
            params, cache, i32(1, C), i32(), i32(1, n)).compile()))
    pool_gb = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(cache)) / GB
    print(json.dumps({"kv_pool_gb": pool_gb, "kv_blocks": N}))
    return rows


def train(cfg_json, chips, layers, batch_per_chip, seq):
    from repro.models import transformer as T
    from repro.runtime import trainer

    topo = _topology()
    c = dict(cfg_json)
    if layers:
        c["num_hidden_layers"] = layers
    cfg = M.arch_config(c)
    devs = np.array(topo.devices[:chips]).reshape(chips, 1)
    mesh = Mesh(devs, ("data", "model"))
    step_fn, init_state = trainer.make_bsp_train_step(
        cfg, mesh, M.adamw_config(c), M.bsp_config(c))
    rep = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("data"))
    pshape = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=rep), pshape)
    mu, nu, ef, step = jax.eval_shape(lambda p: init_state(p)[1:], pshape)
    mu = jax.ShapeDtypeStruct(mu.shape, mu.dtype, sharding=shard)
    nu = jax.ShapeDtypeStruct(nu.shape, nu.dtype, sharding=shard)
    ef = jax.ShapeDtypeStruct(ef.shape, ef.dtype, sharding=rep)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    gb = batch_per_chip * chips
    bsh = NamedSharding(mesh, P("data", None))
    batch = {k: jax.ShapeDtypeStruct((gb, seq), jnp.int32, sharding=bsh)
             for k in ("tokens", "labels")}
    return [_mem(f"bsp chips={chips} layers={cfg.num_layers} "
                 f"batch={batch_per_chip}x{seq}",
                 step_fn.lower(params, mu, nu, ef, step, batch).compile())]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("path", choices=("serve", "train"))
    ap.add_argument("--config", required=True)
    ap.add_argument("--kv-blocks", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--batch-per-chip", type=int, default=2)
    ap.add_argument("--seq", type=int, default=2048)
    a = ap.parse_args(argv)
    with open(BENCH / "configs" / f"{a.config}.json") as f:
        cfg_json = json.load(f)
    if a.path == "serve":
        serve(cfg_json, a.kv_blocks)
    else:
        train(cfg_json, a.chips, a.layers, a.batch_per_chip, a.seq)


if __name__ == "__main__":
    main()
