"""The serve programs update the cache in place.

Each engine program that takes the cache (decode, prefill, reset, block
copy) donates it, and the layer scan carries each segment's stacked cache
and writes only the rows a step produces into layer ``i`` of it.  What
must hold:

  * the previous cache's buffers are consumed by every call, and
    ``ServeMetrics`` counts it (``cache_donated_share`` 1.0);
  * a step changes nothing in the stacked pool but the rows it writes,
    and a masked or padded write lands on its own layer's sentinel block;
  * every cache kind — paged and contiguous GQA with a sliding window,
    MLA + MoE, hybrid mamba rows, xLSTM rows — still serves the wave
    oracle's tokens when a segment stacks more than one repeat (the smoke
    configurations stack one, so these double their layer pattern);
  * the engine on a 2-device data mesh (replicated pools, as
    ``launch/serve.py`` builds it) serves the single-device tokens.
"""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer as T
from repro.models.layers import PAGED_SENTINEL, paged_scatter
from repro.models.registry import get_config
from repro.serve import (EngineConfig, Request, ServeEngine, ServeMetrics,
                         serve_waves)

ROOT = Path(__file__).resolve().parents[1]


def _stacked(arch, reps=2):
    """``arch`` with its layer pattern repeated: each segment stacks
    ``reps`` repeats of its unit, so the scan visits layers i > 0."""
    cfg = get_config(arch)
    return dataclasses.replace(cfg, num_layers=cfg.num_layers * reps,
                               layer_pattern=tuple(cfg.layer_pattern) * reps)


def _requests(cfg, lens, gens, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(req_id=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=(n,)).tolist(),
                    max_new_tokens=g, arrival_s=0.0)
            for i, (n, g) in enumerate(zip(lens, gens))]


def _deleted(cache):
    return [x.is_deleted() for x in jax.tree.leaves(cache)]


# ---------------------------------------------------------------------------
# donation: every call consumes the previous cache
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_mode", ["paged", "contiguous"])
def test_prefill_and_decode_ticks_consume_the_previous_cache(kv_mode):
    cfg = get_config("gemma2-2b-smoke")
    params = T.init_params(cfg, jax.random.key(0))
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=24, prefill_chunk=4, kv_mode=kv_mode,
        block_size=4))
    eng.submit(_requests(cfg, [6], [3]))
    eng.metrics.start()
    eng._admit_ready(0.0)                # contiguous: resets the slot row
    for tick in (eng._prefill_tick, eng._prefill_tick, eng._decode_tick):
        old = eng.cache
        tick()
        assert all(_deleted(old)), tick.__name__
        assert not any(_deleted(eng.cache))
    calls = 3 + (kv_mode == "contiguous")
    assert eng.metrics.cache_calls == calls
    assert eng.metrics.summary()["cache_donated_share"] == 1.0


def test_copy_on_write_consumes_the_previous_cache():
    """Prefix sharing: a tail chunk that rewrites a shared block clones it
    first (``serve_copy_block``), in place like every other program."""
    cfg = get_config("gemma2-2b-smoke")
    params = T.init_params(cfg, jax.random.key(0))
    # request 1 arrives while request 0 decodes: it hits 0's two published
    # blocks, and its right-aligned tail chunk [6, 10) rewrites half of
    # the shared block [4, 8)
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, 10)
    reqs = [Request(req_id=0, prompt=prompt.tolist(), max_new_tokens=16),
            Request(req_id=1, prompt=prompt.tolist(), max_new_tokens=3,
                    arrival_s=0.1)]
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=32, prefill_chunk=4, kv_mode="paged",
        block_size=4))
    copy = eng._copy
    copied = []

    def logged(c, src, dst):
        out = copy(c, src, dst)
        copied.append(all(_deleted(c)))
        return out

    eng._copy = logged
    eng.run(reqs)
    assert eng.metrics.prefix_hit_tokens == 8
    assert copied == [True]
    assert eng.metrics.cache_donated_share == 1.0


def test_cache_donated_share_counts_calls():
    m = ServeMetrics()
    assert m.cache_donated_share == 0.0
    for donated in (True, True, False, True):
        m.on_cache_call(donated)
    assert (m.cache_calls, m.cache_donated) == (4, 3)
    assert m.summary()["cache_donated_share"] == 0.75


# ---------------------------------------------------------------------------
# a step writes its rows into layer i of the stack, and nothing else
# ---------------------------------------------------------------------------


def test_paged_scatter_redirects_to_the_given_sentinel():
    pool = jnp.zeros((6, 2, 1), jnp.float32)
    tables = jnp.asarray([[3, PAGED_SENTINEL], [PAGED_SENTINEL] * 2])
    new = jnp.asarray([[[1.0], [2.0], [3.0], [4.0], [5.0]],
                       [[6.0], [7.0], [8.0], [9.0], [10.0]]])
    out = np.asarray(paged_scatter(pool, new, tables, 0, sentinel=5))
    assert out[3, :, 0].tolist() == [1.0, 2.0]
    # row 0's unmapped block and the span's tail, and every write of the
    # all-sentinel row 1, land on block 5; block 0 is never written
    assert out[0].sum() == 0 and out[5].sum() > 0
    assert np.count_nonzero(out[[1, 2, 4]]) == 0


def test_decode_step_writes_only_its_rows_of_the_stacked_pool():
    """Two stacked GQA layers over one paged pool: a decode step with one
    live row and one masked row changes, in each layer, the live row's
    position and the layer's own sentinel block — nothing else."""
    cfg = get_config("granite-34b-smoke")            # (("attn",), 2)
    assert cfg.segments()[0][1] == 2
    params = T.init_params(cfg, jax.random.key(0))
    N, bs = 6, 4
    cache = T.init_paged_cache(cfg, N, bs)
    cache = jax.tree.map(
        lambda x: jnp.arange(x.size, dtype=x.dtype).reshape(x.shape)
        / x.size, cache)
    before = [np.asarray(x) for x in jax.tree.leaves(cache)]
    tables = jnp.asarray([[2, 5, 0], [0, 0, 0]], jnp.int32)
    _, after = T.decode_step(params, cfg, jnp.asarray([[7], [9]]), cache,
                             jnp.asarray([5, 11], jnp.int32),
                             block_tables=tables)
    for a, b in zip(before, jax.tree.leaves(after)):
        b = np.asarray(b)
        assert a.shape == b.shape == (2, N, bs) + a.shape[3:]
        changed = {tuple(ix[:3]) for ix in np.argwhere(a != b)}
        for layer in range(2):
            mine = {ix[1:] for ix in changed if ix[0] == layer}
            # live row: position 5 is block 5 (table entry 1), slot 1;
            # the masked row's write goes to this layer's block 0
            assert (5, 1) in mine and {blk for blk, _ in mine} == {0, 5}
            assert mine - {(5, 1)} == {(0, 3)}


# ---------------------------------------------------------------------------
# token identity over stacked repeats, every cache kind
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,kv_mode", [
    ("gemma2-2b-smoke", "paged"),           # GQA, sliding window, softcap
    ("gemma2-2b-smoke", "contiguous"),
    ("deepseek-v3-671b-smoke", "paged"),    # MLA + MoE
    ("deepseek-v3-671b-smoke", "contiguous"),
    ("jamba-v0.1-52b-smoke", "paged"),      # attention + mamba rows
    ("jamba-v0.1-52b-smoke", "contiguous"),
    ("xlstm-1.3b-smoke", "contiguous"),     # recurrent rows only
])
def test_stacked_layers_serve_the_oracle_tokens(arch, kv_mode):
    cfg = _stacked(arch)
    assert all(reps == 2 for _, reps in cfg.segments())
    params = T.init_params(cfg, jax.random.key(0))
    ecfg = EngineConfig(max_slots=2, max_len=32, prefill_chunk=4,
                        chunks_per_step=2, temperature=0.7, seed=5,
                        kv_mode=kv_mode, block_size=8, paged_kernel="pallas")
    # 14 prompt + up to 8 generated positions pass the 16-position window
    lens, gens = [14] * 4, [8, 3, 6, 5]
    oracle, _ = serve_waves(cfg, params, ecfg, _requests(cfg, lens, gens))
    eng = ServeEngine(cfg, params, ecfg)
    assert eng.run(_requests(cfg, lens, gens)) == oracle
    assert eng.metrics.cache_donated_share == 1.0


# ---------------------------------------------------------------------------
# a 2-device data mesh, as the serve CLI builds it
# ---------------------------------------------------------------------------

_MESH_SCRIPT = """
import json, sys
from repro.launch import serve
argv = sys.argv[1:]
out = {}
for kv in ("paged", "contiguous"):
    args = argv + ["--kv-mode", kv]
    mesh_res, mesh_m, eng = serve.main(args + ["--devices", "2"])
    one_res, _, _ = serve.main(args)
    out[kv] = dict(same=mesh_res == one_res, n=len(mesh_res),
                   devices=len(eng.mesh.devices.flat),
                   share=mesh_m.cache_donated_share)
print("RESULT " + json.dumps(out))
"""


def test_two_device_mesh_serves_the_single_device_tokens():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT, "--arch", "gemma2-2b-smoke",
         "--requests", "5", "--prompt-len", "8", "--gen", "6",
         "--gen-spread", "3", "--max-slots", "2", "--temperature", "0.7",
         "--block-size", "4"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    out = json.loads(line[-1][len("RESULT "):])
    for kv, r in out.items():
        assert r == dict(same=True, n=5, devices=2, share=1.0), (kv, r)
