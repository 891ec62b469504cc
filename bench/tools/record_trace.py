"""Record the small trace that ``bench/tests`` reduce: a few steps of a
tiny jitted program with a Pallas kernel inside, each under a
``bench.engine_step`` host span, on one chip.

  python bench/tools/record_trace.py chiprun_out/trace_small.xplane.pb
"""

import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))


def main(out):
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention.ops import paged_attention

    B, n, bs, hkv, G, d = 8, 8, 16, 2, 4, 128
    k = jax.random.key(0)
    q = jax.random.normal(k, (B, 1, hkv * G, d), jnp.bfloat16)
    pool = jax.random.normal(k, (1 + B * n, bs, hkv, d), jnp.bfloat16)
    tables = (1 + jnp.arange(B * n, dtype=jnp.int32)).reshape(B, n)
    off = jnp.full((B,), n * bs - 1, jnp.int32)
    w = jax.random.normal(k, (1024, 2048), jnp.bfloat16)

    @jax.jit
    def step(q, w):
        o = paged_attention(q, pool, pool, tables, off)
        x = o.reshape(B, -1) @ w
        return x.sum()

    step(q, w).block_until_ready()
    d_ = tempfile.mkdtemp(dir=str(ROOT))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d_, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            step(q, w).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host_gap"):
            sum(range(200000))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d_, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(d_)
    print("wrote", out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
