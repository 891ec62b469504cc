"""Multi-device numerics: bucketed pipelined sync ≡ monolithic sync.

Run standalone (spawned by tests/test_superstep.py as a subprocess so the
rest of the suite keeps a single-device jax):

    PYTHONPATH=src python tests/superstep_checks.py

Covers the ISSUE's equivalence matrix on a 16-device 4×4 host mesh:
ragged pytrees, odd bucket boundaries (pad_align variations), every
schedule (incl. per-bucket "auto") and every compression codec.  The
codec-free bucketed paths must match the monolithic path EXACTLY (the
same elementwise reduction tree, just regrouped); codec paths match the
psum-mean reference within codec tolerance.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=16 "
    + os.environ.get("XLA_FLAGS", ""))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import superstep as SS  # noqa: E402
from repro.core.bsp import BSPConfig, sync_gradients  # noqa: E402

AXES, SIZES = ("a", "b"), (4, 4)
N_DEV = 16

PASS = []


def check(name, fn):
    fn()
    PASS.append(name)
    print(f"ok  {name}", flush=True)


def ragged_tree(rng):
    """Deliberately awkward leaf shapes: primes, scalars-ish, matrices."""
    return {
        "embed": jnp.asarray(rng.normal(size=(N_DEV, 97, 13))
                             .astype(np.float32)),
        "layers": [
            {"w": jnp.asarray(rng.normal(size=(N_DEV * 31,))
                              .astype(np.float32)),
             "b": jnp.asarray(rng.normal(size=(N_DEV, 7))
                              .astype(np.float32))}
            for _ in range(3)
        ],
        "head": jnp.asarray(rng.normal(size=(N_DEV * 5, 11))
                            .astype(np.float32)),
    }


def run_sync(tree, cfg):
    spec = jax.tree.map(lambda _: P(("a", "b")), tree)
    fn = jax.jit(jax.shard_map(
        lambda g: sync_gradients(g, cfg, SIZES),
        mesh=jax.make_mesh(SIZES, AXES), in_specs=(spec,), out_specs=spec,
        check_vma=False, axis_names=frozenset(AXES)))
    return fn(tree)


def psum_mean_reference(tree):
    """Per-shard mean over the 16 device shards, replicated back."""
    def ref_leaf(x):
        shards = np.asarray(x).reshape(N_DEV, -1)
        mean = shards.mean(0)
        return np.tile(mean, (N_DEV, 1)).reshape(x.shape)
    return jax.tree.map(ref_leaf, tree)


def main():
    rng = np.random.default_rng(7)
    tree = ragged_tree(rng)
    ref = psum_mean_reference(tree)

    mono = {}   # schedule -> monolithic result (bucket_mb=None)

    # --- every schedule, monolithic vs reference ---------------------------
    for schedule in ("fractal", "ring", "xy", "naive", "hierarchical",
                     "tree", "auto"):
        def do(schedule=schedule):
            cfg = BSPConfig(sync_axes=AXES, schedule=schedule)
            out = run_sync(tree, cfg)
            mono[schedule] = out
            for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
                np.testing.assert_allclose(np.asarray(got), want,
                                           rtol=2e-5, atol=2e-5)
        check(f"monolithic[{schedule}] == psum-mean", do)

    # --- bucketed vs monolithic ------------------------------------------
    # The fractal butterfly reduces every element through the SAME binary
    # tree regardless of its position in the flat buffer, so bucketing is
    # BIT-EXACT there.  Ring/xy summation order depends on an element's
    # chunk index, which bucketing shifts — f32-tolerance equality (the
    # ISSUE's bar) for those.  Odd bucket boundaries: tiny bucket targets
    # and non-default pad_align.
    for schedule in ("fractal", "ring", "xy", "naive", "hierarchical",
                     "tree", "auto"):
        for bucket_mb, pad_align in ((0.002, 128), (0.01, 8), (0.0005, 32)):
            def do(schedule=schedule, bucket_mb=bucket_mb,
                   pad_align=pad_align):
                cfg = BSPConfig(sync_axes=AXES, schedule=schedule,
                                bucket_mb=bucket_mb, pad_align=pad_align)
                eng = SS.engine_for(tree, cfg, SIZES)
                assert eng.n_buckets > 1, \
                    f"test should exercise >1 bucket, got {eng.describe()}"
                out = run_sync(tree, cfg)
                for got, want in zip(jax.tree.leaves(out),
                                     jax.tree.leaves(mono[schedule])):
                    if schedule == "fractal":
                        np.testing.assert_array_equal(np.asarray(got),
                                                      np.asarray(want))
                    else:
                        np.testing.assert_allclose(np.asarray(got),
                                                   np.asarray(want),
                                                   rtol=1e-5, atol=1e-6)
            tag = ("== monolithic exactly" if schedule == "fractal"
                   else "≈ monolithic (f32)")
            check(f"bucketed[{schedule},{bucket_mb}MB,align{pad_align}] "
                  f"{tag}", do)

    # --- overlap=False collapses to the monolithic result ------------------
    def no_overlap():
        cfg = BSPConfig(sync_axes=AXES, schedule="fractal", bucket_mb=0.002,
                        overlap=False)
        assert SS.engine_for(tree, cfg, SIZES).n_buckets == 1
        out = run_sync(tree, cfg)
        for got, want in zip(jax.tree.leaves(out),
                             jax.tree.leaves(mono["fractal"])):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    check("bucketed[overlap=False] == monolithic exactly", no_overlap)

    # --- every codec: bucketed vs reference within codec tolerance ---------
    for comp, tol in (("bf16", 2e-2), ("int8", 6e-2)):
        def do(comp=comp, tol=tol):
            cfg = BSPConfig(sync_axes=AXES, schedule="fractal",
                            compression=comp, bucket_mb=0.002)
            out = run_sync(tree, cfg)
            for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
                scale = max(np.abs(want).max(), 1e-3)
                np.testing.assert_allclose(np.asarray(got), want,
                                           atol=tol * scale)
        check(f"bucketed[fractal+{comp}] ≈ psum-mean", do)

    # --- DP bucket-boundary search (bucket_mb="auto") ----------------------
    # Boundaries move; the reduction tree does not: the fractal DP plan must
    # stay bit-identical to the monolithic sync.  A bandwidth-starved link
    # forces the DP to actually split (with the default TPU link this tiny
    # payload is latency-bound and one bucket IS optimal).
    def dp_auto():
        from repro.core.cost_model import LinkParams
        starved = LinkParams(alpha_s=1e-9, bw_Bps=1e6, name="starved")
        cfg = BSPConfig(sync_axes=AXES, schedule="fractal",
                        bucket_mb="auto", link=starved)
        eng = SS.engine_for(tree, cfg, SIZES)
        assert eng.plan is not None and eng.plan.source == "dp", \
            eng.describe()
        assert eng.n_buckets > 1, \
            f"starved link should split buckets, got {eng.describe()}"
        out = run_sync(tree, cfg)
        for got, want in zip(jax.tree.leaves(out),
                             jax.tree.leaves(mono["fractal"])):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    check("bucketed[bucket_mb=auto,fractal] == monolithic exactly", dp_auto)

    # --- per-bucket codec (bucket_codec) -----------------------------------
    def bucket_codec_forced():
        cfg = BSPConfig(sync_axes=AXES, schedule="fractal",
                        bucket_mb=0.002, bucket_codec="bf16")
        out = run_sync(tree, cfg)
        for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
            scale = max(np.abs(want).max(), 1e-3)
            np.testing.assert_allclose(np.asarray(got), want,
                                       atol=2e-2 * scale)
    check("bucketed[bucket_codec=bf16] ≈ psum-mean", bucket_codec_forced)

    def bucket_codec_auto_none_is_exact():
        # tiny latency-bound buckets: the policy must skip compression,
        # making the auto-codec path bit-identical to the codec-free one
        cfg = BSPConfig(sync_axes=AXES, schedule="fractal",
                        bucket_mb=0.002, bucket_codec="auto")
        eng = SS.engine_for(tree, cfg, SIZES)
        assert all(c == "none" for c in eng.codec_names), eng.describe()
        out = run_sync(tree, cfg)
        for got, want in zip(jax.tree.leaves(out),
                             jax.tree.leaves(mono["fractal"])):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    check("bucketed[bucket_codec=auto→none] == monolithic exactly",
          bucket_codec_auto_none_is_exact)

    # --- codec'd fractal reduce-scatter (the ZeRO-1 trainer wire path) -----
    def rs_codec():
        from repro.core import collectives as C
        from repro.optim.compression import Bf16Codec
        flat = jnp.asarray(rng.normal(size=(N_DEV * N_DEV * 128,))
                           .astype(np.float32))
        spec = P(("a", "b"))
        mesh = jax.make_mesh(SIZES, AXES)

        def run_rs(codec):
            fn = jax.jit(jax.shard_map(
                lambda v: C.reduce_scatter(v, "fractal", AXES, SIZES,
                                           codec=codec),
                mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False,
                axis_names=frozenset(AXES)))
            return np.asarray(fn(flat))

        exact = run_rs(None)
        coded = run_rs(Bf16Codec())
        scale = max(np.abs(exact).max(), 1e-3)
        np.testing.assert_allclose(coded, exact, atol=2e-2 * scale)
    check("reduce_scatter[fractal+bf16 wire] ≈ uncompressed", rs_codec)

    print(f"ALL OK ({len(PASS)} checks)")


if __name__ == "__main__":
    main()
