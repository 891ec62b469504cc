"""Multi-device numerical checks for the FractalSync collective schedules.

Run standalone (spawned by tests/test_collectives.py as a subprocess so the
rest of the suite keeps a single-device jax):

    PYTHONPATH=src python tests/collective_checks.py
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=16 "
    + os.environ.get("XLA_FLAGS", ""))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import collectives as C  # noqa: E402
from repro.core import schedule_ir as IR  # noqa: E402
from repro.core.bsp import BSPConfig, bsp_shard_map, sync_gradients  # noqa: E402
from repro.core.barrier import SyncDomainMesh  # noqa: E402

PASS = []


def check(name, fn):
    fn()
    PASS.append(name)
    print(f"ok  {name}", flush=True)


def sm(fn, mesh, spec):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False,
                                 axis_names=frozenset(mesh.axis_names)))


def main():
    rng = np.random.default_rng(0)
    mesh44 = jax.make_mesh((4, 4), ("a", "b"))
    axes, sizes = ("a", "b"), (4, 4)
    n_dev = 16
    x = jnp.asarray(rng.normal(size=(n_dev * 64, 8)).astype(np.float32))
    spec = P(("a", "b"))
    want = np.asarray(x)  # all-reduce of a sharded array == sum of shards
    shards = np.asarray(x).reshape(n_dev, -1, 8)
    total = shards.sum(0)  # per-shard expected all-reduce value

    def expect_allreduce(fn, tol=1e-5):
        out = sm(fn, mesh44, spec)(x)
        got = np.asarray(out).reshape(n_dev, -1, 8)
        for d in range(n_dev):
            np.testing.assert_allclose(got[d], total, rtol=tol, atol=tol)

    check("fractal_all_reduce == psum",
          lambda: expect_allreduce(
              lambda v: C.fractal_all_reduce(v, axes, sizes)))

    check("naive_all_reduce == psum",
          lambda: expect_allreduce(
              lambda v: C.naive_all_reduce(v, axes, sizes)))

    check("xy_all_reduce == psum",
          lambda: expect_allreduce(
              lambda v: C.xy_all_reduce(v, "b", "a", 4, 4)))

    check("ring nested == psum",
          lambda: expect_allreduce(
              lambda v: C.all_reduce(v, "ring", axes, sizes)))

    check("hierarchical == psum",
          lambda: expect_allreduce(
              lambda v: C.hierarchical_all_reduce(v, ("b",), (4,), ("a",), (4,))))

    def rs_ag():
        def f(v):
            s = C.fractal_reduce_scatter(v, axes, sizes)
            return C.fractal_all_gather(s, axes, sizes)
        expect_allreduce(f)
    check("fractal reduce_scatter∘all_gather == psum", rs_ag)

    def rs_alone():
        def f(v):
            s = C.fractal_reduce_scatter(v, axes, sizes)
            return lax.all_gather(s, axes, tiled=False).reshape(v.shape[0] // 16 * 16, *v.shape[1:]) * 0 + jnp.sum(s)  # noqa
        # simpler: verify the scattered shards jointly cover the sum
        def g(v):
            s = C.fractal_reduce_scatter(v, axes, sizes)
            return jnp.sum(s)
        out = sm(g, mesh44, P(("a", "b")))  # scalar per shard not valid out_spec
    # coverage of rs alone is implied by rs∘ag test; skip direct check

    # --- barrier tokens per level -----------------------------------------
    def barrier_levels():
        sdm = SyncDomainMesh(mesh44, ("a", "b"))
        for level in range(sdm.num_levels + 1):
            def f(v, level=level):
                tok = sdm.fsync(level)
                return v * 0 + tok
            out = sm(f, mesh44, spec)(x)
            got = np.unique(np.asarray(out))
            assert got.size == 1 and got[0] == 2 ** level, (level, got)
    check("fsync(level) token == 2^level", barrier_levels)

    # --- sync_gradients: every schedule matches psum-mean ------------------
    grads = {
        "w": jnp.asarray(rng.normal(size=(n_dev, 40, 3)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(n_dev * 5,)).astype(np.float32)),
    }
    gspec = {"w": P(("a", "b")), "b": P(("a", "b"))}
    wsh = np.asarray(grads["w"]).reshape(n_dev, 1, 40, 3)
    bsh = np.asarray(grads["b"]).reshape(n_dev, 5)
    wmean, bmean = wsh.mean(0), bsh.mean(0)

    for schedule in ("fractal", "ring", "xy", "naive", "hierarchical",
                     "tree", "auto", "xla"):
        def do(schedule=schedule):
            cfg = BSPConfig(sync_axes=axes, schedule=schedule)
            f = lambda g: sync_gradients(g, cfg, sizes)
            out = jax.jit(jax.shard_map(
                f, mesh=mesh44, in_specs=(gspec,), out_specs=gspec,
                check_vma=False, axis_names=frozenset(("a", "b"))))(grads)
            w = np.asarray(out["w"]).reshape(n_dev, 1, 40, 3)
            b = np.asarray(out["b"]).reshape(n_dev, 5)
            for d in range(n_dev):
                np.testing.assert_allclose(w[d], wmean, rtol=2e-5, atol=2e-5)
                np.testing.assert_allclose(b[d], bmean, rtol=2e-5, atol=2e-5)
        check(f"sync_gradients[{schedule}] == mean", do)

    # --- compressed payloads ------------------------------------------------
    for comp, tol in (("bf16", 2e-2), ("int8", 6e-2)):
        def do(comp=comp, tol=tol):
            cfg = BSPConfig(sync_axes=axes, schedule="fractal", compression=comp)
            f = lambda g: sync_gradients(g, cfg, sizes)
            out = jax.jit(jax.shard_map(
                f, mesh=mesh44, in_specs=(gspec,), out_specs=gspec,
                check_vma=False, axis_names=frozenset(("a", "b"))))(grads)
            w = np.asarray(out["w"]).reshape(n_dev, 1, 40, 3)
            scale = np.abs(wmean).max()
            for d in range(n_dev):
                np.testing.assert_allclose(w[d], wmean, atol=tol * scale)
        check(f"sync_gradients[fractal+{comp}] ≈ mean", do)

    # --- IR lowering ≡ legacy hand-rolled lowering --------------------------
    def ir_vs_legacy():
        prog = IR.build_program("fractal", (4, 4))

        def f(v):
            a = C.ir_all_reduce(v, prog, axes)
            b = C.fractal_all_reduce(v, axes, sizes)
            return a - b
        out = np.asarray(sm(f, mesh44, spec)(x))
        np.testing.assert_allclose(out, 0.0, atol=1e-4 * np.abs(total).max())
    check("IR lowering ≡ legacy fractal lowering", ir_vs_legacy)

    # --- manual sync axes + auto model axis ---------------------------------
    def auto_model():
        mesh = jax.make_mesh((2, 2, 4), ("pod", "data", "model"))
        k = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))

        def f(kv):
            kk, vv = kv
            y = kk @ vv            # model-axis GSPMD matmul inside manual DP
            cfg = BSPConfig(sync_axes=("pod", "data"), schedule="fractal")
            return sync_gradients(y, cfg, (2, 2), mean=False)

        fn = bsp_shard_map(f, mesh,
                           in_specs=((P(("pod", "data")), P(None)),),
                           out_specs=P(("pod", "data")),
                           sync_axes=("pod", "data"))
        out = jax.jit(fn)((k, v))
        got = np.asarray(out).reshape(4, 4, 8)
        ref = (np.asarray(k) @ np.asarray(v)).reshape(4, 4, 8).sum(0)
        for d in range(4):
            np.testing.assert_allclose(got[d], ref, rtol=1e-4, atol=1e-4)
    check("bsp_shard_map manual-DP + auto-model", auto_model)

    print(f"ALL OK ({len(PASS)} checks)")


if __name__ == "__main__":
    main()
