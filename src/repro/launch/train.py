"""Training entry point.

Single-process usage (CPU devices; multi-host launch wires the same pieces
with per-host data sharding):

  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b-smoke \
      --steps 50 --batch 8 --seq 128 --schedule fractal [--devices 8]

``--schedule xla`` uses the GSPMD tier; anything else uses the explicit BSP
superstep (fractal | ring | xy | naive | hierarchical | tree | auto) with
optional ``--compression {bf16,int8}`` — the paper's technique end to end.
``auto`` asks the cost-model autotuner (core.autotune) to pick the schedule
for the mesh/payload at build time.

``--bucket-mb N`` partitions the gradients into ~N MB reverse-layer buckets
and pipelines one collective per bucket (SuperstepEngine); with
``--schedule auto`` the autotuner picks a schedule *per bucket*.
``--bucket-mb auto`` searches the bucket boundaries themselves (dynamic
program over leaf prefix sums against the overlap-aware cost model), and
``--bucket-codec auto`` lets the tuner pick a wire codec per bucket.
``--calibrate`` times a grid of real collectives on the launch devices
first and fits the cost model's link parameters to the measurements, so
every "auto" pick is priced with platform numbers instead of defaults.
``--no-overlap`` is the A/B switch back to the monolithic single-collective
superstep; ``--grad-accum K`` accumulates over K micro-batches per rank.
"""

import argparse
import os


def _bucket_mb_arg(v):
    return "auto" if v == "auto" else float(v)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--schedule", default="fractal")
    ap.add_argument("--compression", default="none")
    ap.add_argument("--fsync-level", type=int, default=None)
    ap.add_argument("--bucket-mb", type=_bucket_mb_arg, default=None,
                    help="pipeline gradient sync over ~N MB buckets "
                         "(reverse-layer order; default: monolithic), or "
                         "'auto' for the DP bucket-boundary search")
    ap.add_argument("--bucket-codec", default=None,
                    choices=["auto", "none", "bf16", "int8"],
                    help="per-bucket wire codec: 'auto' lets the tuner "
                         "pick per bucket (default: uniform --compression)")
    ap.add_argument("--calibrate", action="store_true",
                    help="fit cost-model link params from measured "
                         "collectives on the launch devices before tuning")
    ap.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="--no-overlap collapses bucketing back to the "
                         "monolithic superstep (A/B baseline)")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="micro-batches accumulated per rank per superstep")
    ap.add_argument("--devices", type=int, default=0,
                    help="host-device override (set before jax init)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    from repro.launch.compile_cache import use_compile_cache
    from repro.models.registry import get_config

    use_compile_cache()
    return run(get_config(args.arch), args)


def run(cfg, args: argparse.Namespace):
    """Train ``cfg`` as ``args`` (from ``parse_args``) say, data-parallel
    over every device JAX sees; returns the loop's result (``history``
    holds each step's loss)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.bsp import BSPConfig
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as T
    from repro.models.sharding import named
    from repro.optim import adamw
    from repro.runtime import trainer
    from repro.runtime.loop import LoopConfig, TrainLoop, resume_or_init

    n_dev = len(jax.devices())
    dp = n_dev
    mesh = make_mesh((dp, 1), ("data", "model"))
    acfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                             warmup_steps=max(1, args.steps // 10))
    key = jax.random.key(args.seed)

    ckpt_meta = {}
    if args.schedule == "xla":
        step_fn, (pspec, ospec, bspec) = trainer.make_gspmd_train_step(
            cfg, mesh, acfg)
        # params and moments are made in place, each device its own shard
        params = jax.jit(lambda k: T.init_params(cfg, k),
                         out_shardings=named(mesh, pspec))(key)
        opt = jax.jit(lambda p: adamw.init(p, acfg),
                      out_shardings=named(mesh, ospec))(params)
        state = (params, opt)
        bshard = {k: NamedSharding(mesh, s) for k, s in bspec.items()}
    else:
        link = None
        if args.calibrate:
            # Fitted params are persisted next to the checkpoints and
            # RELOADED on resume: refitting from fresh (noisy) timings
            # could move the DP bucket boundaries and invalidate the
            # checkpointed moment layout with no way back.
            import dataclasses
            import json
            cal_path = (os.path.join(args.checkpoint_dir,
                                     "link_calibration.json")
                        if args.checkpoint_dir else None)
            if cal_path and os.path.exists(cal_path):
                from repro.core.cost_model import LinkParams
                with open(cal_path) as f:
                    link = LinkParams(**json.load(f)["link"])
                print(f"calibrate: reloaded {link.name} from {cal_path}")
            elif n_dev >= 2:
                from repro.core.calibrate import fit_link_params
                # fit on the largest power-of-two sub-mesh the devices allow
                fit = fit_link_params(min_devices=2)
                print(fit.describe())
                link = fit.link
                if cal_path:
                    os.makedirs(args.checkpoint_dir, exist_ok=True)
                    with open(cal_path, "w") as f:
                        json.dump({"link": dataclasses.asdict(link)}, f,
                                  indent=2)
            else:
                print("calibrate: skipped (needs ≥2 devices; "
                      "pass --devices 8)")
        bsp = BSPConfig(sync_axes=("data",), schedule=args.schedule,
                        compression=args.compression,
                        fsync_level=args.fsync_level,
                        bucket_mb=args.bucket_mb,
                        overlap=args.overlap,
                        bucket_codec=args.bucket_codec,
                        link=link)
        step_fn, init_state = trainer.make_bsp_train_step(
            cfg, mesh, acfg, bsp, grad_accum=args.grad_accum)
        # DP-replicated params, made on every device at once
        params = jax.jit(lambda k: T.init_params(cfg, k),
                         out_shardings=NamedSharding(mesh, P()))(key)
        state = init_state(params)
        ckpt_meta = {"superstep_layout": init_state.superstep_layout}
        bshard = {k: NamedSharding(mesh, P("data", *([None] * pad)))
                  for k, pad in (("tokens", 1), ("labels", 1),
                                 ("frontend", 2))}
        if not cfg.frontend:
            bshard.pop("frontend")

    print(f"arch={cfg.name} devices={n_dev} params="
          f"{sum(x.size for x in jax.tree.leaves(state[0])):,}")
    state, start = resume_or_init(args.checkpoint_dir, state,
                                  expect_meta=ckpt_meta)
    data = SyntheticLM(cfg, DataConfig(global_batch=args.batch,
                                       seq_len=args.seq, seed=args.seed))
    loop = TrainLoop(
        step_fn=step_fn, state=state, data=data,
        cfg=LoopConfig(total_steps=args.steps,
                       checkpoint_every=args.checkpoint_every,
                       checkpoint_dir=args.checkpoint_dir),
        batch_shardings=bshard, start_step=start, ckpt_meta=ckpt_meta)
    out = loop.run()
    losses = [h["loss"] for h in out["history"]]
    if losses:
        print(f"loss: first={losses[0]:.4f} last={losses[-1]:.4f}")
    return out


if __name__ == "__main__":
    main()
