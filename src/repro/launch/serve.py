"""Serving entry point: continuous-batching engine over a slot pool.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b-smoke \
      --requests 8 --prompt-len 32 --gen 16 --max-slots 4 \
      [--kv-mode paged --block-size 16 --kv-blocks 64] \
      [--arrival poisson:50] [--eos-id 2] [--devices 8] [--mode wave]

Built on ``repro.serve``: a fixed pool of ``--max-slots`` decode slots over
one shared cache; queued requests are admitted the moment EOS (or the
per-request budget) frees capacity, with chunked prefill interleaved
between decode steps.  Per-layer decode state goes through the SlotState
protocol, so every token-only architecture serves — pure attention, pure
recurrent (mamba / xLSTM), and hybrids (Jamba) mixing KV and recurrent
backends in one run.  Reports per-request TTFT, per-step throughput and
slot occupancy.  ``--mode wave`` runs the old wave-at-a-time loop — the
token-identity test oracle — for A/B comparison (see
``benchmarks/serve_bench.py``).

  --arrival immediate | poisson:RATE | trace:SPEC   synthetic arrivals
  --gen-spread K        ragged output budgets: gen drawn from [gen-K, gen]
  --max-slots S         decode slot pool size (shards over --devices)
  --kv-mode M           contiguous (one max_len row per slot) or paged
                        (pooled blocks + block tables: admission gated on
                        free blocks, prefix-cache sharing, preemption)
  --block-size B        paged: positions per physical block
  --kv-blocks N         paged: pool size (0 = match contiguous capacity)
  --paged-kernel K      paged decode attention lowering: auto (fused Pallas
                        kernel on TPU, gather oracle elsewhere) | pallas
                        (force the fused kernel; interpret mode off-TPU) |
                        ref (force the gather-then-attend oracle)
  --slot-state M        KV-layer backend override: auto (follow --kv-mode) |
                        contiguous | paged; recurrent layers always use the
                        recurrent-row backend
  --rec-slots R         recurrent-state rows (0 = match --max-slots); fewer
                        rows than slots makes rows the scarce admission
                        resource
  --clock C             step (virtual, deterministic; idle gaps jump) |
                        wall (measured seconds; idle gaps really sleep)
"""

import argparse
import os


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16,
                    help="per-request generation budget (first token incl.)")
    ap.add_argument("--gen-spread", type=int, default=0,
                    help="ragged budgets: draw from [gen-K, gen] per request")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=None,
                    help="token id that completes a request and frees its "
                         "slot for the next admission")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--kv-mode", choices=("contiguous", "paged"),
                    default="contiguous")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV: cache positions per physical block")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="paged KV: physical blocks in the pool "
                         "(0 = match contiguous capacity)")
    ap.add_argument("--paged-kernel", choices=("auto", "pallas", "ref"),
                    default="auto",
                    help="paged decode attention lowering (auto: fused "
                         "Pallas kernel on TPU, gather oracle elsewhere)")
    ap.add_argument("--slot-state", choices=("auto", "contiguous", "paged"),
                    default="auto",
                    help="KV-layer backend override (auto: follow "
                         "--kv-mode); recurrent layers always use the "
                         "recurrent-row backend")
    ap.add_argument("--rec-slots", type=int, default=0,
                    help="recurrent-state rows (0 = match --max-slots)")
    ap.add_argument("--clock", choices=("step", "wall"), default="step",
                    help="serve clock: step (virtual, deterministic) or "
                         "wall (measured seconds, idle gaps sleep)")
    ap.add_argument("--arrival", default="immediate",
                    help="immediate | poisson:RATE | trace:SPEC")
    ap.add_argument("--mode", choices=("continuous", "wave"),
                    default="continuous")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.mode == "wave" and args.kv_mode == "paged":
        ap.error("--mode wave serves from the contiguous cache only")

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import numpy as np

    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as T
    from repro.models.registry import get_config
    from repro.serve import (EngineConfig, Request, ServeEngine,
                             parse_arrival_spec, serve_waves)

    use_compile_cache()
    cfg = get_config(args.arch)
    params = T.init_params(cfg, jax.random.key(args.seed))

    rng = np.random.default_rng(args.seed)
    arrivals = parse_arrival_spec(args.arrival, args.requests, args.seed)
    requests = []
    for i in range(args.requests):
        gen = args.gen if args.gen_spread <= 0 else int(
            rng.integers(max(1, args.gen - args.gen_spread), args.gen + 1))
        prompt = rng.integers(0, cfg.vocab_size,
                              size=(args.prompt_len,)).tolist()
        requests.append(Request(req_id=i, prompt=prompt, max_new_tokens=gen,
                                arrival_s=arrivals[i]))

    max_len = args.prompt_len + args.gen + 1
    if args.kv_mode == "paged":
        # the paged backend needs block_size | max_len (virtual view shape
        # == contiguous row shape, the token-identity invariant)
        max_len = -(-max_len // args.block_size) * args.block_size
    ecfg = EngineConfig(
        max_slots=args.max_slots,
        max_len=max_len,
        prefill_chunk=args.prefill_chunk,
        temperature=args.temperature,
        eos_id=args.eos_id,
        seed=args.seed,
        kv_mode=args.kv_mode,
        slot_state=args.slot_state,
        rec_slots=args.rec_slots,
        block_size=args.block_size,
        kv_blocks=args.kv_blocks,
        paged_kernel=args.paged_kernel,
        clock=args.clock)

    mesh = None
    if args.devices:
        if args.mode == "wave":
            print(f"note: --devices {args.devices} ignored in wave mode "
                  "(the baseline runs unsharded)")
        else:
            mesh = make_mesh((args.devices,), ("data",))

    print(f"arch={cfg.name} mode={args.mode} kv={args.kv_mode} "
          f"requests={args.requests} "
          f"prompt={args.prompt_len} gen={args.gen}"
          f"{f'±{args.gen_spread}' if args.gen_spread else ''} "
          f"slots={args.max_slots} arrival={args.arrival}"
          + (f" block_size={args.block_size}" if args.kv_mode == "paged"
             else "")
          + (f" devices={args.devices}" if args.devices else ""))

    engine = None
    if args.mode == "wave":
        results, metrics = serve_waves(cfg, params, ecfg, requests)
    else:
        engine = ServeEngine(cfg, params, ecfg, mesh=mesh)
        print(f"slot-state plan: {engine.plan.describe()}"
              + (f" ({engine.rec.capacity} recurrent rows)"
                 if engine.rec is not None else ""))
        results = engine.run(requests)
        metrics = engine.metrics

    print(metrics.report())
    shown = sorted(results)[:2]
    print("sample outputs:", [results[i][:8] for i in shown])
    return results, metrics, engine


if __name__ == "__main__":
    main()
