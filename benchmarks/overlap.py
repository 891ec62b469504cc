"""Bucketed superstep overlap sweep — bucket size × schedule vs monolithic.

For each (mesh, model) cell the SuperstepEngine partitions a synthetic
transformer's gradient leaves into reverse-layer buckets and the sweep
reports, per bucket size (including the DP-searched ``bucket_mb="auto"``
boundaries):

  * the per-bucket autotuned schedules (``schedule="auto"``) and codecs
    (``bucket_codec="auto"``),
  * the overlap-aware predicted step time (``cost_model.overlap_step_cost``:
    buckets enter the shared fabric as backward produces them), and
  * the no-overlap baseline (backward, THEN all communication — what the
    monolithic path pays).

The headline claim is asserted: for at least one realistic cell the
overlap-aware predicted step time is strictly below the no-overlap sum,
and the DP-searched boundaries predict ≤ every fixed-size greedy packing.
A second section replays a bucket pipeline on the contended-NoC simulator
(``simulator.pipelined_on_noc``) against the serial sum of per-bucket
replays — the same overlap, with link contention simulated rather than
modeled.

``--measured`` adds the measured mode (≥8 host devices): the link
parameters are CALIBRATED from real jitted collectives
(``core.calibrate.fit_link_params``), the DP + per-bucket-codec engine is
refined with a measured-schedule budget (``SuperstepEngine.refined``), and
the resulting configuration's real jitted sync wall-clock is compared
against the greedy analytic configuration.  The greedy baseline is itself
in the measured candidate set (it is the tuner's upper bound), so the
chosen configuration's wall-clock ≤ greedy+analytic is asserted — measured
autotuning never does worse than its fallback on the very measurements it
selected by.

Results are persisted machine-readably to ``BENCH_overlap.json``
(predicted vs measured seconds, chosen schedules/codecs, speedups) so the
perf trajectory is tracked across PRs.

Standalone: PYTHONPATH=src python -m benchmarks.overlap \
                [--smoke] [--measured] [--devices N] [--out FILE]
Harness:    PYTHONPATH=src python -m benchmarks.run --only overlap
CI runs ``--smoke --measured --devices 8`` so neither path can rot.
"""

from __future__ import annotations

import argparse
import json
import time

from repro.core import autotune, cost_model as CM, schedule_ir as IR
from repro.core import superstep as SS
from repro.core.bsp import BSPConfig
from repro.core.simulator import pipelined_on_noc, schedule_on_noc

MFU = 0.4           # assumed model-flops utilization for the backward pass


def transformer_leaf_specs(d_model: int, n_layers: int, vocab: int):
    """Leaf sizes of a GPT-ish decoder in forward (layer) order."""
    leaves = [(vocab, d_model)]                       # embedding
    for _ in range(n_layers):
        leaves += [(d_model, 3 * d_model),            # qkv
                   (d_model, d_model),                # attn out
                   (d_model, 4 * d_model),            # mlp up
                   (4 * d_model, d_model),            # mlp down
                   (d_model,), (d_model,)]            # norms
    leaves += [(d_model,), (vocab, d_model)]          # final norm, lm head
    return tuple(SS.LeafSpec(shape=s, dtype="float32") for s in leaves)


def backward_seconds(n_params: int, tokens_per_rank: int,
                     chip: CM.ChipParams = CM.TPU_V5E) -> float:
    """4·P FLOPs/token for backward, at MFU of the chip's peak."""
    return 4.0 * n_params * tokens_per_rank / (MFU * chip.peak_flops)


CELLS = (
    # (mesh shape, d_model, n_layers, vocab, tokens/rank/step)
    ((4, 4), 2048, 24, 32_000, 8_192),     # ~1.4B on a 4×4 v5e slice
    ((8, 8), 4096, 32, 32_000, 4_096),     # ~6.5B on an 8×8 slice
)
BUCKET_MBS = (None, 16.0, 64.0, 256.0, "auto")


def sweep_cell(shape, d_model, n_layers, vocab, tokens,
               bucket_mbs=BUCKET_MBS, rows=None):
    specs = transformer_leaf_specs(d_model, n_layers, vocab)
    n_params = sum(s.size for s in specs)
    bwd_s = backward_seconds(n_params, tokens)
    cell = f"{shape[0]}x{shape[1]}/{n_params / 1e9:.1f}B"
    any_overlap_win = False
    fixed_overlapped, auto_overlapped = [], None
    for mb in bucket_mbs:
        cfg = BSPConfig(schedule="auto", bucket_mb=mb, bucket_codec="auto")
        eng = SS.SuperstepEngine(specs, cfg, shape, backward_s=bwd_s)
        tl = eng.timeline(bwd_s)
        picks = "+".join(
            f"{n}x{c}" for n, c in sorted(
                (s, eng.schedules.count(s)) for s in set(eng.schedules)))
        codecs = "+".join(
            f"{n}x{c}" for n, c in sorted(
                (s, eng.codec_names.count(s))
                for s in set(eng.codec_names)))
        label = "mono" if mb is None else \
            ("auto" if mb == "auto" else f"{mb:g}MB")
        print(f"overlap/{cell},{label},{eng.n_buckets} buckets,{picks},"
              f"{codecs},overlapped={tl.overlapped_s * 1e3:.2f}ms,"
              f"serial={tl.serial_s * 1e3:.2f}ms,"
              f"gain={tl.overlap_gain * 100:.1f}%")
        if rows is not None:
            rows.append({"cell": cell, "bucket_mb": mb,
                         "n_buckets": eng.n_buckets,
                         "schedules": list(eng.schedules),
                         "codecs": list(eng.codec_names),
                         "plan": eng.plan.source if eng.plan else None,
                         "predicted_overlapped_s": tl.overlapped_s,
                         "predicted_serial_s": tl.serial_s,
                         "overlap_gain": tl.overlap_gain})
        if mb is not None and tl.overlapped_s < tl.serial_s:
            any_overlap_win = True
        if mb == "auto":
            auto_overlapped = tl.overlapped_s
        elif mb is not None:
            fixed_overlapped.append((mb, tl.overlapped_s))
    if auto_overlapped is not None and fixed_overlapped:
        # the DP searches the space the fixed sizes sample, so it must not
        # predict (meaningfully) worse than any greedy packing it had as an
        # upper bound.  The DP optimizes the band-quantized policy price
        # while the timeline reprices exactly, so allow the quantization
        # slack (one quarter-octave ≈ 9%); the EXACT optimality claim is
        # locked by the brute-force property test instead.
        best_fixed = min(t for _, t in fixed_overlapped)
        assert auto_overlapped <= best_fixed * 1.10, (
            f"{cell}: DP-searched boundaries predict {auto_overlapped} "
            f"> best fixed bucket size {best_fixed}")
    return any_overlap_win


def noc_replay_section(shape=(4, 4), payload_flits=2048, n_buckets=4,
                       rows=None) -> None:
    """Simulated (contended-NoC) overlap vs serial replay of the buckets."""
    flits = [payload_flits // n_buckets] * n_buckets
    names = [autotune.pick_schedule(shape, f * 4, link=CM.MAGIA)
             for f in flits]
    progs = [IR.build_program(n, shape) for n in names]
    serial = sum(schedule_on_noc(p, payload_flits=f).overhead
                 for p, f in zip(progs, flits))
    # grads drop out of backward at a steady cadence ending at `serial`
    ready = [int(serial * (i + 1) / n_buckets) for i in range(n_buckets)]
    pipe = pipelined_on_noc(progs, payload_flits=flits, ready=ready)
    overlapped = pipe.overhead
    no_overlap = max(ready) + serial    # backward, THEN all buckets
    print(f"overlap/noc_{shape[0]}x{shape[1]},{n_buckets} buckets,"
          f"{'+'.join(names)},sim_overlapped={overlapped},"
          f"sim_serial={no_overlap},program_finish={pipe.program_finish}")
    if rows is not None:
        rows.append({"shape": list(shape), "n_buckets": n_buckets,
                     "schedules": names, "sim_overlapped": int(overlapped),
                     "sim_serial": int(no_overlap)})
    assert overlapped < no_overlap, (
        f"pipelined NoC replay {overlapped} should beat the serial sum "
        f"{no_overlap}")


# ---------------------------------------------------------------------------
# measured mode: calibrated + DP + per-bucket codec vs greedy analytic,
# real jitted wall-clock on ≥8 host devices
# ---------------------------------------------------------------------------

MEASURE_WORLD = 8


def _sync_step_seconds(eng, mesh, axes, leaves, repeats=5):
    """Best-of-``repeats`` wall-clock of the engine's jitted bucketed sync."""
    import jax
    from jax.sharding import PartitionSpec as P

    spec = [P() for _ in leaves]
    fn = jax.jit(jax.shard_map(
        lambda tree: eng.sync(tree), mesh=mesh, in_specs=(spec,),
        out_specs=spec, check_vma=False, axis_names=frozenset(axes)))
    out = fn(leaves)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(leaves)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def measured_section(smoke: bool, rows=None) -> None:
    """The acceptance claim, measured: DP+calibrated+codec ≤ greedy+analytic.

    The measured tuner's candidate set CONTAINS the greedy analytic config
    (its own fallback/upper bound), so the selected configuration can never
    measure worse than it — the assert locks the selection logic, the
    printed speedup reports how much the search actually bought.
    """
    import jax
    import numpy as np

    from repro.core.calibrate import fit_link_params
    from repro.launch.mesh import make_mesh

    if len(jax.devices()) < MEASURE_WORLD:
        print(f"overlap/measured,skip,needs {MEASURE_WORLD} devices,")
        return
    shape = (MEASURE_WORLD,)
    axes = ("data",)
    mesh = make_mesh(shape, axes)

    d_model, n_layers, vocab = (256, 4, 4096) if smoke else (512, 8, 8192)
    specs = transformer_leaf_specs(d_model, n_layers, vocab)
    rng = np.random.default_rng(0)
    leaves = [jax.numpy.asarray(
        rng.normal(size=s.shape).astype(np.float32)) for s in specs]
    n_params = sum(s.size for s in specs)
    bwd_s = backward_seconds(n_params, 1024)

    # 1. calibrate: fit (alpha, hop, beta) from real jitted collectives
    fit = fit_link_params(shape=shape,
                          payload_elems=(1 << 12, 1 << 15, 1 << 17),
                          repeats=2)
    print(f"overlap/calibrated,{fit.link.name},"
          f"alpha={fit.link.alpha_s:.2e},bw={fit.link.bw_Bps:.3g},"
          f"residual={fit.residual:.2f}")

    # 2. the greedy analytic baseline: fixed bucket size, default link
    cfg_greedy = BSPConfig(schedule="auto", bucket_mb=4.0)
    eng_greedy = SS.SuperstepEngine(specs, cfg_greedy, shape,
                                    backward_s=bwd_s)

    # 3. the tuned contender: DP boundaries + calibrated link + per-bucket
    #    codec, schedules refined with a measured budget
    cfg_dp = BSPConfig(schedule="auto", bucket_mb="auto",
                       bucket_codec="auto", link=fit.link)
    eng_dp = SS.SuperstepEngine(specs, cfg_dp, shape, backward_s=bwd_s)

    def measure(schedule: str, payload_bytes: float) -> float:
        from repro.core.calibrate import _measure_collective
        per_rank = max(MEASURE_WORLD,
                       int(payload_bytes / 4) // MEASURE_WORLD
                       * MEASURE_WORLD)
        return _measure_collective(mesh, axes, shape, schedule, per_rank,
                                   repeats=2, inner=3)

    budget = 4 if smoke else 8
    eng_ref = eng_dp.refined(measure, measure_budget=budget)

    # 4. measure the full bucketed sync for every candidate; the tuner
    #    takes the measured argmin (greedy included — it is the fallback)
    candidates = {
        "greedy+analytic": eng_greedy,
        "dp+calibrated": eng_dp,
        "dp+calibrated+refined": eng_ref,
    }
    repeats = 3 if smoke else 5
    timed = {}
    for name, eng in candidates.items():
        timed[name] = _sync_step_seconds(eng, mesh, axes, leaves,
                                         repeats=repeats)
        print(f"overlap/measured_{name},{eng.n_buckets} buckets,"
              f"{'+'.join(eng.schedules)},"
              f"{'+'.join(eng.codec_names)},"
              f"wall={timed[name] * 1e3:.2f}ms")
    chosen = min(timed, key=timed.get)
    greedy_s = timed["greedy+analytic"]
    chosen_s = timed[chosen]
    speedup = greedy_s / max(chosen_s, 1e-12)
    print(f"overlap/measured_chosen,{chosen},"
          f"{chosen_s * 1e3:.2f}ms,speedup_vs_greedy={speedup:.2f}x")
    if rows is not None:
        rows.append({
            "world": MEASURE_WORLD,
            "link": {"alpha_s": fit.link.alpha_s, "hop_s": fit.link.hop,
                     "bw_Bps": fit.link.bw_Bps, "residual": fit.residual},
            "measured_s": timed,
            "chosen": chosen,
            "chosen_schedules": list(candidates[chosen].schedules),
            "chosen_codecs": list(candidates[chosen].codec_names),
            "speedup_vs_greedy": speedup,
        })
    assert chosen_s <= greedy_s, (
        f"measured selection broke: chose {chosen} at {chosen_s}s over "
        f"greedy+analytic at {greedy_s}s")
    print("overlap/measured_claim,ok,DP+calibrated selection wall-clock "
          "<= greedy+analytic")


def run(smoke: bool = False, measured: bool = False,
        out: str = "BENCH_overlap.json") -> None:
    results = {"cells": [], "noc": [], "measured": []}
    print("overlap/cell,buckets,schedules,codecs,predicted,baseline,gain")
    cells = CELLS[:1] if smoke else CELLS
    bucket_mbs = (None, 64.0, "auto") if smoke else BUCKET_MBS
    wins = [sweep_cell(*cell, bucket_mbs=bucket_mbs, rows=results["cells"])
            for cell in cells]
    assert any(wins), (
        "expected ≥1 cell where the overlap-aware predicted step time "
        "is strictly below the no-overlap sum")
    print("overlap/claim,ok,overlap-aware predicted step time < "
          "no-overlap sum")
    noc_replay_section(payload_flits=512 if smoke else 2048,
                       rows=results["noc"])
    if measured:
        measured_section(smoke, rows=results["measured"])
    if out:
        with open(out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"overlap/json,written,{out}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one-cell sweep for CI")
    ap.add_argument("--measured", action="store_true",
                    help="calibrate + measure real jitted configs "
                         "(needs ≥8 devices)")
    ap.add_argument("--devices", type=int, default=0,
                    help="host-device override (set before jax init)")
    ap.add_argument("--out", default="BENCH_overlap.json",
                    help="machine-readable results path ('' disables)")
    args = ap.parse_args(argv)
    if args.devices:
        import os
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))
    run(smoke=args.smoke, measured=args.measured, out=args.out)


if __name__ == "__main__":
    main()
