"""The benchmark's cells cut to a size a CPU test holds: same drivers,
same program paths, same checks, with tiny widths and the limits the
tiny sizes read (the chip's limits are in the configuration files)."""

from __future__ import annotations

import json
import time
from typing import Any, Dict

from bench.harness import model as M
from bench.harness import spec

TINY_MODEL = dict(hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  num_hidden_layers=2, vocab_size=512)

# tiny readings on the CPU: program ~0.004, fp8 control ~0.05 (serve);
# program <1e-4 on every train number, bf16 control and the faults far
# above (see test_bench_cells.py)
SERVE_LIMITS = {"sample_requests": 4, "served_logit_gap": 0.02}
TRAIN_LIMITS = {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
                "delta_norm_gap": 1e-3}


def serve_cell() -> spec.Cell:
    cell = spec.resolve("serve.phi4.chat")
    c = M.replace_sizes(cell.config, **TINY_MODEL,
                        engine={"max_slots": 4, "max_len": 256,
                                "prefill_chunk": 32, "kv_blocks": 64,
                                "block_size": 16})
    c["correct"] = dict(c["correct"], **SERVE_LIMITS)
    mix = dict(cell.traffic, warmup_s=1.0,
               arrival={"process": "poisson", "rate_per_s": 4.0},
               prompt_len={"dist": "lognormal", "median": 40, "sigma": 0.6,
                           "min": 8, "max": 200},
               output_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                           "min": 2, "max": 40})
    return spec.Cell(cell.name, 1, c, mix, cell.end_to_end, cell.per_layer)


def train_cell(chips: int) -> spec.Cell:
    """The BSP training path of ``bench/configs/qwen2.5-3b-bsp.json`` and
    ``bench/traffic/lm_2k_dp<chips>.json``, cut to tiny widths."""
    with open(spec.BENCH / "configs" / "qwen2.5-3b-bsp.json") as f:
        c = M.replace_sizes(json.load(f), **TINY_MODEL)
    c["correct"] = dict(c["correct"], **TRAIN_LIMITS)
    with open(spec.BENCH / "traffic" / f"lm_2k_dp{chips}.json") as f:
        mix = dict(json.load(f), seq_len=64, batch_per_chip=2)
    return spec.Cell(f"train.qwen.dp{chips}", chips, c, mix, [], [])


def drive(cell: spec.Cell, seed: int, seconds: float = 2.0, fault=None,
          devices=None, workdir="/tmp") -> Any:
    """One run of the cell's driver, past the harness's look for a chip."""
    import jax
    return spec.driver(cell.config).run(
        cell, devices=devices or jax.devices()[:cell.chips], seed=seed,
        seconds=seconds, trace=False, process_start=time.monotonic(),
        workdir=workdir, fault=fault)


def readings(run) -> Dict[str, float]:
    return {k: v["value"] for k, v in run.checks.items()}
